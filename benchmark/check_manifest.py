#!/usr/bin/env python3
"""Check BENCHMARK.json and every file it names against the manifest's
rules, by code and not by eye.

    python3 benchmark/check_manifest.py

Exit 0 and one line when every rule holds; else every failure on a line
of its own and exit 1.  Run it before anything goes to the chip and again
as the last step of a PR: one field of one entry lost PR 22 whole.  The
rules are in ``harness/manifest.py``."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import manifest as mf  # noqa: E402


def main(root: str = mf.REPO_ROOT) -> int:
    path = os.path.join(root, "BENCHMARK.json")
    manifest = mf.load(root)
    errors = mf.validate(manifest, root, raw_bytes=os.path.getsize(path))
    if not errors:
        errors = mf.validate_harness(manifest, root)
    for e in errors:
        print("manifest: " + e)
    if errors:
        return 1
    four = [w["name"] for w in manifest["workloads"] if w["chips"] == 4]
    print(f"manifest ok: {len(manifest['configs'])} configurations, "
          f"{len(manifest['workloads'])} cells (4 chips: {', '.join(four)}), "
          f"{len(manifest['end_to_end'])} end-to-end and "
          f"{len(manifest['per_layer'])} per-layer metrics, "
          f"run_seconds {manifest['run_seconds']}; sources "
          + ", ".join(str(len(c["source"])) for c in manifest["configs"])
          + " characters")
    return 0


if __name__ == "__main__":
    sys.exit(main())
