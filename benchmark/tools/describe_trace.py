#!/usr/bin/env python3
"""Look at a trace by hand before trusting a reduction of it.

    python3 benchmark/tools/describe_trace.py <cell> [--events out.json]

Prints every plane and line of the newest trace a ``--trace 1`` run of
``<cell>`` left under ``.bench_out/trace/<cell>/``, with the commonest
event names, then the reduction.  ``--events`` also writes the neutral
event form (``harness/tracered.py``), from which ``tests/fixtures`` are
cut."""
import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

from harness import tracered  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--events")
    args = ap.parse_args()
    path = tracered.find_xplane(os.path.join(
        os.path.dirname(BENCH_DIR), ".bench_out", "trace", args.cell))
    print(path, os.path.getsize(path), "bytes")
    for row in tracered.describe_xplane(path):
        print(json.dumps(row))
    events = tracered.load_xplane(path)
    with open(os.path.join(os.path.dirname(BENCH_DIR), ".bench_out", "trace",
                           args.cell, "calls.json"), encoding="utf-8") as f:
        said = json.load(f)
    # {"calls": {point: k}, "programs": {point: programs a call}} since
    # PR 32; before it the file was the calls alone
    events["calls"] = said.get("calls", said)
    print("programs a call, as the trace showed them:",
          json.dumps(tracered.programs_per_call(events)))
    if args.events:
        with open(args.events, "w", encoding="utf-8") as f:
            json.dump(events, f)
    reduced = tracered.reduce_trace(events)
    reduced["device_ops"] = reduced["device_ops"][:20]
    print(json.dumps(reduced, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
