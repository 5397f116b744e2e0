#!/usr/bin/env python3
"""Read the two numbers a limit of ``correct`` is set from, in one
process: over a dozen seeds, the most positions at which the **program**
differs from the plain reference, and the fewest at which the
**control** does.

    python3 benchmark/tools/control.py --workload <cell> --seeds 12 [--base N]

The control is the reference put in the program's place and computed in
the nearest precision below the one the configuration states (bfloat16
for float32): the step that would tempt a later PR (a gradient rounded
to bfloat16 on its way).  A comparison that lets it through proves
nothing.  Inputs are made on the device from each seed at the cell's own
size, two sets a point; a long array is compared at the positions
``protocol.sample_positions`` draws, as in a run.  Points whose dtype has
no lower float precision (the bitwise ops' int32) have no control and
say so.  Needs the cell's chips, like ``run.py``; the benchmark's own
runs never run this.  ``--platform cpu`` is for the test that keeps it
(``tests/test_control.py``)."""
import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (BENCH_DIR, os.path.dirname(BENCH_DIR)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run  # noqa: E402
from harness import manifest as mf  # noqa: E402


def lower_of(dtype: str):
    """The nearest precision below ``dtype``, or None."""
    import jax.numpy as jnp

    return {"float32": jnp.bfloat16}.get(dtype)


def read(workload: str, seeds: list, platform: str = "tpu",
         root: str = run.CHECKOUT) -> dict:
    """{point: {"program": [bad a seed], "control": [...], "compared"}}"""
    import numpy as np
    import ompi_tpu

    from harness import protocol as pt

    manifest = mf.load(root)
    bench_dir = os.path.join(root, manifest["paths"][0])
    cell = mf.by_name(manifest["workloads"], workload, "workload")
    devs = run.require_devices(platform, cell["chips"])
    world = run.boot(devs)
    out: dict = {}
    try:
        env = pt.Env(world, devs)
        for seed in seeds:
            for point in mf.traffic_points(cell["traffic"], bench_dir):
                # a pool of two sets: bytes of 1 ask for the least
                pr = pt.PointRun(env, point, pt.load_kind(point, bench_dir),
                                 seed, 1, 2, want_raw=False)
                row = out.setdefault(point["name"], {
                    "program": [], "control": [], "compared": 0})
                bad, row["compared"] = pt.check_counts(
                    pr, np.random.default_rng([seed, 0]))
                row["program"].append(bad)
                low = lower_of(point["dtype"])
                if low is not None:
                    row["control"].append(pt.check_counts(
                        pr, np.random.default_rng([seed, 0]), low)[0])
                del pr
    finally:
        ompi_tpu.finalize()
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--base", type=int, default=3200002000)
    ap.add_argument("--platform", default="tpu")
    args = ap.parse_args()
    seeds = [args.base + i for i in range(args.seeds)]
    table = read(args.workload, seeds, args.platform)
    for name, row in table.items():
        control = (f"control (bfloat16) fewest {min(row['control'])}"
                   if row["control"] else "no control for its dtype")
        print(f"{name}: of {row['compared']} positions compared a seed, "
              f"program most {max(row['program'])}, {control}; "
              f"{len(seeds)} seeds")
    print(json.dumps({"seeds": seeds, "points": table}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
