#!/usr/bin/env python3
"""Read what the ``train_step_kit`` kind's ``TOLERANCE`` rests on, at a
cell's own widths and on the cell's own batches, in one process and
outside any timed window: ``tools/share_check.py`` for **any kit** the
cell's configuration names.  Over some seeds, how far one optimiser step
of the **program** lies from the kit's plain float32 reference, and how
far the **controls** do, each in units of the tolerance (``|got - want| /
(atol + rtol |want|)``: at most 1 passes):

* ``control_bf16``: the reference itself computed throughout in bfloat16
  (parameters, residual stream, norms, the state-space recurrence,
  softmax, router, loss), compared as the program is with the whole
  float32 model, under the program's routing;
* ``parts_<variant>`` for every variant of the kit's ``PART_CONTROLS``:
  each float32 part of the step as a wrong implementation of that part
  alone would have made it from the step's own inputs to it (for
  ``nemotronkit``: ``bf16`` a bfloat16 router and head, ``scan_bf16`` the
  scan's decay and state held in bfloat16, ``bias_in_weights`` weights
  taken from score + bias, ``softmax`` in the sigmoid's place), compared
  as the program's parts are.

Every control has to lie outside the tolerance, the program inside: the
last line gives the program's widest deviation over the seeds and each
control's **narrowest**.

    python3 benchmark/tools/kit_check.py --workload nemotron3-train-1chip \\
        --seeds 6 [--base N]

Per seed, as a run of that seed: fresh parameters, the pool's first
batch, ``--warm`` steps on the pool's batches (default 4, so that the
balancing biases are no longer zero and a control that puts them into the
weights can differ), then the kind's own checked step and reference.
Needs the cell's chip, like ``run.py``; ``--platform cpu`` and ``--root``
are for a rehearsal."""
import argparse
import gc
import json
import os
import pickle
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.dirname(os.path.abspath(__file__)), BENCH_DIR,
           os.path.dirname(BENCH_DIR)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run  # noqa: E402


def read(seeds, warm, workload, platform="tpu", root=run.CHECKOUT,
         dump=None):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import ompi_tpu

    import train_check
    from harness import protocol as pt

    env, point, bench_dir = train_check._open(platform, root, workload)
    rows = []
    try:
        for seed in seeds:
            kind = pt.load_kind(point, bench_dir)   # a module a seed
            batch = train_check._batches(env, kind, point, seed)
            first = batch(0)
            pool = [first] + [batch(i) for i in range(1, max(2, warm))]
            call, _ = kind.bind(env, point, first)
            for _ in range(warm):
                jax.block_until_ready(call(first))  # walks the pool
            kind._RUN["keep_last"] = True
            xs = [np.asarray(a) for a in kind.inputs_of(pool[-1])]
            call(pool[-1])
            kind.reference(point, env.n, xs)
            held, kit, cfg, wrt = (kind._RUN[k] for k in (
                "held", "kit", "cfg", "checked"))
            got, last, aux = held["got"], held["last"], held["aux"]
            want = last["want"]
            bias_after = jax.device_get(held["state"][4]) if dump else None
            # the trainer's state has done its step: the bfloat16
            # reference does not fit beside it and the float32 copy
            for a in jax.tree.leaves(held.pop("state")):
                a.delete()
            low = jax.device_get({k: v for k, v in kit.reference_step(
                jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                             last["params"]),
                last["tokens"], last["labels"], cfg,
                jax.device_put(last["bias"]), wrt,
                routed=aux["experts"]).items() if k != "grads"})
            held_experts = cfg["experts_here"] or cfg["n_routed_experts"]
            row = {"seed": seed, "local_load": float(
                aux["local_slots"] * cfg["n_routed_experts"]
                / (np.asarray(aux["loads"]).sum() * held_experts)),
                "losses": [float(x) for x in got["losses"]]}
            sides = {"program": got,
                     "control_bf16": kit.compared(low, cfg, wrt)}
            for variant in kit.PART_CONTROLS:
                sides["parts_" + variant] = kit.precision_want(
                    aux, last["by_name"], last["bias"]["layers"],
                    last["params"]["head"], xs[1], cfg, variant=variant)
            if dump:        # the raw statistics, for units set offline
                os.makedirs(dump, exist_ok=True)
                with open(os.path.join(dump, f"{workload}.{seed}.pkl"),
                          "wb") as f:
                    pickle.dump(jax.tree.map(np.asarray, {
                        "program": kit.step_stats(aux, bias_after, cfg),
                        "reference": last["out"],
                        "control_bf16": low, "wrt": list(wrt),
                        "parts_program": kit.precision_got(aux, cfg),
                        "parts_want": {k: want[k] for k in kit.PRECISION},
                    }), f)      # a part control's units scale as printed
            for name, side in sides.items():
                units = {k: float(np.max(np.abs(
                    np.float64(side[k]) - want[k]) / (
                    kind.TOLERANCE["atol"]
                    + kind.TOLERANCE["rtol"] * np.abs(want[k]))))
                    for k in side}
                row[name] = {"units_by_group": units,
                             "widest_units": max(units.values())}
            print("seed " + json.dumps(row), flush=True)
            rows.append(row)
            # the next seed's trainer does not fit beside this one's
            held.clear()
            kind._RUN.clear()
            del call, last, low, sides, got, want, aux, xs, pool, first
            gc.collect()
    finally:
        ompi_tpu.finalize()
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--base", type=int, default=2147584200)
    ap.add_argument("--warm", type=int, default=4)
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--root", default=run.CHECKOUT)
    ap.add_argument("--dump", help="a directory for each seed's raw "
                    "statistics (program, reference, controls), from "
                    "which a kit's units are set")
    args = ap.parse_args(argv)
    rows = read([args.base + i for i in range(args.seeds)], args.warm,
                args.workload, args.platform, args.root, args.dump)
    sides = [n for n in rows[0] if n.startswith(("program", "control",
                                                 "parts"))]
    # the program's widest over the seeds, each control's narrowest
    print("summary " + json.dumps({
        n: (max if n == "program" else min)(
            r[n]["widest_units"] for r in rows) for n in sides}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
