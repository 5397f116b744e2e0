#!/usr/bin/env python3
"""Read what the ``train_step`` kind's ``TOLERANCE`` is set from, at the
cell's own widths and on the cell's own batches, in one process and
outside any timed window: over some seeds, how far one optimiser step of
the **program** lies from the benchmark's plain float32 reference
(``harness/olmoekit``), and how far two **controls** do:

* ``control_bf16``: the reference itself computed throughout in
  bfloat16, the nearest precision below the one the configuration
  states (parameters, residual stream, norms, softmaxes, router and
  loss), compared as the program is with the whole float32 model;
* ``control_parts``: each float32 part of the step (the router's
  matmul, its softmax, the head's logits and rows) as a bfloat16
  implementation of that part alone would have made it from the step's
  own inputs to it, compared as the program's parts are
  (``olmoekit.precision_want``).

Both have to lie outside the tolerance, the program inside.

    python3 benchmark/tools/train_check.py --seeds 8 [--base N]

Per seed: fresh parameters and a batch drawn as a run of that seed draws
them; one step of the program (every leaf reported, not only the kind's
``CHECKED``), its state freed after the step so that the reference's
whole gradient (2.5 GB) and its dense experts fit; then the reference in
float32 and in bfloat16, both under the program's routing, as in a run
(``slots_routed_otherwise`` counts the token-slots the float32
reference's own top 8 would have sent elsewhere).  Printed per seed and
in all, for each of the three: the widest deviation of what the kind
compares, in units of its tolerance (``|got - want| / (atol + rtol
|want|)``: at most 1 passes), by group, and the positions over it; and
beyond what a run compares: the gradient's global norm, 64 entries of
every leaf's gradient in units of its RMS, and the parameters after the
update at the same positions in units of the first step's learning rate
(entries whose gradient is under a quarter of the leaf's RMS are left
out: AdamW's first step moves an entry by the whole rate on its
gradient's sign, which bfloat16's noise of up to 0.12 RMS may turn).
``--warm 16,150`` instead follows one trainer a seed as a run does (a
pool of 16 batches walked in order, the warm-up's learning rates) and
compares its step after that many steps, on an entry of the pool, with
the reference from the parameters it had then, as a run's own two checks
do (the first after the pool's 16 warm steps, the second after the timed
ones); ``--checked a,b`` reads other leaves than the kind's.
``--routing 40`` times that many steps twice, on the parameters as drawn
(whose routing is collapsed: PERF.md section 5) and with attention's
output projection at zero, so that a token's routing follows its own
embedding and the loads are near even, and then profiles four steps of
each: step time, the fullest expert's load over the mean, and the device
milliseconds a step in the grouped matmuls and in all ops.
Needs the cell's chip, like ``run.py``; ``--platform cpu`` and
``--root`` are for the test that keeps it."""
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

CELL = "olmoe-train-1chip"
POOL = 16               # the cell's pool of batches


def widest(got, want, tol) -> tuple:
    """(the largest deviation in units of the tolerance, positions over
    it, positions) of one compared array."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    units = np.abs(got - want) / (tol["atol"] + tol["rtol"] * np.abs(want))
    return float(units.max()), int((units > 1).sum()), int(units.size)


def _open(platform, root, workload):
    from harness import protocol as pt

    manifest = mf.load(root)
    bench_dir = os.path.join(root, manifest["paths"][0])
    cell = mf.by_name(manifest["workloads"], workload, "workload")
    devs = run.require_devices(platform, cell["chips"])
    world = run.boot(devs)
    (point,) = mf.traffic_points(cell["traffic"], bench_dir)
    return pt.Env(world, devs), point, bench_dir


def _batches(env, kind, point, seed):
    """``batch(i)``: the i-th batch a run of ``seed`` draws."""
    import jax

    from harness import data

    gen = env.generator(kind.input_shape(point, env.n), point["dtype"],
                        "SUM", kind.input_sharding(env), None)
    key = jax.random.fold_in(jax.random.PRNGKey(seed),
                             data.stable_hash(point["name"]))
    return lambda i: kind.prepare(env, point, gen(
        jax.random.fold_in(key, i) if i else key))


def read(seeds: list, platform: str = "tpu", root: str = run.CHECKOUT,
         workload: str = CELL) -> list:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import ompi_tpu
    from ompi_tpu.parallel.train import (build_train_step,
                                         init_model_params,
                                         load_model_config)

    from harness import olmoekit
    from harness import protocol as pt

    env, point, bench_dir = _open(platform, root, workload)
    rows = []
    try:
        leaves = olmoekit.LEAVES
        built = None
        for seed in seeds:
            kind = pt.load_kind(point, bench_dir)   # a module a seed
            path = kind.config_path(point)
            cfgd = olmoekit.load_config(path)
            tokens, labels = _batches(env, kind, point, seed)(0)
            cfg = load_model_config(path)
            if built is None:
                built = build_train_step(*kind._mesh(env), model=cfg)
            step, place = built
            pseed = kind._RUN["seed"] & 0x7FFFFFFF
            row = {"seed": seed}
            state, tk, lb = place(init_model_params(cfg, pseed), tokens,
                                  labels)
            state, aux = step(state, tk, lb)
            aux = jax.device_get(aux)
            del state
            params = init_model_params(cfg, pseed)
            routed = aux["experts"]
            low = olmoekit.reference_step(
                jax.tree.map(lambda a: a.astype(jnp.bfloat16), params),
                tokens, labels, cfgd, leaves, routed=routed)
            low.pop("grads")
            low = jax.device_get(low)
            ref = olmoekit.reference_step(params, tokens, labels, cfgd,
                                          leaves, routed=routed)
            free = jax.device_get(olmoekit.reference_step(
                params, tokens, labels, cfgd, ())["loads"])
            row["slots_routed_otherwise"] = int(np.abs(
                free - aux["loads"]).sum() // 2)
            new = {n: olmoekit.adamw_leaf(
                n, olmoekit.leaf_of(params, n), ref["grads"][n], cfgd
            ).reshape(-1)[olmoekit.probe_positions(
                n, olmoekit.leaf_of(params, n).size)] for n in leaves}
            new = np.stack([jax.device_get(new[n]) for n in leaves])
            ref = jax.device_get({k: v for k, v in ref.items()
                                  if k != "grads"})
            gnorm = float(np.sqrt(sum(ref["grad_sq"].values())))
            parts = [olmoekit.precision_want(
                aux, olmoekit.leaf_of(params, "router"), params["head"],
                labels, cfgd, lowered) for lowered in (False, True)]
            del params
            want = {**olmoekit.compared(ref, cfgd, leaves), **parts[0]}
            checked = [leaves.index(n) for n in kind.CHECKED]
            live = np.abs(want["grad_probe"]) * olmoekit.PROBE_UNIT > 0.25
            for name, got in (
                    ("program", {
                        **olmoekit.compared(olmoekit.step_stats(aux), cfgd,
                                            leaves),
                        **olmoekit.precision_got(aux, cfgd)}),
                    ("control_bf16", olmoekit.compared(low, cfgd, leaves)),
                    ("control_parts", parts[1])):
                cmp = {}
                for key_ in got:
                    g, w = got[key_], want[key_]
                    if key_.startswith("grad_"):
                        g, w = g[checked], w[checked]
                    cmp[key_] = widest(g, w, kind.TOLERANCE)
                row[name] = {
                    "units_by_group": {k: v[0] for k, v in cmp.items()},
                    "widest_units": max(v[0] for v in cmp.values()),
                    "outside": sum(v[1] for v in cmp.values()),
                    "positions": sum(v[2] for v in cmp.values())}
                if "losses" in got:
                    row[name].update({
                        "losses": [float(x) for x in got["losses"]],
                        "losses_dev": [float(x) for x in np.abs(
                            got["losses"] - want["losses"])],
                        "grad_probe_dev_in_rms": {
                            n: float(olmoekit.PROBE_UNIT * np.abs(
                                got["grad_probe"][i]
                                - want["grad_probe"][i]).max())
                            for i, n in enumerate(leaves)}})
            row["program"]["grad_norm_rel"] = abs(float(np.sqrt(
                aux["grad_sq"].sum())) - gnorm) / gnorm
            row["program"]["param_dev_in_lr"] = float((np.abs(
                aux["param_probe"] - new)[live]
                / olmoekit.first_lr(cfgd)).max())
            row["reference_losses"] = [float(x) for x in ref["losses"]]
            rows.append(row)
            print("seed " + json.dumps(row), flush=True)
    finally:
        ompi_tpu.finalize()
    return rows


def read_warm(seeds: list, warm: list, platform: str = "tpu",
              root: str = run.CHECKOUT, workload: str = CELL,
              checked: tuple = ()) -> list:
    import numpy as np
    import ompi_tpu

    from harness import protocol as pt

    env, point, bench_dir = _open(platform, root, workload)
    rows = []
    try:
        for seed in seeds:
            kind = pt.load_kind(point, bench_dir)
            kind.CHECKED = tuple(checked) or kind.CHECKED
            batch = _batches(env, kind, point, seed)
            pool = [batch(i) for i in range(POOL)]
            call, _ = kind.bind(env, point, pool[0])
            done = 0
            for at in warm:
                while done < at:
                    call(pool[0])       # the kind walks its pool
                    done += 1
                given = pool[(seed + at) % POOL]
                xs = kind.inputs_of(given)
                got = [np.asarray(a) for a in call(given)]
                done += 1
                want = kind.reference(point, env.n, [np.asarray(a)
                                                     for a in xs])
                names = kind.OUTPUTS + kind.PRECISION
                cmp = {k: widest(g, w, kind.TOLERANCE)
                       for k, g, w in zip(names, got, want)}
                row = {"seed": seed, "steps_before": at,
                       "units_by_group": {k: v[0] for k, v in cmp.items()},
                       "widest_units": max(v[0] for v in cmp.values()),
                       "outside": sum(v[1] for v in cmp.values()),
                       "losses": [float(x) for x in got[0]]}
                rows.append(row)
                print("warm " + json.dumps(row), flush=True)
            kind._RUN.clear()
            del call
    finally:
        ompi_tpu.finalize()
    return rows


def read_routing(seed: int, steps: int, platform: str = "tpu",
                 root: str = run.CHECKOUT, workload: str = CELL,
                 traced: int = 4) -> list:
    import re
    import tempfile
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    import ompi_tpu

    from harness import protocol as pt
    from harness import tracered

    env, point, bench_dir = _open(platform, root, workload)
    rows = []
    try:
        for routing in ("as_drawn", "even"):
            kind = pt.load_kind(point, bench_dir)
            batch = _batches(env, kind, point, seed)
            pool = [batch(i) for i in range(4)]
            call, _ = kind.bind(env, point, pool[0])
            held = kind._RUN["held"]
            if routing == "even":
                params, *rest = held["state"]
                held["state"] = ({**params, "layers": {
                    **params["layers"],
                    "wo": jnp.zeros_like(params["layers"]["wo"])}}, *rest)
            for i in range(3):
                out = call(pool[0])     # the kind walks its pool
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for i in range(steps):
                out = call(pool[0])
            jax.block_until_ready(out)
            step_ms = (time.perf_counter() - t0) / steps * 1e3
            loads = np.asarray(held["aux"]["loads"])
            row = {"routing": routing, "seed": seed, "steps": steps,
                   "step_ms": step_ms,
                   "imbalance": float(loads.max() / loads.mean()),
                   "loss": float(np.asarray(held["aux"]["losses"])[0])}
            with tempfile.TemporaryDirectory() as tmp:
                with jax.profiler.trace(tmp):
                    for i in range(traced):
                        out = call(pool[0])
                    jax.block_until_ready(out)
                ops = next(iter(tracered.load_xplane(
                    tracered.find_xplane(tmp))["device"].values()), [])
            by_op: dict = {}
            for name, _, ns in ops:
                by_op[name] = by_op.get(name, 0) + ns / traced / 1e6
            if ops:     # no device plane on the CPU
                row["device_ms"] = tracered.total(tracered.merge(
                    (s, s + d) for _, s, d in ops)) / traced / 1e6
                row["ragged_dot_ms"] = sum(
                    ms for n, ms in by_op.items()
                    if re.match("ragged-dot", n))
                row["top_ops_ms"] = sorted(
                    ((n, round(ms, 3)) for n, ms in by_op.items()),
                    key=lambda kv: -kv[1])[:24]
            rows.append(row)
            print("routing " + json.dumps(row), flush=True)
            kind._RUN.clear()
            del call, held, out
    finally:
        ompi_tpu.finalize()
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=8)
    ap.add_argument("--base", type=int, default=3300002000)
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--root", default=run.CHECKOUT)
    ap.add_argument("--warm", default="")
    ap.add_argument("--checked", default="")
    ap.add_argument("--routing", type=int, default=0)
    args = ap.parse_args()
    seeds = [args.base + i for i in range(args.seeds)]
    if args.routing:
        read_routing(args.base, args.routing, args.platform, args.root)
        return 0
    if args.warm:
        rows = read_warm(seeds, [int(w) for w in args.warm.split(",")],
                         args.platform, args.root,
                         checked=tuple(filter(None,
                                              args.checked.split(","))))
        for at in sorted({r["steps_before"] for r in rows}):
            units = [r["widest_units"] for r in rows
                     if r["steps_before"] == at]
            print(f"program after {at} steps: over {len(units)} seeds the "
                  f"widest deviation of what a run compares is "
                  f"{min(units):.3f} to {max(units):.3f} of the tolerance")
        return 0
    rows = read(seeds, args.platform, args.root)
    for name in ("program", "control_bf16", "control_parts"):
        units = [r[name]["widest_units"] for r in rows]
        worst = rows[0][name]
        groups = {g: [r[name]["units_by_group"][g] for r in rows]
                  for g in worst["units_by_group"]}
        print(f"{name}: over {len(rows)} seeds the widest deviation of what "
              f"a run compares is {min(units):.3f} to {max(units):.3f} of "
              "the tolerance (by group, least to most over the seeds: "
              + json.dumps({g: [round(min(u), 4), round(max(u), 4)]
                            for g, u in groups.items()})
              + f"); positions outside {[r[name]['outside'] for r in rows]}"
              f" of {rows[0][name]['positions']}"
              + ("" if "grad_probe_dev_in_rms" not in worst else
                 "; gradient entries off by at most " + format(max(max(
                     r[name]["grad_probe_dev_in_rms"].values())
                     for r in rows), ".3f") + " RMS")
              + ("" if "grad_norm_rel" not in worst else
                 "; gradient norm by at most "
                 f"{max(r[name]['grad_norm_rel'] for r in rows):.2e}; "
                 "updated parameters by at most "
                 f"{max(r[name]['param_dev_in_lr'] for r in rows):.3f} lr"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
