#!/usr/bin/env python3
"""Run one cell of the benchmark once, in this process.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Needs the chips the cell asks for, every one a TPU; anywhere else it
exits non-zero before any work and prints no result.  Everything a cell
is made of is data found by name from ``BENCHMARK.json``: its
configuration (``configs/``), its traffic mix (``traffic/``), its own
parameters (``cells/``), its metrics (``metrics/``, each naming a reader
in ``readers/``), and the call kinds its points name (``kinds/``).  The
timing protocol is ``harness/protocol.py``'s, the same for every cell.

Earlier lines of stdout: the per-point table (``point {...}``) and facts
of the run (``run {...}``, with the set-up's phases; ``setup_s`` counts
from the open TPU to the first call that could be timed, see ``run_cell``;
the harness's own check against the reference follows it as ``check_s``).
Last line: the one JSON object of the contract.  ``--trace 0`` reports
the cell's end-to-end metrics; ``--trace 1`` alternates the framework's
windows with the raw twin's at the points where a metric of the cell
reads one, then profiles a few whole rounds, and reports the per-layer
metrics and a breakdown.  The full rows also go to
``<checkout>/.bench_out/``.
"""
import time

T_START = time.perf_counter()       # the process, as near as Python sees it

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)
for _p in (BENCH_DIR, CHECKOUT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import manifest as mf  # noqa: E402

OUT_NAME = ".bench_out"         # under the checkout; .gitignore lists it


def require_devices(platform: str, chips: int) -> list:
    """``jax.devices()``: exactly the chips the cell asks for, every one
    of ``platform``, or exit non-zero naming what was found.  Sets no
    platform itself."""
    import jax

    devs = jax.devices()
    found = sorted({d.platform for d in devs})
    if found != [platform] or len(devs) != chips:
        raise SystemExit(
            f"benchmark: the cell needs {chips} {platform} device(s); jax "
            f"found {len(devs)} of platform(s) {found}. Nothing was run.")
    return devs


def boot(devs):
    """The normal entry, and what ``chip_smoke.boot`` requires of it."""
    import ompi_tpu
    from ompi_tpu.mca.coll.xla import XlaCollModule

    world = ompi_tpu.init()
    if not world.rte.is_device_world:
        raise SystemExit(f"benchmark: init() booted "
                         f"{type(world.rte).__name__}, not the "
                         "single-process device world")
    if world.size != len(devs):
        raise SystemExit(f"benchmark: world.size {world.size} != "
                         f"{len(devs)} devices")
    owner = world.c_coll["allreduce_array"].__self__
    if not isinstance(owner, XlaCollModule):
        raise SystemExit(f"benchmark: allreduce_array is owned by "
                         f"{type(owner).__name__}, not XlaCollModule")
    return world


def memory_peak(devs) -> int:
    """Peak bytes in use on the fullest chip, as the backend reports it
    (0 where it reports nothing, as on the CPU)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             platform: str = "tpu", root: str = CHECKOUT,
             min_window_s: float | None = None) -> dict:
    """One run of one cell; returns the object of the last line.

    ``platform``, ``root`` and ``min_window_s`` are for the benchmark's
    own tests (a rehearsal at tiny sizes on virtual CPU devices, a
    throw-away cell in a temporary directory); the command passes none
    of them."""
    manifest = mf.load(root)
    bench_dir = os.path.join(root, manifest["paths"][0])
    out_dir = os.path.join(root, OUT_NAME)
    cell = mf.by_name(manifest["workloads"], workload, "workload")
    config = mf.load_json(os.path.join(root, mf.by_name(
        manifest["configs"], cell["config"], "config")["file"]))
    spec = mf.load_json(mf.data_file("cells", workload, bench_dir))
    wanted = mf.metrics_of(manifest, "per_layer" if trace else "end_to_end",
                           workload)

    if importlib.util.find_spec("ompi_tpu") is None:
        raise SystemExit("benchmark: the program (ompi_tpu) is not in this "
                         "directory. Nothing was run.")
    import jax
    import numpy as np

    # Opening the TPU is neither the program's work nor the benchmark's,
    # and on the v5e machine it took 5 to 19 s, in steps of 3 s from one
    # run to the next (PERF.md section 6): a set-up time that held it
    # could not be told from noise.  So set-up counts from here, with the
    # program's own import inside it, and the two earlier phases are
    # printed on the run line.
    phases = {"imports_s": time.perf_counter() - T_START}
    t0 = time.perf_counter()
    devs = require_devices(platform, cell["chips"])
    phases["devices_s"] = time.perf_counter() - t0
    t_setup = time.perf_counter()
    import ompi_tpu

    from harness import counters as cn
    from harness import protocol as pt
    from harness import tracered

    phases["program_import_s"] = time.perf_counter() - t_setup
    if config.get("ranks") != len(devs):
        raise SystemExit(f"benchmark: configuration {cell['config']!r} "
                         f"states {config.get('ranks')} ranks, the cell "
                         f"{len(devs)} chips")
    # the sub-second collective programs are most of what a run builds:
    # cache them too, or every run compiles them again
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = cn.CompileCounters()

    t0 = time.perf_counter()
    world = boot(devs)          # places the compile cache itself
    init_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    env = pt.Env(world, devs)
    spc_start = cn.device_collectives()
    collectives = 0             # collective calls issued, by my count
    attempted = failed = 0

    traffic = mf.traffic_points(cell["traffic"], bench_dir)
    twins = mf.raw_points(manifest, workload, traffic, bench_dir) \
        if trace else ()
    points = [pt.PointRun(env, point, pt.load_kind(point, bench_dir),
                          seed, spec["pool_bytes_per_point"],
                          spec["pool_max"], want_raw=point["name"] in twins)
              for point in traffic]
    phases["inputs_s"] = time.perf_counter() - t0      # pools, bindings
    peak_after = {"inputs": memory_peak(devs)}     # the peak so far, bytes
    t0 = time.perf_counter()
    window_s = pt.MIN_WINDOW_S if min_window_s is None else min_window_s
    for pr in points:
        calls = pt.warm_and_calibrate(pr, window_s)
        collectives += (pr.bind_collectives
                        + calls * pr.collectives_per_call)

    def check_all(tag: int) -> None:
        nonlocal attempted, failed, collectives
        rng = np.random.default_rng([seed, tag])
        for pr in points:
            attempted += 1
            collectives += pr.collectives_per_call
            try:
                ok = pt.check(pr, rng)
            except Exception as e:      # a call that raises has failed
                print(f"check {pr.name}: raised {e!r}", flush=True)
                ok = False
            if not ok:
                failed += 1
                print(f"check {pr.name}: FAILED against the numpy "
                      "reference", flush=True)

    phases["warm_s"] = time.perf_counter() - t0        # first calls, k
    peak_after["warm"] = memory_peak(devs)
    # From the open TPU to the first call that could be timed.  The check
    # that follows is the benchmark's own work, a comparison against a
    # float32 reference partly on the host's shared cores (12-18 s of a
    # step cell's 35-45 s, and what made an unchanged tree's set-up read
    # 8-10% apart: PERF.md 5): no user of the system pays for it, so it is
    # reported beside set-up (check_s, harness.check_s) and not inside.
    setup_s = time.perf_counter() - t_setup
    cn.mark_setup()             # what the build metrics count ends here
    t0 = time.perf_counter()
    check_all(0)
    check_s = phases["check_s"] = time.perf_counter() - t0
    peak_after["check"] = memory_peak(devs)
    gc.collect()
    gc.freeze()                 # no full collection inside a window
    builds = compiles.builds
    setup_compile = compiles.as_dict()
    phases["from_process_start_s"] = time.perf_counter() - T_START

    t0 = time.perf_counter()
    pt.measure(points, seconds, seed, with_raw=trace)
    measured_s = time.perf_counter() - t0
    timed = {pr.name: len(pr.windows) for pr in points}
    if trace:
        log_dir = os.path.join(out_dir, "trace", workload)
        shutil.rmtree(log_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # every Python call is too much
        options.enable_hlo_proto = False
        jax.profiler.start_trace(log_dir, profiler_options=options)
        try:
            pt.measure(points, 0, seed + 1, rounds=spec["trace_rounds"])
        finally:
            jax.profiler.stop_trace()
    if compiles.builds != builds:
        raise SystemExit(
            f"benchmark: {compiles.builds - builds} program(s) were built "
            "inside the measured time; this run is not a measurement")
    reduced = None
    if trace:
        events = tracered.load_xplane(tracered.find_xplane(log_dir))
        events["calls"] = {pr.name: pr.k for pr in points}
        # how many programs one call launches is what the trace shows,
        # never what a kind says: a trace it cannot account for raises
        programs = tracered.programs_per_call(events)
        with open(os.path.join(log_dir, "calls.json"), "w",
                  encoding="utf-8") as f:         # for tools/describe_trace
            json.dump({"calls": events["calls"], "programs": programs}, f)
        reduced = tracered.reduce_trace(events)
        for pr in points:
            pr.programs_per_call = programs[pr.name]
    for pr in points:
        calls = len(pr.windows) * pr.k
        attempted += calls
        collectives += calls * pr.collectives_per_call
        # the tracer slows the host: traced windows stay out of the medians
        del pr.windows[timed[pr.name]:]
    check_all(1)

    spc_seen = cn.device_collectives() - spc_start
    if spc_seen != collectives:
        print(f"spc: device_collectives moved by {spc_seen}, the harness "
              f"issued {collectives} collective calls", flush=True)
    correct = failed == 0 and spc_seen == collectives

    rows = [pr.summary() for pr in points]
    run = {
        "workload": workload, "seed": seed, "seconds": seconds,
        "trace": int(trace), "setup_s": setup_s, "check_s": check_s,
        "init_s": init_s,
        "setup_phases": phases, "measured_s": measured_s, "hold": pt.HOLD,
        "min_window_s": window_s, "compile_s": setup_compile["compile_s"],
        "compile": setup_compile, "memory_peak_bytes": memory_peak(devs),
        "memory_peak_after": peak_after,
        "spc_device_collectives": spc_seen,
        "cache_dir": jax.config.jax_compilation_cache_dir,
    }
    for row in rows:
        print("point " + json.dumps(row), flush=True)
    print("run " + json.dumps(run), flush=True)

    ctx = {"points": rows, "run": run, "trace": reduced,
           "device_kind": devs[0].device_kind}
    metrics = {}
    for m in wanted:
        mspec = mf.metric_spec(m["name"], bench_dir)
        reader = pt.load_module("readers", mspec["reader"], bench_dir)
        value = reader.read(ctx, mspec.get("params", {}))
        if value is not None:       # nothing to read: leave it out
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": run["memory_peak_bytes"]}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {
            "device_ops": [list(kv) for kv in reduced["device_ops"][:10]],
            "idle_gaps": [list(kv) for kv in reduced["idle_gaps"][:10]]}
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(
            out_dir, f"{workload}.seed{seed}.trace{int(trace)}.json"),
            "w", encoding="utf-8") as f:
        json.dump({"run": run, "points": rows, "result": result,
                   "trace_points": reduced and reduced["points"],
                   "windows_us": {pr.name: [w / pr.k * 1e6
                                            for w, _ in pr.windows]
                                  for pr in points},
                   # the issue half of each window: a stall sits in it
                   # or in the closing sync
                   "issue_windows_us": {pr.name: [i / pr.k * 1e6
                                                  for _, i in pr.windows]
                                        for pr in points}}, f, indent=1)
    ompi_tpu.finalize()
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
