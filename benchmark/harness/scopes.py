"""A train step's device time by the program's own scopes.

The step's parts are wrapped in ``jax.named_scope("otpu_*")``; JAX writes
the open scopes into every HLO instruction's ``op_name`` and XLA keeps the
root's on the fusions it forms.  The program reads that back from its own
compiled text: ``train.scopes_of_built_steps()`` gives, a step it built,

    {"module": "jit_otpu_train_step",
     "ops": {instruction: {"chain": [scope, ...], "pass": "forward" |
             "remat" | "backward" | "update" | None, "mixed": bool,
             "opcode": ...}}}

(``ompi_tpu/runtime/trace.scope_map``).  A device op of the profiler's
trace is named by its whole HLO line, of which ``tracered.short_op`` keeps
``<instruction> <type>``, and runs inside one event of the ``XLA Modules``
line, named ``<module>(<program id>)``: so the **neutral form already
holds the key**, and this module joins every op to its entry by the two
names.  (The v5e's op events carry no ``hlo_op`` / ``hlo_module`` stat and
``ProfileData`` does not give an event's metadata, where a ``tf_op`` stat
holds the root's path: ``tools/describe_ops.py`` prints both.)

Seconds are **self seconds**: the ops of one device nest by containment
(``hostspans.nest``: a ``while`` holds its body's ops), and an op's self
time is its duration less what its children cover.  A loop's seconds are
so counted once and go to the ops inside it; the self times of a window's
ops add up to the window's busy seconds, which is what
``tracered.reduce_trace`` calls the point's ``busy_s``.

Which names are scopes, which passes there are and which scopes make an
op the optimiser's are data (``scopes.json``, which repeats the program's
``trace.STEP_SCOPES``).  No map (the parent of the PR that added it, a
point that is no step, a CPU run of another kind): nothing is read.
"""
from __future__ import annotations

import bisect
import json
import os
import re
import resource
import time

from harness import hostspans
from harness import tracered as tr

TABLE = "step.scopes"           # .bench_out/<cell>.step.scopes.json
MAP_TABLE = "step.scope_map"    # the program's maps, for tools/describe_ops
TOP_OPS = 10

with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "scopes.json"), encoding="utf-8") as _f:
    DATA = json.load(_f)

_PROGRAM_ID_RE = re.compile(r"\(\d+\)$")
_loaded: dict = {}              # as hostspans._loaded: one run a process


def instruction_of(short_name: str) -> str:
    """``fusion.41`` of ``fusion.41 f32[65536,2048]``."""
    return short_name.split(" ", 1)[0].lstrip("%")


def module_of(run_name: str) -> str:
    """``jit_otpu_train_step`` of ``jit_otpu_train_step(21672562...)``."""
    return _PROGRAM_ID_RE.sub("", run_name)


def program_maps():
    """{module: its scope map} of the steps the program built and ran,
    or None with a printed reason: a program that has no such function
    (the parent commit), that built no step, or that could not compile
    one again.  Prints what the call cost."""
    try:
        from ompi_tpu.parallel import train

        ask = train.scopes_of_built_steps
    except (ImportError, AttributeError) as e:
        print(f"scopes: the program gives no scope map: {e}", flush=True)
        return None
    import jax

    def device_bytes():
        stats = jax.devices()[0].memory_stats() or {}
        return stats.get("bytes_in_use", 0), stats.get("peak_bytes_in_use", 0)

    t0 = time.perf_counter()
    rss0, (used0, peak0) = _max_rss(), device_bytes()
    try:
        maps = ask()
    except Exception as e:      # a second load that does not fit, ...
        print(f"scopes: scopes_of_built_steps() raised {e!r}", flush=True)
        return None
    used1, peak1 = device_bytes()
    print("scopes " + json.dumps({
        "steps": len(maps), "seconds": time.perf_counter() - t0,
        "ops": sum(len(m["ops"]) for m in maps),
        "host_max_rss_bytes": [rss0, _max_rss()],
        "device_bytes_in_use": [used0, used1],
        "device_peak_bytes_in_use": [peak0, peak1]}), flush=True)
    out: dict = {}
    for m in maps:
        if m["module"] in out:
            print(f"scopes: two steps are named {m['module']!r}; an op "
                  "cannot be told to either", flush=True)
            return None
        out[m["module"]] = m
    return out or None


def _max_rss() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def self_times(ops: list) -> list:
    """[(name, start ns, self ns)] of one device's ops ``[name, start_ns,
    dur_ns]`` nested by containment: a duration less what the ops inside
    it cover."""
    root, _ = hostspans.nest(ops)
    out, stack = [], list(root.children)
    while stack:
        node = stack.pop()
        out.append((node.name, node.start, node.self_ns()))
        stack += node.children
    return out


def reduce_scopes(events: dict, maps: dict, points: list = None,
                  data: dict = None, top_ops: int = TOP_OPS) -> dict:
    """Self seconds of the device ops inside the traced windows of
    ``points`` by (chain, pass), averaged over the devices, as the table
    that goes to ``.bench_out``: ms a step a row and its share of the
    busy time, each row's largest ops, and what was booked to a mixed
    fusion's root, took its scopes from a neighbour (``inherited``), had
    no scope even so (``unnamed``: of which the compiler's own, and
    those with no entry in the map).

    ``events`` is the neutral form with ``calls``; the windows are the
    ones ``tracered.reduce_trace`` reads (``device_windows``).  Without
    ``points`` (a trace of one's own, with no harness span in it) every
    run of a program that has a map is a step, and its ops are read."""
    data = data or DATA
    vocabulary, passes = set(data["scopes"]), set(data["passes"])
    if points is not None:
        windows = tr.windows_of(events["host"])
        programs = tr.window_programs(events)
    ndev = len(events["device"])
    steps = 0
    rows: dict = {}             # (chain, pass) -> [ns, mixed ns, {op: ns}]
    marked = {"mixed": {}, "inherited": {}, "unnamed": {}, "compiler": {},
              "no_entry": {}}
    mixes: dict = {}            # "scope:pass + scope:pass" -> ns
    busy_ns = 0
    for dev in sorted(events["device"], key=int):
        ops = events["device"][dev]
        starts = [o[1] for o in ops]
        runs = events["modules"][dev]
        run_starts = [r[1] for r in runs]
        if points is None:
            mine_windows = [(None, s, s + d) for name, s, d in runs
                            if module_of(name) in maps]
            steps += len(mine_windows)
        else:
            mine_windows = [w for w in tr.device_windows(
                windows, runs, programs) if w[0] in points]
            steps += sum(events["calls"][w[0]] for w in mine_windows)
        for _, lo, hi in mine_windows:
            mine = ops[bisect.bisect_left(starts, lo):
                       bisect.bisect_left(starts, hi)]
            busy_ns += tr.total(tr.merge((s, s + d) for _, s, d in mine))
            for name, start, ns in self_times(mine):
                # an op's program is the run it started in
                run = runs[max(0, bisect.bisect_right(run_starts, start) - 1)]
                entry = maps.get(module_of(run[0]), {"ops": {}})["ops"].get(
                    instruction_of(name))
                chain = tuple(s for s in (entry or {}).get("chain", ())
                              if s in vocabulary)
                which = (entry or {}).get("pass")
                which = which if which in passes else None
                row = rows.setdefault((chain, which), [0, 0, {}])
                row[0] += ns
                row[2][name] = row[2].get(name, 0) + ns
                kinds = [k for k, yes in (
                    ("mixed", entry and entry["mixed"]),
                    ("inherited", entry and entry.get("inherited")),
                    ("no_entry", entry is None),
                    ("compiler", entry is not None and which is None
                     and not chain),
                    ("unnamed", not chain)) if yes]
                if "mixed" in kinds:
                    row[1] += ns
                    mix = " + ".join(entry.get("kinds", ()))
                    mixes[mix] = mixes.get(mix, 0) + ns
                for kind in kinds:
                    marked[kind][name] = marked[kind].get(name, 0) + ns
    if not steps or busy_ns <= 0:
        return None
    per_step = lambda ns: ns / steps / 1e6      # ms a step, a device
    top = lambda by_op, n=top_ops: [[name, per_step(ns)] for name, ns in
                                    sorted(by_op.items(),
                                           key=lambda kv: -kv[1])[:n]]
    by_scope: dict = {}
    by_pass: dict = {}
    for (chain, which), (ns, _, _) in rows.items():
        by_pass[which or "none"] = by_pass.get(which or "none", 0) + ns
        for scope in chain:
            by_scope[scope] = by_scope.get(scope, 0) + ns
    return {
        "points": sorted(points or ()), "steps": steps // ndev,
        "devices": ndev,
        "busy_ms_per_step": per_step(busy_ns),
        "rows_ms_per_step": per_step(sum(r[0] for r in rows.values())),
        "rows": [{"chain": list(chain), "pass": which,
                  "ms_per_step": per_step(ns),
                  "share_pct": 100.0 * ns / busy_ns,
                  "mixed_ms_per_step": per_step(mixed),
                  "top_ops": top(by_op)}
                 for (chain, which), (ns, mixed, by_op) in sorted(
                     rows.items(), key=lambda kv: -kv[1][0])],
        # a scope's row holds every op with the scope anywhere in its
        # chain, so nested scopes' rows overlap; the passes' do not
        "by_scope_ms_per_step": {s: per_step(ns) for s, ns in sorted(
            by_scope.items(), key=lambda kv: -kv[1])},
        "by_pass_ms_per_step": {p: per_step(ns) for p, ns in sorted(
            by_pass.items(), key=lambda kv: -kv[1])},
        **{f"{kind}_ms_per_step": per_step(sum(by_op.values()))
           for kind, by_op in marked.items()},
        **{f"{kind}_top_ops": top(by_op, 2 * top_ops)
           for kind, by_op in marked.items()},
        # which scopes and passes the mixed fusions hold, largest first
        "mixed_by_kinds_ms_per_step": top(mixes, 2 * top_ops),
        "unknown_scopes": sorted({s for m in maps.values()
                                  for s in m.get("unknown", ())}),
    }


def share(table: dict, params: dict) -> float:
    """One metric of the table, in % of the busy time: the rows that
    have one of ``scopes`` in their chain and, if given, the ``pass``;
    or what is ``marked`` (``unnamed``: no scope in the path, or no
    entry; ``mixed``: booked to a mixed fusion's root)."""
    busy = table["busy_ms_per_step"]
    if "marked" in params:
        return 100.0 * table[params["marked"] + "_ms_per_step"] / busy
    wanted = set(params.get("scopes", ()))
    ms = sum(r["ms_per_step"] for r in table["rows"]
             if (not wanted or wanted & set(r["chain"]))
             and params.get("pass", r["pass"]) == r["pass"])
    return 100.0 * ms / busy


def table_of(ctx: dict, reader_file: str, params: dict):
    """The table of the run a reader is asked about, for the points its
    ``select`` names: made once a run, written to
    ``.bench_out/<cell>.step.scopes.json`` with the program's maps beside
    it, or None with a printed reason."""
    from harness import readerkit

    points = sorted(r["name"] for r in readerkit.select(ctx["points"],
                                                        params))
    if not points or not ctx.get("trace"):
        return None
    key = (ctx["run"]["workload"], tuple(points))
    if key in _loaded:
        return _loaded[key]
    table = None
    maps = program_maps()
    if maps:
        log_dir = os.path.join(hostspans._out_dir(reader_file), "trace",
                               ctx["run"]["workload"])
        try:
            events = tr.load_xplane(tr.find_xplane(log_dir))
            events["calls"] = {r["name"]: r["k"] for r in ctx["points"]}
            table = reduce_scopes(events, maps, points)
        except (OSError, ValueError, KeyError, IndexError) as e:
            print(f"scopes: no device ops to read: {e}", flush=True)
    if table:
        hostspans.write_table(ctx, reader_file, TABLE, table)
        hostspans.write_table(ctx, reader_file, MAP_TABLE, maps)
    _loaded.clear()             # one run a process: keep one, None too
    _loaded[key] = table
    return table
