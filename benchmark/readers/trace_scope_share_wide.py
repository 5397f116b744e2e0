"""Reader ``trace_scope_share_wide``: ``trace_scope_share`` with a wider
vocabulary.

``harness/scopes.py`` drops from an op's scope chain every name that
``harness/scopes.json`` does not list, and that file repeats the leading
part of the program's ``trace.STEP_SCOPES``: the names a later PR adds to
the program stand behind it.  A metric that reads one of those lists them
in its own file (``vocabulary``), and this reader makes the same
reduction (``scopes.reduce_scopes``, which takes its vocabulary as an
argument) with ``scopes.json``'s names followed by the file's.  Every
metric of a run with the same points and vocabulary shares one
reduction.  No trace, a program that gives no map, or no selected point:
nothing to read.

params: ``vocabulary`` (names behind ``scopes.json``'s), then
``trace_scope_share``'s: ``select``/``exclude``; ``scopes`` and/or
``pass``, or ``marked``.  The table goes to
``.bench_out/<cell>.step.scopes.wide.json``."""
import os

from harness import hostspans, readerkit, scopes
from harness import tracered as tr

TABLE = "step.scopes.wide"
_loaded: dict = {}              # one run a process, as scopes._loaded


def table_of(ctx: dict, params: dict):
    points = sorted(r["name"] for r in readerkit.select(ctx["points"],
                                                        params))
    if not points or not ctx.get("trace"):
        return None
    extra = tuple(params.get("vocabulary", ()))
    key = (ctx["run"]["workload"], tuple(points), extra)
    if key in _loaded:
        return _loaded[key]
    table = None
    maps = scopes.program_maps()
    if maps:
        data = {**scopes.DATA, "scopes": list(scopes.DATA["scopes"]) + [
            s for s in extra if s not in scopes.DATA["scopes"]]}
        log_dir = os.path.join(hostspans._out_dir(__file__), "trace",
                               ctx["run"]["workload"])
        try:
            events = tr.load_xplane(tr.find_xplane(log_dir))
            events["calls"] = {r["name"]: r["k"] for r in ctx["points"]}
            table = scopes.reduce_scopes(events, maps, points, data=data)
        except (OSError, ValueError, KeyError, IndexError) as e:
            print(f"scopes: no device ops to read: {e}", flush=True)
    if table:
        hostspans.write_table(ctx, __file__, TABLE, table)
    _loaded.clear()
    _loaded[key] = table
    return table


def read(ctx, params):
    table = table_of(ctx, params)
    if table is None:
        return None
    wanted = set(params.get("scopes", ()))
    if wanted and not any(wanted & set(r["chain"]) for r in table["rows"]):
        return None         # the program has no such scope: nothing to read
    return scopes.share(table, params)
