"""Reader ``trace_op_share``: the share, in %, of the selected points'
device seconds that ran in ops matching ``pattern``, from the traced
rounds' per-point op table (``tracered.reduce_trace``: seconds by op name,
averaged over the devices).  A kernel that carries the program's name is
found by it whatever XLA numbers it.  No op matching: nothing to read.

params: ``pattern``, ``select``/``exclude``, ``table`` (per point:
matching and all device seconds, to ``.bench_out/<cell>.<table>.json``)."""
import re

from harness import hostspans, readerkit


def read(ctx, params):
    trace = ctx.get("trace")
    if not trace:
        return None
    pattern = re.compile(params["pattern"])
    table = []
    for row in readerkit.select(ctx["points"], params):
        ops = trace["points"].get(row["name"], {}).get("ops", {})
        table.append({"point": row["name"],
                      "matching_s": sum(s for n, s in ops.items()
                                        if pattern.search(n)),
                      "all_s": sum(ops.values())})
    matching = sum(t["matching_s"] for t in table)
    everything = sum(t["all_s"] for t in table)
    if matching <= 0 or everything <= 0:
        return None
    hostspans.write_table(ctx, __file__, params["table"], table)
    return 100.0 * matching / everything
