"""Reader ``trace_op_busy_share``: the share, in %, of the selected points'
**device-busy seconds** that ran in ops matching ``pattern``.

``trace_op_share`` divides by the sum of every op's seconds, which counts a
loop twice where a program has loops (a ``while`` op's seconds hold its
body's ops', and those are listed too: a scanned stack of layers reads
about half its share there).  This one divides by ``busy_s``, the union of
the device's op intervals in the point's traced windows
(``tracered.reduce_trace``), which is the step's device time however the
program nests.  The pattern has to name leaf ops (a kernel, a grouped
matmul), not a loop.  No op matching, or no trace: nothing to read.

params: ``pattern``, ``select``/``exclude``, ``table`` (per point:
matching and busy device seconds, to ``.bench_out/<cell>.<table>.json``)."""
import re

from harness import hostspans, readerkit


def read(ctx, params):
    trace = ctx.get("trace")
    if not trace:
        return None
    pattern = re.compile(params["pattern"])
    table = []
    for row in readerkit.select(ctx["points"], params):
        seen = trace["points"].get(row["name"], {})
        table.append({"point": row["name"],
                      "matching_s": sum(s for n, s in seen.get(
                          "ops", {}).items() if pattern.search(n)),
                      "busy_s": seen.get("busy_s", 0.0)})
    matching = sum(t["matching_s"] for t in table)
    busy = sum(t["busy_s"] for t in table)
    if matching <= 0 or busy <= 0:
        return None
    hostspans.write_table(ctx, __file__, params["table"], table)
    return 100.0 * matching / busy
