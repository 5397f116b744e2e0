"""Reader ``host_issue_rest``: the host time of a traced window that is
not inside a span matching ``inside``, in us, from the issuing thread's
events (``harness/hostspans``).

For each selected point, over its traced windows: the durations of the
``bench.issue.<point>`` spans less the outermost spans matching
``inside`` within them, over the calls issued (k a window) times the
point's column ``over_field`` where one is given (``collectives_per_call``:
the result is then a bucket's share of a step).  With ``inside`` the
launch event, what is left is everything a call costs the host outside
its launches: the program's own Python, the harness's loop, and the
release of outputs dropped.  The metric is the geometric mean over the
points.  ``inside`` matching nothing in a point's windows: nothing to
read.

params: ``inside`` (a regular expression), ``over_field`` (optional),
``select``/``exclude``, ``table`` (per point, to
``.bench_out/<cell>.<table>.json``)."""
import re

from harness import hostspans, readerkit, stats
from harness import tracered as tr


def read(ctx, params):
    rows = readerkit.select(ctx["points"], params)
    run = hostspans.run_of(ctx, __file__) if rows and ctx.get("trace") \
        else None
    if run is None:
        return None
    inside = re.compile(params["inside"])
    table = []
    for r in rows:
        issues = [n for n in run.issues if n.name == tr.ISSUE + r["name"]]
        spans = [s for n in issues for s in n.outermost(inside)]
        units = len(issues) * r["k"] * (r.get(params["over_field"], 0)
                                        if params.get("over_field") else 1)
        if not spans or not units:
            print(f"host_issue_rest {params['table']}: {r['name']}: "
                  f"{len(issues)} traced windows, {len(spans)} spans "
                  f"matching {inside.pattern!r}", flush=True)
            return None
        issue_ns = sum(n.dur for n in issues)
        inside_ns = sum(s.dur for s in spans)
        table.append({"point": r["name"], "k": r["k"],
                      "windows": len(issues), "units": units,
                      "spans_inside": len(spans),
                      "issue_us_per_unit": issue_ns / units / 1e3,
                      "inside_us_per_unit": inside_ns / units / 1e3,
                      "rest_us_per_unit": (issue_ns - inside_ns) / units
                      / 1e3})
    hostspans.write_table(ctx, __file__, params["table"], table)
    values = [t["rest_us_per_unit"] for t in table]
    if any(v <= 0 for v in values):
        return None
    return stats.geomean(values)
