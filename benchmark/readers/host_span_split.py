"""Reader ``host_span_split``: one part of a call on the host, in us,
from the host-thread events of the traced rounds (``harness/hostspans``).

For each selected point, over every call of its traced windows: the
duration of the spans matching ``part`` inside the call's one span
matching ``span`` (of that span itself where no ``part`` is given), less
what matches ``child`` inside them: a span's self time where ``child`` is
what it calls.  A point's value is the median over its calls; the metric
is the geometric mean over the points.  Every traced window must hold
exactly k spans matching ``span`` (k from the per-point table), else
nothing is read and the reason is printed: a count that is off means the
names moved, and a time read from the wrong spans is worse than none.
Where a call is a step of several launches and the span is one a launch
(``per`` ``"launch"``), a window must hold k times the point's programs a
call, as the trace itself showed them (``tracered.programs_per_call``),
and a point's value is the median over its launches.

params: ``span``, ``part`` and ``child`` (regular expressions, the last
two optional), ``per`` (``"call"``, the default, or ``"launch"``),
``select``/``exclude``, ``table`` (the full per-point
table goes to ``.bench_out/<cell>.<table>.json``, with the mean and the
harness's own issue time a call beside each median)."""
import re

from harness import hostspans, readerkit, stats


def read(ctx, params):
    rows = readerkit.select(ctx["points"], params)
    run = hostspans.run_of(ctx, __file__) if rows and ctx.get("trace") \
        else None
    if run is None:
        return None
    span, part, child = (re.compile(params[key]) if params.get(key) else None
                         for key in ("span", "part", "child"))
    table = [hostspans.split_point(run, r["name"], r["k"], span, part, child,
                                   params.get("per", "call"))
             for r in rows]
    refused = [t for t in table if isinstance(t, str)]
    if refused:
        print(f"host_span_split {params['table']}: {refused[0]}", flush=True)
        return None
    hostspans.write_table(ctx, __file__, params["table"], table)
    values = [t["median_us"] for t in table]
    if any(v <= 0 for v in values):
        print(f"host_span_split {params['table']}: a median is not above 0",
              flush=True)
        return None
    return stats.geomean(values)
