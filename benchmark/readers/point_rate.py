"""Reader ``point_rate``: a stated amount of work a call over one time
column of the per-point table, a second: ``amount`` / (``field`` in us x
1e-6), as the geometric mean over the selected points.  Tokens a second
of a training step from ``per_call_mean_us`` (all of a point's window
seconds over all its calls, so a stall counts).

params: ``amount`` (units of work one call does, from the traffic
file's own numbers), ``field``, ``select``/``exclude``."""
from harness import readerkit, stats


def read(ctx, params):
    rows = readerkit.select(ctx["points"], params)
    values = [r.get(params["field"]) for r in rows]
    if not values or any(not v for v in values):
        return None
    return stats.geomean([params["amount"] / (v * 1e-6) for v in values])
