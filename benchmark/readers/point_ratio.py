"""Reader ``point_ratio``: one column of the per-point table over
another, or one selection of points over another.

The geometric mean of ``field`` over the points ``select`` chooses,
divided by the geometric mean of ``over_field`` (default: ``field``)
over the points ``over_select`` chooses (default: the same points, and
the value is then the geometric mean of each point's own ratio).  A
``--trace 1`` run alone has the column ``programs_per_call``.  Nothing
selected on either side, or a point without its column: nothing to read.

params: ``field``, ``over_field``, ``select``/``exclude``,
``over_select``."""
from harness import readerkit, stats


def read(ctx, params):
    above = readerkit.select(ctx["points"], params)
    below = readerkit.select(ctx["points"],
                             {"select": params["over_select"]}) \
        if "over_select" in params else above
    top = [r.get(params["field"]) for r in above]
    bottom = [r.get(params.get("over_field", params["field"]))
              for r in below]
    if not top or not bottom or any(not v for v in top + bottom):
        return None
    return stats.geomean(top) / stats.geomean(bottom)
