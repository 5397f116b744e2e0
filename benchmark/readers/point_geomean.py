"""Reader ``point_geomean``: the geometric mean, over the selected points,
of one column of the per-point table.

params: ``field`` (a column: ``per_call_us``, ``issue_us``,
``busbw_GBps``, ``moved_GBps``, ``fw_over_raw``, ...), ``select`` and
``exclude`` (column -> value or list of values).  One point selected by
``name`` makes a per-point metric from a data file alone.  Nothing
selected, or a point without the column: nothing to read."""
from harness import readerkit, stats


def read(ctx, params):
    rows = readerkit.select(ctx["points"], params)
    values = [r.get(params["field"]) for r in rows]
    if not values or any(v is None for v in values):
        return None
    return stats.geomean(values)
