"""Reader ``trace_rate``: bytes per device second from the profiler's
trace, as the geometric mean over the selected points.

For each point: the computed bytes of one call (column ``bytes_field``:
``bus_bytes`` or ``moved_bytes``) times the calls in its traced windows,
over the device seconds those windows took: the union of every device op
in them, averaged over the devices.

params: ``bytes_field``, ``select``/``exclude``,
``percent_of_peak`` (optional: a key of the peaks table; the result is
then a share of that peak in %, a roofline share), else GB/s."""
from harness import peaks, readerkit, stats


def read(ctx, params):
    trace = ctx.get("trace")
    if not trace:
        return None
    rates = []
    for row in readerkit.select(ctx["points"], params):
        seen = trace["points"].get(row["name"], {})
        nbytes = row.get(params["bytes_field"])
        if not nbytes or not seen.get("calls"):
            return None
        seconds = seen.get("busy_s", 0.0)
        if seconds <= 0:
            return None
        rates.append(nbytes * seen["calls"] / seconds)
    if not rates:
        return None
    rate = stats.geomean(rates)
    if params.get("percent_of_peak"):
        peak = peaks.peaks(ctx["device_kind"])[params["percent_of_peak"]]
        return 100.0 * rate / peak
    return rate / 1e9
