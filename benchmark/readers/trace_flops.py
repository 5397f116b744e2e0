"""Reader ``trace_flops``: operations per device second from the
profiler's trace, as a share of a peak, over the selected points.

For each point: the FLOP one call does, counted from the shapes of the
configuration the metric's file names (``harness/olmoekit.step_flops``;
``count`` picks the part: ``step`` is the model FLOP of a whole training
step, ``flash_forward`` what the attention kernel computes), times the
calls in its traced windows, over device seconds: with ``pattern`` the
seconds in the ops it matches (a kernel's own rate), else the union of
every device op in those windows (the step's).  A program whose trace
holds no matching op, or no trace: nothing to read.

params: ``config`` (a file of ``configs/``), ``count``,
``select``/``exclude``, ``pattern`` (optional), ``percent_of_peak`` (a
key of the peaks table)."""
import os
import re

from harness import olmoekit, peaks, readerkit, stats


def read(ctx, params):
    trace = ctx.get("trace")
    if not trace:
        return None
    bench_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = olmoekit.load_config(os.path.join(
        bench_dir, "configs", params["config"] + ".json"))
    flops = olmoekit.step_flops(cfg)[params["count"]]
    pattern = re.compile(params["pattern"]) if "pattern" in params else None
    rates = []
    for row in readerkit.select(ctx["points"], params):
        seen = trace["points"].get(row["name"], {})
        seconds = seen.get("busy_s", 0.0) if pattern is None else sum(
            s for n, s in seen.get("ops", {}).items() if pattern.search(n))
        if not seen.get("calls") or seconds <= 0:
            return None
        rates.append(flops * seen["calls"] / seconds)
    if not rates:
        return None
    peak = peaks.peaks(ctx["device_kind"])[params["percent_of_peak"]]
    return 100.0 * stats.geomean(rates) / peak
