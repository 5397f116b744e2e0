"""Reader ``trace_kit_flops``: operations per device second from the
profiler's trace, as a share of a peak, over the selected points, the
operations counted by **the kit that the point's configuration names**.

``trace_flops`` with one difference: the FLOP one call does are not
``olmoekit``'s.  The point (found again in the cell's traffic file by its
name) names its configuration file, the file names its kit (``"kit"``: a
module of ``harness/``), and ``kit.step_flops(kit.load_config(file))``
gives the parts (``count`` picks one).  So a next model brings a
configuration and a kit, and no third reader.  Seconds: with ``pattern``
those in the ops it matches (a kernel's own rate), else the union of every
device op in the point's traced windows.  No trace, no matching op, or a
configuration without a kit: nothing to read.

params: ``count``, ``select``/``exclude``, ``pattern`` (optional),
``percent_of_peak`` (a key of the peaks table)."""
import importlib
import os
import re

from harness import manifest, peaks, readerkit, stats


def flops_of(point: dict, bench_dir: str, count: str):
    """The FLOP of one call of ``point`` by its configuration's kit."""
    path = os.path.join(bench_dir, "configs", point["config"] + ".json")
    name = manifest.load_json(path).get("kit")
    if not name:
        return None
    kit = importlib.import_module("harness." + name)
    return kit.step_flops(kit.load_config(path))[count]


def read(ctx, params):
    trace = ctx.get("trace")
    if not trace:
        return None
    bench_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cell = manifest.by_name(
        manifest.load(os.path.dirname(bench_dir))["workloads"],
        ctx["run"]["workload"], "workload")
    points = {p["name"]: p for p in manifest.traffic_points(
        cell["traffic"], bench_dir)}
    pattern = re.compile(params["pattern"]) if "pattern" in params else None
    rates = []
    for row in readerkit.select(ctx["points"], params):
        seen = trace["points"].get(row["name"], {})
        seconds = seen.get("busy_s", 0.0) if pattern is None else sum(
            s for n, s in seen.get("ops", {}).items() if pattern.search(n))
        flops = flops_of(points[row["name"]], bench_dir, params["count"])
        if not flops or not seen.get("calls") or seconds <= 0:
            return None
        rates.append(flops * seen["calls"] / seconds)
    if not rates:
        return None
    peak = peaks.peaks(ctx["device_kind"])[params["percent_of_peak"]]
    return 100.0 * stats.geomean(rates) / peak
