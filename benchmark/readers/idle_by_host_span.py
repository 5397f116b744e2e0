"""Reader ``idle_by_host_span``: what the host was doing while the device
was idle, in % of all idle time of the traced rounds.

Every idle nanosecond of the first device (as ``device.idle_share`` and
the breakdown's ``idle_gaps`` count them) is put down to the innermost
host event open on the issuing thread at that time
(``harness/hostspans``).  The metric sums the events matching
``pattern``; with ``below`` true, also everything nested inside such an
event.  No event of the line matching ``pattern``: nothing to read.

params: ``pattern``, ``below`` (optional), ``table`` (idle seconds by
innermost event name go to ``.bench_out/<cell>.<table>.json``)."""
import re

from harness import hostspans


def read(ctx, params):
    run = hostspans.run_of(ctx, __file__) if ctx.get("trace") else None
    if run is None:
        return None
    pattern = re.compile(params["pattern"])
    if not run.root.outermost(pattern):
        print(f"idle_by_host_span {params['table']}: nothing matches "
              f"{pattern.pattern!r}", flush=True)
        return None
    idle = run.idle_by_innermost()
    total = sum(ns for _, ns in idle)
    if total <= 0:
        return None
    by_name: dict = {}
    mine = 0
    for path, ns in idle:
        name = path[-1] if path else "(no host event)"
        by_name[name] = by_name.get(name, 0) + ns
        names = path if params.get("below") else path[-1:]
        if any(pattern.search(n) for n in names):
            mine += ns
    hostspans.write_table(ctx, __file__, params["table"], {
        "idle_s": total / 1e9,
        "by_innermost_event": sorted(((n, ns / 1e9)
                                      for n, ns in by_name.items()),
                                     key=lambda kv: -kv[1])[:40]})
    return 100.0 * mine / total
