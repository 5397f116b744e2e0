"""Reader ``trace_idle``: the share of the traced window, whole rounds of
the cell's traffic, in which no operation ran on the device, in %:
1 - (union of device-op intervals) / window, averaged over the devices."""


def read(ctx, params):
    trace = ctx.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
