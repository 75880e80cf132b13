"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips (profiler trace)."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.devices:
        return None
    lo, hi = ctx["window"]
    return 100.0 * (1.0 - tr.busy_ns(ctx["window"]) / (hi - lo))
