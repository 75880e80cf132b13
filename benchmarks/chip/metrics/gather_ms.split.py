"""Device time of collective operations (the gathers between the two
chip groups) per inference, averaged over the chips (profiler trace)."""


def read(ctx):
    tr, raw = ctx["trace"], ctx["raw"]
    if tr is None or raw.get("mesh_groups", 1) < 2:
        return None
    return tr.collective_ns(ctx["window"]) / raw["n"] / 1e6
