"""Wall time of the scheduler's run over its steps: host work and device
work of one step together (host clock)."""


def read(ctx):
    raw = ctx["raw"]
    if not raw.get("steps"):
        return None
    return raw["wall_s"] / raw["steps"] * 1e3
