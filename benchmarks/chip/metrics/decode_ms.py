"""Wall time of the window over the decode steps of the block graph it
completed, each ended by the executor's `block_until_ready` (host
clock)."""


def read(ctx):
    raw = ctx["raw"]
    if "n" not in raw:
        return None
    return raw["wall_s"] / raw["n"] * 1e3
