"""Operations of the block graph's layers (from the configuration's
shapes) times decode steps, over the window's wall time and the chips'
bf16 peak."""


def read(ctx):
    raw = ctx["raw"]
    if "ops" not in raw:
        return None
    flops = sum(op["flops"] for op in raw["ops"]) * raw["n"]
    return 100.0 * flops / raw["wall_s"] / (
        ctx["peak"]["bf16_flops"] * ctx["chips"])
