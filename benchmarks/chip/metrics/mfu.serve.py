"""Operations of the rows that carried a request, over every step (from
the configuration's shapes), over the run's wall time and the chips' bf16
peak."""


def read(ctx):
    raw = ctx["raw"]
    if "totals" not in raw:
        return None
    return 100.0 * raw["totals"]["flops"] / raw["wall_s"] / (
        ctx["peak"]["bf16_flops"] * ctx["chips"])
