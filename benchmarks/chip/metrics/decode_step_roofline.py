"""The decode step program's share of its roofline: the least time the
chip could take for every step of the run (weights read once a step, the
cached K/V rows each active slot attends over, and the active rows'
operations, from the configuration's shapes), over the summed device time
of the decode program's runs (profiler trace)."""
PROGRAM = r"decode_step"


def read(ctx):
    tr, raw = ctx["trace"], ctx["raw"]
    if tr is None or "totals" not in raw:
        return None
    t_ns, runs = tr.module_time_ns(PROGRAM, ctx["window"])
    if t_ns <= 0.0:
        return None
    tot, pk = raw["totals"], ctx["peak"]
    bound = max(tot["flops"] / pk["bf16_flops"],
                tot["bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * bound / (t_ns / 1e9)
