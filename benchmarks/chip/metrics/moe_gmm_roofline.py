"""The routed experts' grouped matmul's share of its roofline: the least
time the chip could take for the run's grouped matmuls (the larger of
operations over peak and bytes over bandwidth: 2 x 3 x d x expert width
operations per routed (row, choice) pair of an active slot; the weights
of the experts hit, as the program counted them, once each, and each
pair's rows in and out), over the device time of the `%gmm` calls of the
run's decode steps (profiler trace: the calls' mean length times the
calls a step makes, both read from the trace by `gmm_events`, times the
run's steps).

The kernel skips experts that no row chose, so the bound counts only the
experts hit; an all-experts bound would read above 100%."""
import gmm_events


def read(ctx):
    raw = ctx["raw"]
    c = gmm_events.calls(ctx)
    if c is None or "gmm_totals" not in raw or not raw.get("steps"):
        return None
    t = c.mean_ns / 1e9 * c.per_run * raw["steps"]
    tot, pk = raw["gmm_totals"], ctx["peak"]
    bound = max(tot["flops"] / pk["bf16_flops"],
                tot["bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * bound / t
