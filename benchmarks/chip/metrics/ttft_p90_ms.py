"""90th percentile of time to first token over every request of the
window, from each request's due time (host clock)."""
import numpy as np


def read(ctx):
    ttft = ctx["raw"].get("ttft_s")
    if not ttft:
        return None
    return float(np.percentile(ttft, 90)) * 1e3
