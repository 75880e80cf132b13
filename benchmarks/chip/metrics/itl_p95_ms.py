"""95th percentile of every gap between two consecutive output tokens of
a request, over all requests of the run (host clock)."""
import numpy as np


def read(ctx):
    itl = ctx["raw"].get("itl_s")
    if not itl:
        return None
    return float(np.percentile(itl, 95)) * 1e3
