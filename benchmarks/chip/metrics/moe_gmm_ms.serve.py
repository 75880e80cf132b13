"""Device time of the routed experts' grouped matmul per scheduler step,
which runs one decode step: the `%gmm` calls' mean length times the
calls a decode step makes, both read from the trace (profiler trace,
`gmm_events`).  A program without the kernel reads None."""
import gmm_events


def read(ctx):
    c = gmm_events.calls(ctx)
    if c is None:
        return None
    return c.mean_ns / 1e6 * c.per_run
