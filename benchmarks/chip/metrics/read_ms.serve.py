"""Host time per scheduler step spent waiting for the step's tokens: the
summed `repro.sched.read` spans (the one read of the sampled tokens back
to the host, inside `repro.sched.sample`) over the `repro.sched.step`
spans of the window (program spans, profiler trace).  A program that
records no such span reads None."""
from spans import STEP, intervals, span_ms

READ = "repro.sched.read"


def read(ctx):
    if not intervals(ctx, READ):
        return None
    return span_ms(ctx, READ, STEP)
