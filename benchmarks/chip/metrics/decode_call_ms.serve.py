"""Host time per scheduler step spent in the call that enqueues the jitted
decode step (and in any wait for the device there): the summed
`repro.sched.decode` spans over the `repro.sched.step` spans of the
window (program spans, profiler trace)."""
from spans import STEP, span_ms


def read(ctx):
    return span_ms(ctx, "repro.sched.decode", STEP)
