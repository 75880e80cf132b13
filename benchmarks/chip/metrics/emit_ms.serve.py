"""Host time per scheduler step spent reading each row's token back and
keeping the slots: the summed `repro.sched.emit` spans over the
`repro.sched.step` spans of the window (program spans, profiler trace)."""
from spans import STEP, span_ms


def read(ctx):
    return span_ms(ctx, "repro.sched.emit", STEP)
