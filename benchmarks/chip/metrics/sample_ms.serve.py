"""Host time per scheduler step spent in `sample_tokens`: the summed
`repro.sched.sample` spans over the `repro.sched.step` spans of the
window (program spans, profiler trace)."""
from spans import STEP, span_ms


def read(ctx):
    return span_ms(ctx, "repro.sched.sample", STEP)
