"""Host time per scheduler step spent building the step's tokens, positions
and temperatures and handing them to the device: the summed
`repro.sched.inputs` spans over the `repro.sched.step` spans of the
window (program spans, profiler trace)."""
from spans import STEP, span_ms


def read(ctx):
    return span_ms(ctx, "repro.sched.inputs", STEP)
