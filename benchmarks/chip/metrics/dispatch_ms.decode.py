"""Host time the executor spends enqueuing work per decode step of the
block graph: the self time of the `repro.exec.segment` spans (each less
its `repro.exec.sync`) over the `repro.exec.run` spans of the window
(program spans, profiler trace)."""
from spans import RUN, SEGMENT, SYNC, self_ms


def read(ctx):
    return self_ms(ctx, SEGMENT, SYNC, RUN)
