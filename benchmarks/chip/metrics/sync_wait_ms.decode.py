"""Host time the executor spends waiting in `block_until_ready` per decode
step of the block graph: the summed `repro.exec.sync` spans over the
`repro.exec.run` spans of the window (program spans, profiler trace)."""
from spans import RUN, SYNC, span_ms


def read(ctx):
    return span_ms(ctx, SYNC, RUN)
