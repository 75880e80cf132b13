"""Program spans on the profiler's clock.

`span(name, **stats)` marks one phase of the program — an executor run,
a segment, a device sync, a scheduler step — as a context manager over
`jax.profiler.TraceAnnotation`.  Under an active profiler session
(`jax.profiler.trace` / `start_trace`) the span is recorded on the host
thread that entered it, on the same clock as the device's `XLA Ops`
events, so each idle gap of the device can be attributed to the program
phase the host was in.  Without a session nothing is recorded and no
annotation is built: the span costs a check of the profiler and two
reads of the host clock, under a microsecond.

Each span also reads the host clock on entry and exit and keeps its own
duration (`elapsed_s`), so code that times a phase reads the span
instead of keeping a second stopwatch.

Span names of the program (`docs/ARCHITECTURE.md`, "Tracing"):

  * `repro.exec.run` ⊃ `repro.exec.segment` ⊃ `repro.exec.sync`
    (`runtime/executor.py`);
  * `repro.sched.step` ⊃ `repro.sched.inputs` / `.decode` / `.sample` /
    `.emit` / `.fidelity` (`serving/scheduler.py`), and `.sample` ⊃
    `repro.sched.read` (`serving/engine.py` `sample_tokens`).

jax is resolved on the first span, not at import: this package stays
import-light (`python -m repro lint`).
"""
from __future__ import annotations

import time
from typing import Any, Optional

_annotation: Optional[type] = None


def _trace_annotation() -> type:
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation


class span:
    """`with span("repro.exec.sync"): ...` — one named, timed phase.

    `stats` are attached to the recorded event (the profiler shows them
    beside the span); `set(**stats)` adds stats known only inside the
    span.  `elapsed_s` holds the span's host-clock duration after exit.
    """

    __slots__ = ("_ann", "_t0", "elapsed_s")

    def __init__(self, name: str, **stats: Any):
        ann = _trace_annotation()
        # without a session no annotation is built: that halves the cost
        self._ann = ann(name, **stats) if ann.is_enabled() else None
        self.elapsed_s = 0.0

    def __enter__(self) -> "span":
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.elapsed_s = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)

    def set(self, **stats: Any) -> None:
        if self._ann is not None:
            self._ann.set_metadata(**stats)
