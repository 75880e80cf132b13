"""Read the program's own spans from a trace.

The program records its spans (`repro.measure.trace`) on the host thread
that runs the window, which is `Trace.host`, on the clock of the device's
events:

* executor: `repro.exec.run` ⊃ `repro.exec.segment` ⊃ `repro.exec.sync`
  (the per-node walk's terminal sync sits directly in the run);
* scheduler: `repro.sched.step` ⊃ `repro.sched.inputs`, `.decode`,
  `.sample`, `.emit`, `.fidelity`.

Every reading is per unit of work: per `repro.exec.run` or per
`repro.sched.step` span in the window.  A program that records no such
span (one older than them) reads None, never zero.  Nanoseconds in,
milliseconds out.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from tracing import Interval, clip, gaps, merge

RUN, SEGMENT, SYNC = "repro.exec.run", "repro.exec.segment", "repro.exec.sync"
STEP = "repro.sched.step"
#: the spans that bound one unit of work; their union over the window is
#: the share of it the program's spans cover
UNITS = (RUN, STEP)
PREFIX = "repro."
OUTSIDE = "(outside program spans)"


def intervals(ctx, name: str) -> List[Interval]:
    """The window's spans called `name`, clipped to the window."""
    tr = ctx["trace"]
    if tr is None:
        return []
    return clip(((s, e) for n, s, e in tr.host if n == name),
                *ctx["window"])


def total(spans: Sequence[Interval]) -> float:
    return sum(e - s for s, e in spans)


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length of the intersection of two sorted, disjoint interval lists
    (as `merge` returns them)."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def per_unit_ms(ctx, ns: float, unit: str) -> Optional[float]:
    n = len(intervals(ctx, unit))
    if not n:
        return None
    return ns / n / 1e6


def span_ms(ctx, name: str, unit: str) -> Optional[float]:
    """Summed time of the `name` spans per unit of work."""
    return per_unit_ms(ctx, total(intervals(ctx, name)), unit)


def self_ms(ctx, name: str, child: str, unit: str) -> Optional[float]:
    """Time of the `name` spans less what their `child` spans cover, per
    unit of work: the layer's self time."""
    spans = intervals(ctx, name)
    inside = overlap(merge(spans), merge(intervals(ctx, child)))
    return per_unit_ms(ctx, total(spans) - inside, unit)


def idle_in_ms(ctx, name: str, unit: str) -> Optional[float]:
    """Device idle time inside the union of the `name` spans per unit of
    work, averaged over the cell's chips."""
    tr = ctx["trace"]
    if tr is None or not tr.devices:
        return None
    spans = merge(intervals(ctx, name))
    idle = sum(total(spans) - overlap(spans, tr.busy(d, ctx["window"]))
               for d in tr.devices) / len(tr.devices)
    return per_unit_ms(ctx, idle, unit)


# ------------------------------------------------------------ reports

def innermost(spans: Sequence[Tuple[str, float, float]]
              ) -> List[Tuple[str, float, float]]:
    """Cut nested spans of one thread into pieces, each named by the
    innermost span that covers it; time covered by no span is left out."""
    out: List[Tuple[str, float, float]] = []
    stack: List[Tuple[str, float]] = []
    t = 0.0
    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            top, end = stack.pop()
            out.append((top, t, end))
            t = end
        if stack:
            out.append((stack[-1][0], t, s))
        stack.append((name, e))
        t = s
    while stack:
        top, end = stack.pop()
        out.append((top, t, end))
        t = end
    return [(n, s, e) for n, s, e in out if e > s]


def idle_by_span(trace, win: Interval) -> Dict[str, float]:
    """Device idle seconds of the window by the innermost program span
    the host was in, averaged over the chips; idle time outside every
    program span is under `OUTSIDE`."""
    pieces: Dict[str, List[Interval]] = {}
    for n, s, e in innermost(clip_spans(trace, win)):
        pieces.setdefault(n, []).append((s, e))
    out: Dict[str, float] = {}
    devs = trace.devices
    for d in devs:
        idle = gaps(trace.busy(d, win), *win)
        left = total(idle)
        for n, p in pieces.items():
            t = overlap(merge(p), idle)
            out[n] = out.get(n, 0.0) + t / len(devs) / 1e9
            left -= t
        out[OUTSIDE] = out.get(OUTSIDE, 0.0) + left / len(devs) / 1e9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def clip_spans(trace, win: Interval) -> List[Tuple[str, float, float]]:
    lo, hi = win
    return [(n, max(s, lo), min(e, hi)) for n, s, e in trace.host
            if n.startswith(PREFIX) and e > lo and s < hi]


def coverage(trace, win: Interval) -> Optional[float]:
    """Share of the window inside the program's top-level spans."""
    spans = merge(clip(((s, e) for n, s, e in trace.host if n in UNITS),
                       *win))
    if not spans:
        return None
    return total(spans) / (win[1] - win[0])
