"""Reduce a JAX profiler trace (`.xplane.pb`) to device metrics.

Device planes are those JAX names `/device:TPU:<n>`; their `XLA Ops` line
holds one event per operation that ran, and their `XLA Modules` line one
event per program run.  Host planes hold the Python thread's spans,
including the benchmark's own `bench.window` span, which bounds the
measured window.

Everything is in nanoseconds on the trace's clock; `summarize` returns
seconds.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

WINDOW_SPAN = "bench.window"
COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|all-to-all|"
                        r"collective-permute|\bsend\b|\brecv\b", re.I)

Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals, sorted and disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def covered(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi] between merged busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


class Trace:
    """Device ops, device programs and host spans of one trace."""

    def __init__(self, ops: Dict[str, List[Tuple[str, float, float]]],
                 modules: Dict[str, List[Tuple[str, float, float]]],
                 host: List[List[Tuple[str, float, float]]]):
        self.ops = ops            # device -> [(name, start, end)]
        self.modules = modules    # device -> [(name, start, end)]
        # the host thread that ran the window: its spans nest
        self.host = next((line for line in host
                          if any(n == WINDOW_SPAN for n, _, _ in line)), [])

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        ops: Dict[str, list] = {}
        modules: Dict[str, list] = {}
        host: list = []
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:"):
                for line in plane.lines:
                    evs = [(e.name, e.start_ns, e.end_ns)
                           for e in line.events]
                    if line.name == "XLA Ops":
                        ops[plane.name] = evs
                    elif line.name == "XLA Modules":
                        modules[plane.name] = evs
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    host.append([(e.name, e.start_ns, e.end_ns)
                                 for e in line.events])
        return cls(ops, modules, host)

    # ------------------------------------------------------------ window
    def window(self) -> Interval:
        spans = [(s, e) for n, s, e in self.host if n == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
        return spans[-1]

    @property
    def devices(self) -> List[str]:
        return sorted(self.ops)

    # ------------------------------------------------------------- times
    def busy(self, device: str, win: Interval) -> List[Interval]:
        return merge(clip(((s, e) for _, s, e in self.ops[device]), *win))

    def busy_ns(self, win: Interval) -> float:
        """Union of op intervals in the window, averaged over devices."""
        devs = self.devices
        if not devs:
            return 0.0
        return sum(covered(self.busy(d, win)) for d in devs) / len(devs)

    def op_time_ns(self, pattern: str, win: Interval) -> float:
        """Summed device time of ops whose name matches `pattern`,
        averaged over devices."""
        rx = re.compile(pattern)
        devs = self.devices
        if not devs:
            return 0.0
        tot = 0.0
        for d in devs:
            tot += sum(e - s for n, s, e in
                       clip_named(self.ops[d], *win) if rx.search(n))
        return tot / len(devs)

    def module_time_ns(self, pattern: str, win: Interval) -> Tuple[float, int]:
        """(summed time, count) of program runs whose name matches,
        averaged over devices."""
        rx = re.compile(pattern)
        devs = sorted(self.modules)
        if not devs:
            return 0.0, 0
        tot, cnt = 0.0, 0
        for d in devs:
            hits = [(s, e) for n, s, e in clip_named(self.modules[d], *win)
                    if rx.search(n)]
            tot += sum(e - s for s, e in hits)
            cnt += len(hits)
        return tot / len(devs), cnt // len(devs)

    def kernel_ns(self, module: str, op: str, win: Interval) -> float:
        """Summed device time of ops matching `op` that ran inside a
        program whose name matches `module` (a kernel's custom call inside
        its jitted wrapper), averaged over devices."""
        rx_m, rx_o = re.compile(module), re.compile(op)
        devs = self.devices
        if not devs:
            return 0.0
        tot = 0.0
        for d in devs:
            mods = sorted((s, e) for n, s, e in self.modules.get(d, ())
                          if rx_m.search(n))
            starts = [s for s, _ in mods]
            for n, s, e in clip_named(self.ops[d], *win):
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and s < mods[i][1] and rx_o.search(n):
                    tot += e - s
        return tot / len(devs)

    def collective_ns(self, win: Interval) -> float:
        return self.op_time_ns(COLLECTIVE.pattern, win)

    def top_ops(self, win: Interval, n: int = 10) -> List[Tuple[str, float]]:
        """Device ops by summed time (seconds), averaged over devices."""
        tot: Dict[str, float] = {}
        devs = self.devices
        for d in devs:
            for name, s, e in clip_named(self.ops[d], *win):
                tot[name] = tot.get(name, 0.0) + (e - s)
        ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / max(1, len(devs)) / 1e9] for k, v in ranked]

    def idle_by_host(self, win: Interval, n: int = 10
                     ) -> List[Tuple[str, float]]:
        """Idle device time of the first device, grouped by the innermost
        span of the window's host thread that covers each gap's midpoint
        (seconds)."""
        devs = self.devices
        if not devs:
            return []
        spans = sorted((h for h in self.host if h[0] != WINDOW_SPAN),
                       key=lambda h: (h[1], -h[2]))
        idle = sorted(gaps(self.busy(devs[0], win), *win))
        tot: Dict[str, float] = {}
        stack: List[Tuple[str, float, float]] = []
        i = 0
        for s, e in idle:
            mid = 0.5 * (s + e)
            while i < len(spans) and spans[i][1] <= mid:
                while stack and stack[-1][2] < spans[i][1]:
                    stack.pop()
                stack.append(spans[i])
                i += 1
            while stack and stack[-1][2] < mid:
                stack.pop()
            name = stack[-1][0] if stack else "(no host span)"
            tot[name] = tot.get(name, 0.0) + (e - s)
        ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9] for k, v in ranked]


def clip_named(events, lo: float, hi: float):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def summarize(trace: Trace) -> Dict[str, object]:
    """What every traced run reports: busy and window seconds, and the
    breakdown of device ops and idle gaps."""
    win = trace.window()
    return {"busy_s": trace.busy_ns(win) / 1e9,
            "window_s": (win[1] - win[0]) / 1e9,
            "breakdown": {"device_ops": trace.top_ops(win),
                          "idle_gaps": trace.idle_by_host(win)}}
