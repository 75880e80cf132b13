"""The routed experts' grouped matmul (`megablox.gmm`) in a trace.

A traced window of a busy decode loop holds about a million op events,
and a trace can keep fewer than ran: of two traced runs of the same cell
on TPU v5e at the same step time (19.05 and 19.06 ms) and the same
device time in the layer loop (9.07 and 9.13 s), one held 2.30 s and the
other 1.81 s of the `%gmm` ops' events, and its other ops fell short by
as much; a third run held all 15,792 calls it made (24 a step, 658
steps).  So the kernel's time is the summed length of the `%gmm` events
the trace kept, scaled by the share of the calls made that it kept.

The calls made are read from the trace too: the programs that run the
kernel are those whose runs (`XLA Modules` events) hold a `%gmm` event,
a run makes as many calls as the most that any of their runs holds, and
the window holds that many calls for each of their runs.  Where the
trace kept every event, the time is the plain sum."""
from __future__ import annotations

import bisect
import re
from typing import NamedTuple, Optional

from tracing import clip_named

#: the Pallas kernel's op, as the device trace names it
KERNEL = re.compile(r"^%?gmm(\.\d+)?\b")


class Calls(NamedTuple):
    kept_ns: float      # summed length of the kept `%gmm` events
    kept: int           # the kept `%gmm` events
    per_run: int        # calls one run of the kernel's programs makes
    runs: int           # runs of those programs in the window

    @property
    def mean_ns(self) -> float:
        return self.kept_ns / self.kept

    @property
    def total_ns(self) -> float:
        """The window's kernel time, scaled for the calls not kept."""
        return self.mean_ns * self.per_run * self.runs


def calls(ctx) -> Optional[Calls]:
    """The window's `%gmm` calls on the first device that ran any, or
    None where the trace holds none (or no program runs)."""
    tr = ctx["trace"]
    if tr is None:
        return None
    lo, hi = ctx["window"]
    for d in tr.devices:
        ev = [(s, e) for n, s, e in clip_named(tr.ops[d], lo, hi)
              if KERNEL.search(n)]
        runs = sorted(clip_named(tr.modules.get(d, []), lo, hi),
                      key=lambda m: m[1])
        if not ev or not runs:
            continue
        starts = [s for _, s, _ in runs]
        held = [0] * len(runs)
        for s, e in ev:
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and e <= runs[i][2]:
                held[i] += 1
        names = {runs[i][0] for i, h in enumerate(held) if h}
        if not names:
            continue
        return Calls(kept_ns=sum(e - s for s, e in ev), kept=len(ev),
                     per_run=max(held),
                     runs=sum(1 for n, _, _ in runs if n in names))
    return None
