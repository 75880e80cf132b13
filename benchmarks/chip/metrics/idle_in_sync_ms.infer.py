"""Device idle time inside the executor's syncs per inference: time within
the union of the `repro.exec.sync` spans in which no operation ran on
the device, averaged over the cell's chips, over the `repro.exec.run`
spans of the window (program spans against the device's ops, profiler
trace)."""
from spans import RUN, SYNC, idle_in_ms


def read(ctx):
    return idle_in_ms(ctx, SYNC, RUN)
