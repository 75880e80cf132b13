"""The split_matmul kernel's share of its roofline, with the kernel found
in any program: the least time the chip could take for the network's
projections (the larger of operations over peak and bytes over bandwidth,
from the configuration's shapes), over the summed device time of the
kernel's events (profiler trace).

`split_matmul_roofline` counts the kernel only inside its own jitted
wrapper; a walk that traces the kernel into one program of its own runs
the same Pallas call there.  So its events are matched by the name of the
Pallas call itself, in whatever program ran it."""
from counts import roofline_s

#: the Pallas call's op, as the device trace names it
KERNEL = r"^%?split_matmul(\.\d+)?\b"


def read(ctx):
    tr, raw = ctx["trace"], ctx["raw"]
    if tr is None or "ops" not in raw:
        return None
    t = tr.op_time_ns(KERNEL, ctx["window"]) / 1e9
    if t <= 0.0:
        return None
    bound = sum(roofline_s(op, ctx["peak"]) for op in raw["ops"]
                if op["kind"] == "linear") * raw["n"]
    return 100.0 * bound / t
