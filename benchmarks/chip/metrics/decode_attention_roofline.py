"""The decode_attention kernel's share of its roofline: the least time the
chip could take to attend over the network's caches (the larger of
operations over peak and bytes over bandwidth, from the configuration's
shapes), over the summed device time of the kernel's events (profiler
trace)."""
from counts import roofline_s

#: the kernel's custom call inside its jitted wrapper
PROGRAM, KERNEL = r"decode_attention_op", r"tpu_custom_call"


def read(ctx):
    tr, raw = ctx["trace"], ctx["raw"]
    if tr is None or "ops" not in raw:
        return None
    t = tr.kernel_ns(PROGRAM, KERNEL, ctx["window"]) / 1e9
    if t <= 0.0:
        return None
    bound = sum(roofline_s(op, ctx["peak"]) for op in raw["ops"]
                if op["kind"] == "attention") * raw["n"]
    return 100.0 * bound / t
