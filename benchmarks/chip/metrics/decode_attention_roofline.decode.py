"""The decode_attention kernel's share of its roofline, with the kernel
found in any program: the least time the chip could take to attend over
the network's caches (the larger of operations over peak and bytes over
bandwidth, from the configuration's shapes), over the summed device time
of the kernel's events (profiler trace).

`decode_attention_roofline` counts the kernel only inside its own jitted
wrapper; a walk that traces the kernel into one program of its own runs
the same Pallas call there.  So its events are matched by the name of the
Pallas call itself, in whatever program ran it.  The wrapper maps the
kernel over a batch, which prefixes the name (`vmap_decode_attention_`)."""
from counts import roofline_s

#: the Pallas call's op, as the device trace names it
KERNEL = r"^%?(vmap_)?decode_attention_?(\.\d+)?\b"


def read(ctx):
    tr, raw = ctx["trace"], ctx["raw"]
    if tr is None or "ops" not in raw:
        return None
    t = tr.op_time_ns(KERNEL, ctx["window"]) / 1e9
    if t <= 0.0:
        return None
    bound = sum(roofline_s(op, ctx["peak"]) for op in raw["ops"]
                if op["kind"] == "attention") * raw["n"]
    return 100.0 * bound / t
