"""The Winograd kernel's share of its roofline: the least time the chip
could take for the network's convolutions that the kernel serves (the
larger of operations over peak and bytes over bandwidth, counted as the
direct convolution's from the configuration's shapes, the same work
whatever implements it), over the summed device time of the kernel's
events (profiler trace).

The kernel runs inside the executor's fused segment programs as well as in
its own jitted wrapper, so its events are matched by the name of the
Pallas call itself, in any program."""
from counts import chain_ops, config_dtype, roofline_s

#: the convolutions the program gives the Winograd kernel: 3 x 3, stride
#: 1, at least 128 output and 32 input channels, at least 1024 positions
KERNEL_SIZE, STRIDE, MIN_C_OUT, MIN_C_IN, MIN_POSITIONS = 3, 1, 128, 32, 1024

#: the Pallas call's op, as the device trace names it
KERNEL = r"^%?winograd_conv(\.\d+)?\b"


def served(layer: dict) -> bool:
    return (layer["kind"] == "conv" and layer["k"] == KERNEL_SIZE
            and layer["s"] == STRIDE and layer["c_out"] >= MIN_C_OUT
            and layer["c_in"] >= MIN_C_IN
            and layer["h"] * layer["w"] >= MIN_POSITIONS)


def read(ctx):
    tr, raw, cfg = ctx["trace"], ctx["raw"], ctx["config"]
    if tr is None or "n" not in raw or "layers" not in cfg:
        return None
    t = tr.op_time_ns(KERNEL, ctx["window"]) / 1e9
    if t <= 0.0:
        return None
    convs = [layer for layer in cfg["layers"] if served(layer)]
    bound = sum(roofline_s(op, ctx["peak"])
                for op in chain_ops(convs, config_dtype(cfg))) * raw["n"]
    return 100.0 * bound / t
