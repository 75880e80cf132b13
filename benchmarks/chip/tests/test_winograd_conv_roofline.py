"""CPU checks of `winograd_conv_roofline`, the Winograd kernel's share of
its roofline in `vgg16.b1`.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q

On a hand-built trace whose op names are as a TPU v5e trace gives them,
where the number is worked out by hand; and on traces or runs that hold no
event of the kernel (it must read None).
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import counts      # noqa: E402
import harness     # noqa: E402
import tracing     # noqa: E402

NAME = "winograd_conv_roofline"
VGG16 = harness.load_json(HERE / "configs" / "vgg16.json")
PEAK = counts.peak("TPU v5 lite")

#: the kernel's custom calls, in a segment program and in its wrapper,
#: and ops around them that name it only as an operand
KERNEL_OP = ("%winograd_conv.1 = f32[16,3200,128]{2,1,0:T(8,128)S(1)} "
             "custom-call(f32[16,3200,128]{2,1,0:T(8,128)S(1)} %pad.2, "
             "f32[16,128,128]{2,1,0:T(8,128)S(1)} %pad.4), "
             "custom_call_target=\"tpu_custom_call\"")
KERNEL_OP_2 = ("%winograd_conv.6 = f32[16,896,256]{2,1,0:T(8,128)S(1)} "
               "custom-call(f32[16,896,256]{2,1,0:T(8,128)S(1)} %pad.13, "
               "f32[16,256,256]{2,1,0:T(8,128)S(1)} %bitcast.191), "
               "custom_call_target=\"tpu_custom_call\"")
CONSUMER = ("%slice.33 = f32[16,3136,128]{2,1,0:T(8,128)S(1)} "
            "slice(f32[16,3200,128]{2,1,0:T(8,128)S(1)} %winograd_conv.1)")
OTHER = ("%split_matmul.1 = f32[8,4096]{1,0:T(8,128)S(1)} custom-call() "
         "custom_call_target=\"tpu_custom_call\"")


def reader():
    return harness.load_module(HERE / "metrics" / f"{NAME}.py")


def ctx_of(tr, n: int = 2, config=VGG16) -> dict:
    return {"trace": tr, "window": tr.window() if tr else None,
            "raw": {"n": n}, "config": config, "peak": PEAK}


def trace(ops) -> tracing.Trace:
    return tracing.Trace({"/device:TPU:0": ops}, {},
                         [[("bench.window", 0, 1_000_000)]])


def test_winograd_roofline_by_hand():
    # the five convolutions VGG-16 gives the kernel, as direct convolutions
    # in float32: (operations, bytes), and the least time of each
    convs = [
        # 112 x 112, 64 -> 128
        (2 * 112 * 112 * 128 * 9 * 64,
         4 * (112 * 112 * 64 + 112 * 112 * 128 + 9 * 64 * 128)),
        # 112 x 112, 128 -> 128
        (2 * 112 * 112 * 128 * 9 * 128,
         4 * (2 * 112 * 112 * 128 + 9 * 128 * 128)),
        # 56 x 56, 128 -> 256
        (2 * 56 * 56 * 256 * 9 * 128,
         4 * (56 * 56 * 128 + 56 * 56 * 256 + 9 * 128 * 256)),
        # 56 x 56, 256 -> 256, twice
        (2 * 56 * 56 * 256 * 9 * 256, 4 * (2 * 56 * 56 * 256 + 9 * 256 * 256)),
        (2 * 56 * 56 * 256 * 9 * 256, 4 * (2 * 56 * 56 * 256 + 9 * 256 * 256)),
    ]
    least = [max(f / 197e12, b / 819e9) for f, b in convs]
    # the first is bound by its bytes, the others by their operations
    assert least[0] == convs[0][1] / 819e9
    assert all(t == f / 197e12 for t, (f, _) in zip(least[1:], convs[1:]))
    assert sum(least) == pytest.approx(77.85e-6, rel=1e-3)
    # two inferences; the kernel's events take 300 and 200 us, and the
    # second's last 100 us lie after the window: 400 us count.  The slice
    # that reads the kernel's output and the other kernel do not count
    tr = trace([(KERNEL_OP, 0, 300_000), (CONSUMER, 300_000, 310_000),
                (OTHER, 310_000, 900_000), (KERNEL_OP_2, 900_000, 1_100_000)])
    got = reader().read(ctx_of(tr, n=2))
    assert got == pytest.approx(100.0 * 2 * sum(least) / 400e-6)
    assert 0.0 < got <= 100.0


def test_winograd_roofline_reads_nothing_without_the_kernel():
    """No kernel event in the window (only ops that name it as an
    operand), no trace, or a configuration with no op chain: None."""
    assert reader().read(ctx_of(trace([(CONSUMER, 0, 10),
                                       (OTHER, 10, 20)]))) is None
    assert reader().read(ctx_of(trace([]))) is None
    assert reader().read(ctx_of(None)) is None
    decoder = harness.load_json(HERE / "configs" / "codeqwen15_7b.json")
    assert reader().read(ctx_of(trace([(KERNEL_OP, 0, 10)]),
                                config=decoder)) is None


def test_winograd_roofline_serves_what_the_kernel_serves():
    """The chain's convolutions that meet the kernel's condition: five of
    VGG-16's thirteen, none of ResNet-18's."""
    served = reader().served
    convs = [x for x in VGG16["layers"] if x["kind"] == "conv"]
    assert len(convs) == 13
    assert [(x["h"], x["c_in"], x["c_out"]) for x in convs if served(x)] == [
        (112, 64, 128), (112, 128, 128), (56, 128, 256), (56, 256, 256),
        (56, 256, 256)]
    resnet18 = harness.load_json(HERE / "configs" / "resnet18.json")
    assert not any(served(x) for x in resnet18["layers"]
                   if x["kind"] == "conv")
