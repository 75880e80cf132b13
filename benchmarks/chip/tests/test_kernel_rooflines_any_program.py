"""CPU checks of `split_matmul_roofline.decode` and
`decode_attention_roofline.decode`, the two decode kernels' shares of their
rooflines with the kernel found in any program.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q

On hand-built traces whose op names are as a TPU v5e trace gives them: the
kernels run in their own jitted wrappers (`jit_split_matmul_op`,
`jit_decode_attention_op`) or traced into one walk program (`jit_walk`),
and the reading must be the same either way and worked out by hand.  The
readers that count the kernel only inside its wrapper read the first and
nothing in the second.
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

PEAK = counts.peak("TPU v5 lite")
DEV = "/device:TPU:0"

MATMUL = ("%split_matmul.48 = bf16[8,13440]{1,0:T(8,128)(2,1)S(1)} "
          "custom-call(bf16[8,4096]{1,0:T(8,128)(2,1)S(1)} %pad.25, "
          "bf16[4096,13440]{1,0:T(8,128)(2,1)} %weights_26__0_.1), "
          "custom_call_target=\"tpu_custom_call\"")
ATTENTION = ("%vmap_decode_attention_.1 = bf16[4,8,128]{2,1,0:T(8,128)(2,1)} "
             "custom-call(s32[1]{0} %reshape.3, bf16[4,8,128] %pad.7), "
             "custom_call_target=\"tpu_custom_call\"")
#: ops that name a kernel only as an operand, and a pad of its weights
CONSUMERS = ("%slice.33 = bf16[1,13440]{1,0} slice(bf16[8,13440]{1,0} "
             "%split_matmul.48)",
             "%reshape.9 = bf16[32,128]{1,0} reshape(bf16[4,8,128]{2,1,0} "
             "%vmap_decode_attention_.1)",
             "%pad.27 = bf16[13824,4096]{1,0} pad(bf16[13440,4096]{1,0} "
             "%weights_27__0_.1, bf16[] %constant.38)")

#: one projection and one attention, bfloat16: (operations, bytes)
RAW = {"n": 2, "ops": [
    {"kind": "linear", "flops": counts.matmul_flops(1, 4096, 13440),
     "bytes": counts.matmul_bytes(1, 4096, 13440, 2)},
    {"kind": "attention", "flops": counts.decode_attention_flops(32, 128, 4096),
     "bytes": counts.decode_attention_bytes(32, 4, 128, 4096, 2)},
    {"kind": "add", "flops": 0, "bytes": 0}]}


def reader(name: str):
    return harness.load_module(HERE / "metrics" / f"{name}.py")


def trace(programs) -> tracing.Trace:
    """Two inferences: each runs the projection for 300 us and the
    attention for 50 us, with the consumers between them, inside
    `programs(k)` (the (name, start, end) program runs of inference k)."""
    ops, modules = [], []
    for k in range(2):
        t = k * 1_000_000
        ops += [(MATMUL, t, t + 300_000),
                (CONSUMERS[0], t + 300_000, t + 310_000),
                (ATTENTION, t + 310_000, t + 360_000),
                (CONSUMERS[1], t + 360_000, t + 362_000),
                (CONSUMERS[2], t + 362_000, t + 500_000)]
        modules += programs(t)
    return tracing.Trace({DEV: ops}, {DEV: modules},
                         [[("bench.window", 0, 2_000_000)]])


def wrappers(t):
    return [("jit_split_matmul_op(1)", t, t + 300_000),
            ("jit_slice(2)", t + 300_000, t + 310_000),
            ("jit_decode_attention_op(3)", t + 310_000, t + 360_000),
            ("jit_reshape(4)", t + 360_000, t + 500_000)]


def walk(t):
    return [("jit_walk(7)", t, t + 500_000)]


def ctx_of(tr, raw=RAW) -> dict:
    return {"trace": tr, "window": tr.window() if tr else None,
            "raw": raw, "peak": PEAK}


@pytest.mark.parametrize("programs", [wrappers, walk])
@pytest.mark.parametrize("name, kind, busy", [
    ("split_matmul_roofline.decode", "linear", 2 * 300e-6),
    ("decode_attention_roofline.decode", "attention", 2 * 50e-6)])
def test_kernel_roofline_by_hand_in_any_program(programs, name, kind, busy):
    (op,) = [o for o in RAW["ops"] if o["kind"] == kind]
    least = max(op["flops"] / 197e12, op["bytes"] / 819e9)
    got = reader(name).read(ctx_of(trace(programs)))
    assert got == pytest.approx(100.0 * 2 * least / busy)
    assert 0.0 < got <= 100.0


@pytest.mark.parametrize("name", ["split_matmul_roofline",
                                  "decode_attention_roofline"])
def test_wrapper_readers_see_the_kernels_only_in_their_wrappers(name):
    """The accepted readers read the same as the new ones where the kernel
    runs in its wrapper, and nothing where it runs inside the walk."""
    in_wrappers = ctx_of(trace(wrappers))
    assert reader(name).read(in_wrappers) == pytest.approx(
        reader(f"{name}.decode").read(in_wrappers))
    assert reader(name).read(ctx_of(trace(walk))) is None


@pytest.mark.parametrize("name", ["split_matmul_roofline.decode",
                                  "decode_attention_roofline.decode"])
def test_kernel_roofline_reads_nothing_without_the_kernel(name):
    """Only ops that name the kernels as operands, no trace, or a run with
    no op counts: None."""
    tr = tracing.Trace({DEV: [(c, 10 * i, 10 * i + 5)
                              for i, c in enumerate(CONSUMERS)]},
                       {DEV: walk(0)}, [[("bench.window", 0, 1_000)]])
    assert reader(name).read(ctx_of(tr)) is None
    assert reader(name).read(ctx_of(None)) is None
    assert reader(name).read(ctx_of(trace(walk), raw={"n": 2})) is None
