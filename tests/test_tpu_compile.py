"""TPU compiles of the main-path Pallas kernels at published widths.

The TPU compiler is installed beside the CPU backend, and it compiles for a
described v5e chip that is not attached.  These tests lower each kernel for
one chip of a described ``v5e:2x2`` topology, in fp32 and bf16, and assert
that the compiled program holds the kernel (``tpu_custom_call``) under its
stable name, which is what a profile shows for it.  They catch
what interpret mode cannot: block shapes the TPU lowering refuses,
primitives it has no rule for, and layouts Mosaic cannot build.  Nothing
runs, so they say nothing about results or times.

The executor's fused walk is compiled the same way, for ResNet-18 and
VGG-16's convolutions at 224 x 224 and for a small decoder: on the chip
the walk is one program with its kernels under their stable names, and no
fence between segments is left in it (on the CPU each segment's output
goes through a conditional, `repro.runtime.segments._fence`).

The topology is described only inside the module fixture: only one process
at a time may load the TPU library, and pytest-xdist workers each import
this file.
"""
import os
import re

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.kernels.decode_attention import decode_attention_op  # noqa: E402
from repro.kernels.split_matmul import split_matmul_op  # noqa: E402
from repro.kernels.ssd_chunk import ssd_chunk_op  # noqa: E402
from repro.kernels.winograd_conv.winograd_conv import (  # noqa: E402
    winograd_conv2d)
from repro.models.moe import grouped_matmul  # noqa: E402
from repro.core import networks  # noqa: E402
from repro.core.partitioner import PartitionDecision  # noqa: E402
from repro.graph.frontends import from_model  # noqa: E402
from repro.graph.ir import from_units  # noqa: E402
from repro.runtime.executor import PlanExecutor  # noqa: E402
from repro.runtime.plan import (CoexecPlan, PlanProvenance,  # noqa: E402
                                build_graph_schedule, segments_json)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _linear(dt):
    # codeqwen1.5-7b MLP up-projection at decode batch 8
    return (lambda x, w: split_matmul_op(x, w, 0, 13440),
            [((8, 4096), dt), ((4096, 13440), dt)])


def _winograd(dt):
    # resnet18 layer2 3x3 conv, C_out 128
    return (winograd_conv2d,
            [((1, 28, 28, 128), dt), ((3, 3, 128, 128), dt)])


def _decode_attention(dt):
    # codeqwen1.5-7b: 32 heads, 32 KV heads, head_dim 128, 4096 cached
    return (lambda q, k, v, pos: decode_attention_op(q, k, v, pos),
            [((1, 32, 128), dt), ((1, 4096, 32, 128), dt),
             ((1, 4096, 32, 128), dt), ((), jnp.int32)])


def _ssd_chunk(dt):
    # zamba2-7b Mamba2: 112 heads of 64, state 64, chunk 256, two chunks
    t, h, hd, n = 512, 112, 64, 64
    return (lambda x, b, c, d, a, s0: ssd_chunk_op(x, b, c, d, a, s0,
                                                   chunk=256),
            [((1, t, h, hd), dt), ((1, t, n), dt), ((1, t, n), dt),
             ((1, t, h), dt), ((h,), dt), ((1, h, hd, n), dt)])


def _grouped_matmul(dt):
    # deepseek-v2-lite routed experts' gate projection: 72 (row, choice)
    # pairs, one layer (as the layer loop gives it) of a stack of 8 MoE
    # layers of 64 experts; the kernel is chosen by the platform the
    # program is lowered for
    return (lambda x, w, sizes, layer: grouped_matmul(x, w, sizes,
                                                      layer=layer),
            [((72, 2048), dt), ((8, 64, 2048, 1408), dt),
             ((64,), jnp.int32), ((), jnp.int32)])


KERNELS = {"split_matmul": _linear, "winograd": _winograd,
           "decode_attention": _decode_attention, "ssd_chunk": _ssd_chunk,
           "grouped_matmul": _grouped_matmul}
#: each kernel's stable `name=`, which names its custom call in a profile
CALL_NAMES = {"split_matmul": "split_matmul", "winograd": "winograd_conv",
              "decode_attention": "decode_attention",
              "ssd_chunk": "ssd_chunk", "grouped_matmul": "gmm"}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, kernel, dtype):
    fn, shapes = KERNELS[kernel](jnp.dtype(dtype))
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # (a batching transform prefixes the name: `vmap_decode_attention_`)
    assert re.search(rf"%[\w.]*{CALL_NAMES[kernel]}[\w.]* = [^\n]*"
                     r'custom_call_target="tpu_custom_call"',
                     compiled.as_text())


def _plan(g):
    """Every conv and projection co-executed (3/4-1/4), attention
    exclusive: fused segments, pool and exclusive singletons."""
    decisions, opaque = {}, {}
    for n in g:
        if n.kind in ("linear", "conv"):
            c = n.op.C_out
            decisions[n.id] = PartitionDecision(
                op=n.op, c_cpu=c // 4, c_gpu=c - c // 4,
                pred_cpu_us=1.0, pred_gpu_us=1.0, pred_total_us=2.0)
        elif n.kind == "attention":
            opaque[n.id] = 1.0
    prov = PlanProvenance(
        device="moto2022", threads=3, mechanism="svm_poll", step=8, seed=1,
        network_fingerprint=g.fingerprint(), predictor_checksum="")
    return CoexecPlan(
        provenance=prov, schedule=build_graph_schedule(g, decisions, opaque),
        graph_json=None if g.is_unit_chain() else g.to_json(),
        segments=segments_json(g, decisions))


#: (graph, dtype, the kernels' call names and how many of each)
WALKS = {
    "resnet18": (lambda: from_units(networks.resnet18()), "float32",
                 {"split_matmul": 1}),
    # the classifier's 123.6 M parameters left out: the Winograd kernel
    # serves five of the thirteen convolutions
    "vgg16_convs": (lambda: from_units([u for u in networks.vgg16()
                                        if u[0] != "linear"]), "float32",
                    {"winograd_conv": 5}),
    "tiny_decoder": (lambda: from_model("tiny_decoder", blocks=2,
                                        cache_len=512), "bfloat16",
                     {"split_matmul": 9, "vmap_decode_attention_": 2}),
}


@pytest.mark.parametrize("network", sorted(WALKS))
def test_fused_walk_compiles_for_v5e_without_a_fence(one_chip, network):
    graph, dtype, calls = WALKS[network]
    exe = PlanExecutor(_plan(graph()), dtype=jnp.dtype(dtype),
                       use_pallas=True)
    x = exe.input_template()
    walk = exe.walk_program(tuple(x.shape))
    assert len(walk.segments) > 1

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    text = walk.fn.lower(on_chip(x),
                         jax.tree.map(on_chip, walk.weights)).compile(
                         ).as_text()
    assert "conditional(" not in text and "opt-barrier" not in text
    names = re.findall(r"^\s*(?:ROOT )?%([\w.]+?)(?:\.\d+)? = [^\n]*"
                       r'custom_call_target="tpu_custom_call"', text, re.M)
    assert {n: names.count(n) for n in set(names)} == calls
