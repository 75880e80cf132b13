"""TPU compiles of the main-path Pallas kernels at published widths.

The TPU compiler is installed beside the CPU backend, and it compiles for a
described v5e chip that is not attached.  These tests lower each kernel for
one chip of a described ``v5e:2x2`` topology, in fp32 and bf16, and assert
that the compiled program holds the kernel (``tpu_custom_call``).  They catch
what interpret mode cannot: block shapes the TPU lowering refuses,
primitives it has no rule for, and layouts Mosaic cannot build.  Nothing
runs, so they say nothing about results or times.

The topology is described only inside the module fixture: only one process
at a time may load the TPU library, and pytest-xdist workers each import
this file.
"""
import os

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.kernels.decode_attention import decode_attention_op  # noqa: E402
from repro.kernels.split_matmul import split_matmul_op  # noqa: E402
from repro.kernels.ssd_chunk import ssd_chunk_op  # noqa: E402
from repro.kernels.winograd_conv.winograd_conv import (  # noqa: E402
    winograd_conv2d)


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


KERNELS = {"split_matmul": _linear, "winograd": _winograd,
           "decode_attention": _decode_attention, "ssd_chunk": _ssd_chunk}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, kernel, dtype):
    fn, shapes = KERNELS[kernel](jnp.dtype(dtype))
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
