"""Per-architecture smoke tests (assignment requirement).

Each assigned architecture is instantiated as a REDUCED same-family variant
(2 layers, d_model<=512, <=4 experts) and runs one forward/train step on
CPU, asserting output shapes and absence of NaNs; serving architectures
also run prefill + decode and check consistency with the full forward.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import make_batch
from repro.models import ARCH_IDS, build_model, get_config

B, T = 2, 16


def _reduced(arch):
    cfg = get_config(arch).reduced()
    return cfg, build_model(cfg)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_shapes_and_no_nans(arch):
    cfg, model = _reduced(arch)
    params = model.init(jax.random.PRNGKey(0))
    batch = {k: jnp.asarray(v)
             for k, v in make_batch(cfg, B, T, seed=1).items()}

    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, batch)
    assert np.isfinite(float(loss)), f"{arch}: loss={loss}"
    flat = jax.tree.leaves(grads)
    assert flat, f"{arch}: empty grads"
    for g in flat:
        assert np.all(np.isfinite(np.asarray(g, np.float32))), \
            f"{arch}: non-finite grad"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_then_decode_matches_forward(arch):
    """Prefill T tokens then decode one more; the decode logits must match
    a full forward over T+1 tokens (numerical tolerance)."""
    cfg, model = _reduced(arch)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(B, T + 1)),
                       jnp.int32)
    max_len = T + 8
    cache = model.init_cache(B, max_len)

    if cfg.is_encoder_decoder:
        frames = jnp.asarray(
            rng.normal(size=(B, cfg.encoder_seq, cfg.d_model)) * 0.02,
            jnp.float32)
        logits_pre, cache = jax.jit(model.prefill)(params, toks[:, :T],
                                                   cache, frames)
        logits_dec, _ = jax.jit(model.decode_step)(
            params, toks[:, T:T + 1], cache, jnp.int32(T))
        assert logits_dec.shape == (B, cfg.vocab_size)
        assert np.all(np.isfinite(np.asarray(logits_dec, np.float32)))
        return

    logits_pre, cache = jax.jit(model.prefill)(params, toks[:, :T], cache)
    assert logits_pre.shape == (B, cfg.vocab_size)
    logits_dec, cache2 = jax.jit(model.decode_step)(
        params, toks[:, T:T + 1], cache, jnp.int32(T))
    assert logits_dec.shape == (B, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits_dec, np.float32)))

    # oracle: full forward over T+1 tokens (MoE layers route dropless
    # without a mesh, so no token is dropped in either)
    if hasattr(model, "forward"):
        full_logits, _ = jax.jit(model.forward)(params, toks)
        np.testing.assert_allclose(
            np.asarray(logits_dec, np.float32),
            np.asarray(full_logits[:, -1, :], np.float32),
            rtol=0.08, atol=0.08)


def test_reduced_configs_are_small():
    for arch in ARCH_IDS:
        cfg = get_config(arch).reduced()
        assert cfg.n_layers <= 4
        assert cfg.d_model <= 512
        assert cfg.n_experts <= 4
