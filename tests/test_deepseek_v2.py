"""DeepSeek-V2-Lite on the normal path, against a plain reference.

A reduced DeepSeek-V2-Lite (3 layers: the dense one and two with 8
experts, top 6; latent attention with YaRN rope), in float32 on the CPU,
checked against `deepseek_v2_reference` on the same seeded weights:

* `forward` logits;
* decode through the cache with a (B,) position vector, rows at
  different positions, some rows idle each step, and the expert counters
  the step accumulates, recounted from the reference's routing;
* `ContinuousScheduler` serving greedy requests: every served token is
  the reference's best at its position;
* dropless routing with both grouped matmuls (the Pallas kernel in
  interpret mode, `ragged_dot`), when one expert receives every row, with
  and without renormalised gates; under a mesh too, where only
  `moe_local_dispatch` selects the shard-local capacity dispatch;
* YaRN's inverse frequencies and softmax factor at the published sizes.

Tolerances: the program and the reference both compute in float32, and
differ by the order of their sums (absorbed against plain attention,
grouped against dense experts), about 1e-7 relative; 1e-5 leaves room for
that and fails on any error in the mathematics, such as a missing mscale
(a 59% change of the attention logits) or a renormalised gate.
"""
import dataclasses
import json
import math
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepseek_v2_reference as ref
from repro.models import build_model, get_config
from repro.models import moe as moe_mod
from repro.models.layers import (apply_rope, rope_frequencies,
                                 yarn_softmax_factor)
from repro.serving import ContinuousScheduler, Request, SchedulerConfig

TOL = 1e-5


def rel_err(y, r) -> float:
    y, r = np.asarray(y, np.float64), np.asarray(r, np.float64)
    return float(np.abs(y - r).max() / np.abs(r).max())


@pytest.fixture(scope="module")
def small():
    cfg = dataclasses.replace(
        get_config("deepseek_v2_lite").reduced(n_layers=3, d_model=64,
                                               n_experts=8),
        dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    # distinct norm scales, so that a swapped or skipped norm shows
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a * (1.0 + 0.2 * jax.random.normal(
            jax.random.PRNGKey(len(str(path))), a.shape))
        if "norm" in str(path) or "ln" in str(path) else a, params)
    return cfg, model, params, ref.published(cfg), ref.from_program(params)


def test_reduced_config_keeps_routing_and_rope_scaling(small):
    cfg = small[0]
    assert cfg.rope_scaling == get_config("deepseek_v2_lite").rope_scaling
    assert cfg.norm_topk_prob is False
    assert (cfg.n_experts, cfg.experts_per_token) == (8, 6)
    assert small[1].moe_layers == 2


def test_forward_matches_reference(small):
    cfg, model, params, rcfg, rw = small
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 12))
    got, _ = jax.jit(model.forward)(params, jnp.asarray(toks, jnp.int32))
    want = ref.logits(toks, rw, rcfg)
    assert rel_err(got, want) < TOL


def _reference_routes(tokens, rw, rcfg):
    """Per MoE layer, the experts (B, T, k) the reference routes to."""
    x = jnp.asarray(rw["embed"])[jnp.asarray(tokens)]
    out = []
    for p in rw["layers"]:
        if "router" in p["ffn"]:
            a = ref.rms_norm(ref.mla(ref.rms_norm(x, p["ln1"],
                                                  rcfg["rms_norm_eps"]),
                                     p["attn"], rcfg) + x,
                             p["ln2"], rcfg["rms_norm_eps"])
            _, e = ref.route(a.reshape(-1, a.shape[-1]), p["ffn"]["router"],
                             rcfg)
            out.append(np.asarray(e).reshape(*tokens.shape, -1))
        x = ref.layer(x, p, rcfg)
    return out


def test_decode_with_per_row_positions_matches_reference(small):
    """Prefill 4 tokens, then decode step by step with a (B,) position
    vector: each step a random set of rows is active and advances, the
    others re-feed their current position (an idempotent cache write) and
    are routed to no expert.  Every active row's logits match the
    reference's full forward at its own position, and the cache's expert
    counters equal a recount of the reference's routing."""
    cfg, model, params, rcfg, rw = small
    b, t, pre = 3, 14, 4
    rng = np.random.default_rng(1)
    toks = rng.integers(0, cfg.vocab_size, (b, t)).astype(np.int32)
    want = np.asarray(ref.logits(toks, rw, rcfg))
    routes = _reference_routes(toks, rw, rcfg)

    cache = model.init_cache(b, t)
    _, cache = jax.jit(model.prefill)(params, jnp.asarray(toks[:, :pre]),
                                      cache)
    step = jax.jit(model.decode_step)
    pos = np.full(b, pre, np.int32)
    hit = np.zeros(model.moe_layers, np.int64)
    rows = np.zeros(model.moe_layers, np.int64)
    seen_apart = False
    while (pos < t).any():
        active = (rng.random(b) < 0.6) & (pos < t)
        if not active.any():
            continue
        p = np.minimum(pos, t - 1)
        seen_apart |= len(set(p[active].tolist())) > 1
        feed = jnp.asarray(toks[np.arange(b), p][:, None])
        logits, cache = step(params, feed, cache, jnp.asarray(p), None,
                             jnp.asarray(active))
        for r in np.flatnonzero(active):
            assert rel_err(logits[r], want[r, p[r]]) < TOL, (r, p[r])
        for layer, e in enumerate(routes):
            chosen = e[np.flatnonzero(active), p[active]]
            hit[layer] += len(np.unique(chosen))
            rows[layer] += chosen.size
        pos += active
    assert seen_apart
    np.testing.assert_array_equal(np.asarray(cache["moe"]),
                                  np.stack([hit, rows], 1))


def test_scheduler_serves_reference_greedy_tokens(small):
    cfg, model, params, rcfg, rw = small
    rng = np.random.default_rng(2)
    reqs = [Request(rid=i, prompt=rng.integers(1, cfg.vocab_size,
                                               int(n)).astype(np.int32),
                    max_new_tokens=int(m), temperature=0.0,
                    arrival_s=0.002 * i)
            for i, (n, m) in enumerate([(3, 5), (7, 3), (2, 6), (5, 4),
                                        (4, 4), (6, 2)])]
    sched = ContinuousScheduler(cfg, model, params, config=SchedulerConfig(
        max_batch=4, max_len=16))
    report = sched.run(reqs)
    done = {c.rid: c.tokens for c in report.completions}
    assert sorted(done) == list(range(len(reqs)))
    for r in reqs:
        seq = np.concatenate([r.prompt, np.asarray(done[r.rid][:-1],
                                                   np.int32)])
        lg = np.asarray(ref.logits(seq[None], rw, rcfg))[0]
        for j, tok in enumerate(done[r.rid]):
            row = lg[len(r.prompt) - 1 + j]
            assert row.max() - row[tok] <= TOL * np.abs(row).max()
    # every routed row is one of a request's prompt or output positions
    served = sum(len(r.prompt) + r.max_new_tokens - 1 for r in reqs)
    assert report.moe_rows == [served * cfg.experts_per_token] * 2
    assert all(0 < h <= report.steps * cfg.n_experts
               for h in report.moe_experts_hit)


# ------------------------------------------------------ dropless routing

def _moe_case(norm_topk_prob: bool):
    cfg = dataclasses.replace(
        get_config("deepseek_v2_lite").reduced(d_model=64, n_experts=8),
        dtype="float32", norm_topk_prob=norm_topk_prob)
    p = moe_mod.init_moe(jax.random.PRNGKey(5), cfg, jnp.float32)
    # expert 3 is every row's first choice (a logit of at least 2 against
    # others of about 0.36 spread): 32 of 32 rows, where the capacity
    # dispatch would keep 1.25 * 32 * 6 / 8 = 30
    p["router"] = (0.3 * p["router"]).at[:, 3].set(0.0).at[0, 3].set(0.5)
    x = jax.random.normal(jax.random.PRNGKey(6), (32, cfg.d_model))
    x = x.at[:, 0].set(jnp.abs(x[:, 0]) + 4.0)
    return cfg, p, x


@pytest.mark.parametrize("impl", ["ragged_dot", "gmm_interpret"])
@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_dropless_routing_keeps_every_row(impl, norm_topk_prob):
    cfg, p, x = _moe_case(norm_topk_prob)
    rcfg = {"num_experts_per_tok": cfg.experts_per_token,
            "norm_topk_prob": norm_topk_prob}
    gates, experts = ref.route(x, p["router"], rcfg)
    assert (np.asarray(experts)[:, 0] == 3).all()
    sums = np.asarray(gates).sum(-1)
    if norm_topk_prob:
        np.testing.assert_allclose(sums, 1.0, rtol=1e-6)
    else:
        assert (sums < 0.99).all()            # the gates stay as routed
    want = ref.moe(x[None], p, rcfg)[0]
    y, _, counts = moe_mod._moe_dropless(p, x, cfg, impl=impl)
    assert rel_err(y, want) < TOL
    assert int(counts[1]) == 32 * cfg.experts_per_token
    # the capacity dispatch drops the rows beyond expert 3's capacity
    y_cap, _, _ = moe_mod._moe_core(p, x, cfg, capacity=30)
    assert rel_err(y_cap, want) > 100 * TOL


def test_idle_rows_route_to_no_expert():
    cfg, p, x = _moe_case(False)
    valid = jnp.arange(32) % 4 != 0
    y, _, counts = moe_mod._moe_dropless(p, x, cfg, valid)
    y_all, _, _ = moe_mod._moe_dropless(p, x, cfg)
    shared = moe_mod.mlp(p["shared"], x)
    np.testing.assert_allclose(y[valid], y_all[valid], rtol=1e-6)
    np.testing.assert_allclose(y[~valid], shared[~valid], rtol=1e-6)
    assert int(counts[1]) == 24 * cfg.experts_per_token


_MESH_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses, json
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from repro.models import get_config
    from repro.models import moe as moe_mod
    from repro.sharding.ctx import activation_mesh
    cfg = dataclasses.replace(
        get_config("deepseek_v2_lite").reduced(d_model=64, n_experts=8),
        dtype="float32", norm_topk_prob=False)
    p = moe_mod.init_moe(jax.random.PRNGKey(5), cfg, jnp.float32)
    p["router"] = (0.3 * p["router"]).at[:, 3].set(0.0).at[0, 3].set(0.5)
    x = jax.random.normal(jax.random.PRNGKey(6), (4, 8, cfg.d_model))
    x = x.at[..., 0].set(jnp.abs(x[..., 0]) + 4.0)
    valid = jnp.arange(4) != 2
    want, _, _ = moe_mod.moe_layer(p, x, cfg)
    _, _, want_counts = moe_mod.moe_layer(p, x, cfg, valid=valid)
    mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))
    out = {"want": [int(v) for v in want_counts]}
    for local, cf in ((False, 1.25), (True, 8.0), (True, 1.25)):
        c = dataclasses.replace(cfg, moe_local_dispatch=local)
        with activation_mesh(mesh):
            y, _, _ = jax.jit(
                lambda p, x: moe_mod.moe_layer(p, x, c, cf))(p, x)
            _, _, counts = jax.jit(
                lambda p, x: moe_mod.moe_layer(p, x, c, cf, valid=valid))(
                    p, x)
        err = float(jnp.abs(y - want).max() / jnp.abs(want).max())
        out[f"{local}/{cf}"] = [err, [int(v) for v in counts]]
    print("MOE_JSON:" + json.dumps(out))
""")


def test_moe_under_a_mesh():
    """On a 2 x 2 mesh of host devices, with every row's first choice on
    one expert: without `moe_local_dispatch` the layer stays dropless and
    agrees with the layer without a mesh; with it, the shard-local
    capacity dispatch agrees where the capacity holds every row (factor 8)
    and drops rows where it does not (1.25).  Both count the experts and
    pairs of the valid rows as the dropless layer does."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(
        os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", _MESH_PROG], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("MOE_JSON:")][0]
    out = json.loads(line[len("MOE_JSON:"):])
    assert out["want"][1] == 3 * 8 * 6
    assert out["False/1.25"][0] < TOL
    assert out["True/8.0"][0] < TOL
    assert out["True/1.25"][0] > 100 * TOL
    for key in ("False/1.25", "True/8.0", "True/1.25"):
        assert out[key][1] == out["want"]


@pytest.mark.parametrize("impl", ["ragged_dot", "gmm_interpret"])
def test_grouped_matmul_picks_a_layer_of_a_stack(impl):
    """A layer's experts picked from a stack (L, E, K, N) by offset equal
    the products with that layer's slice; rows past the groups are 0."""
    key = jax.random.split(jax.random.PRNGKey(9), 2)
    w = jax.random.normal(key[0], (3, 4, 32, 48))
    x = jax.random.normal(key[1], (40, 32))
    sizes = jnp.asarray([9, 0, 14, 11], jnp.int32)
    got = moe_mod.grouped_matmul(x, w, sizes, impl, layer=jnp.int32(1))
    ends = np.cumsum(sizes)
    for g, (lo, hi) in enumerate(zip(ends - sizes, ends)):
        np.testing.assert_allclose(got[lo:hi], x[lo:hi] @ w[1, g],
                                   rtol=1e-5, atol=1e-5)
    assert not np.asarray(got[ends[-1]:]).any()


def test_gmm_tiling_fits_published_widths():
    # gate and up (2048 -> 1408), down (1408 -> 2048), 64 rows x 6
    assert moe_mod.gmm_tiling(384, 2048, 1408) == (128, 1024, 1408)
    assert moe_mod.gmm_tiling(384, 1408, 2048) == (128, 1408, 1024)
    assert moe_mod.gmm_tiling(40, 64, 64) == (48, 64, 64)


# ------------------------------------------------------------------ YaRN

def test_yarn_frequencies_and_softmax_factor():
    scaling = get_config("deepseek_v2_lite").rope_scaling
    base = np.asarray(rope_frequencies(64, 1e4))
    yarn = np.asarray(rope_frequencies(64, 1e4, scaling))
    np.testing.assert_allclose(yarn[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(yarn[23:], base[23:] / 40, rtol=1e-6)
    between = yarn[11:23] / base[11:23]
    assert ((between < 1) & (between > 1 / 40)).all()
    assert (np.diff(between) < 0).all()
    want = (0.1 * 0.707 * math.log(40) + 1) ** 2
    assert abs(yarn_softmax_factor(scaling) - want) < 1e-12
    assert abs(want - 1.5896) < 1e-4
    rcfg = ref.published(get_config("deepseek_v2_lite"))
    np.testing.assert_allclose(yarn, ref.inv_freq(rcfg), rtol=1e-6)


def test_rope_without_scaling_is_unchanged():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 16))
    pos = jnp.broadcast_to(jnp.arange(5), (2, 5))
    freqs = 1.0 / (1e4 ** (np.arange(0, 16, 2) / 16))
    ang = np.arange(5)[:, None] * freqs
    cos, sin = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
    x1, x2 = np.asarray(x[..., :8]), np.asarray(x[..., 8:])
    want = np.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    np.testing.assert_allclose(apply_rope(x, pos, 1e4), want, atol=1e-5)
