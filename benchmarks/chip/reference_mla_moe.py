"""A plain DeepSeek-V2 decoder, in float32 `jax.numpy`, written from the
configuration file alone.  Nothing here imports the program under test.

It follows the published equations (arXiv:2405.04434 and the model's own
modelling code) over whole sequences, with no kernel, cache or batching:

* RMSNorm before attention and before the feed-forward, and at the end;
* multi-head latent attention in its plain (non-absorbed) form: q from
  `wq`; the latent `c_kv = RMSNorm(x @ w_dkv)`; per-head keys
  `[c_kv @ w_uk, rope(x @ w_krope)]` (one rope key shared by every head)
  and values `c_kv @ w_uv`; causal softmax at scale
  mscale(factor, mscale_all_dim)^2 / sqrt(qk_nope + qk_rope);
* YaRN rope: DeepSeek-V2's inverse frequencies (base frequency below the
  correction range, divided by `factor` above it, a linear ramp between),
  cos and sin scaled by mscale / mscale_all_dim;
* a dense SiLU-gated MLP in the first `first_k_dense_replace` layers;
  then softmax routing in float32 over all experts, top
  `num_experts_per_tok`, gates renormalised only with `norm_topk_prob`,
  every expert computed densely over every token and weighted by its gate
  (zero where the expert was not chosen), plus the shared experts (one
  MLP of n_shared x the expert width, as published).

Departures from the published model:

* rope rotates halves (dimension i with i + d/2).  The published weights
  store the rope dims interleaved and de-interleave them first; on
  random weights that is a fixed permutation of the rope columns of `wq`
  and `w_krope`, and the program under test uses the same convention on
  the same tensors;
* `w_uk` (r, H, nope) and `w_uv` (r, H, v) are the two halves of the
  published `kv_b_proj`, held apart.

Matrix products take `reference.product`'s modes: `"fp32"` is the
reference; `"fp8"` (operands rounded to float8 e4m3) is the control a
step below the configuration's bfloat16.  The router's product is always
float32, as published.

Weights: "embed" (V, d), "ln_f" (d,), "unembed" (d, V) and "layers", an
iterable of per-layer dicts: "ln1", "ln2", "attn" (wq, w_dkv, w_krope,
kv_norm, w_uk, w_uv, wo) and "ffn" (w_gate, w_up, w_down; an MoE layer
adds "router" (d, E) and "shared" (w_gate, w_up, w_down), its expert
weights stacked (E, ...)).  Each is cast to float32 where it is used, one
expert at a time, so no float32 copy of a layer's experts is made.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from reference import einsum, mm, rms_norm

HIGHEST = jax.lax.Precision.HIGHEST


def _f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def inv_freq(cfg: dict) -> np.ndarray:
    """The rope head's inverse frequencies (float64)."""
    dim, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    freq = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    rs = cfg.get("rope_scaling")
    if not rs:
        return freq

    def correction_dim(rot):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return freq / rs["factor"] * (1.0 - keep) + freq * keep


def softmax_scale(cfg: dict) -> float:
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    rs = cfg.get("rope_scaling")
    if rs and rs.get("mscale_all_dim"):
        scale *= yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


def rope(x, cfg: dict):
    """x (B, T, H, d) at positions 0 .. T - 1, halves rotated."""
    t, d = x.shape[1], x.shape[-1]
    ang = np.arange(t, dtype=np.float64)[:, None] * inv_freq(cfg)[None]
    rs = cfg.get("rope_scaling") or {}
    f = rs.get("factor", 1.0)
    attn = (yarn_mscale(f, rs.get("mscale", 1.0))
            / yarn_mscale(f, rs.get("mscale_all_dim", 0.0)))
    cos = jnp.asarray(np.cos(ang) * attn, jnp.float32)[None, :, None]
    sin = jnp.asarray(np.sin(ang) * attn, jnp.float32)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def mla(x, p: dict, cfg: dict, mode: str):
    b, t, _ = x.shape
    h = cfg["num_attention_heads"]
    nd, rd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    q = mm(x, p["wq"], mode).reshape(b, t, h, nd + rd)
    q_nope, q_rope = q[..., :nd], rope(q[..., nd:], cfg)
    c_kv = rms_norm(mm(x, p["w_dkv"], mode), _f32(p["kv_norm"]),
                    cfg["rms_norm_eps"])
    k_rope = rope(mm(x, p["w_krope"], mode)[:, :, None, :], cfg)
    k_nope = einsum("btr,rhn->bthn", c_kv, p["w_uk"], mode)
    v = einsum("btr,rhv->bthv", c_kv, p["w_uv"], mode)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, t, h, rd))],
                        -1)
    q = jnp.concatenate([q_nope, q_rope], -1)
    s = einsum("bthd,bshd->bhts", q, k, mode) * softmax_scale(cfg)
    s = jnp.where(np.tril(np.ones((t, t), bool)), s, -jnp.inf)
    o = einsum("bhts,bshv->bthv", jax.nn.softmax(s, -1), v, mode)
    return mm(o.reshape(b, t, -1), p["wo"], mode)


def mlp(x, p: dict, mode: str):
    return mm(jax.nn.silu(mm(x, p["w_gate"], mode))
              * mm(x, p["w_up"], mode), p["w_down"], mode)


def route(x, router, cfg: dict):
    """Each token's gate per expert (N, E): its softmax score where the
    expert is among its top `num_experts_per_tok`, else zero."""
    probs = jax.nn.softmax(jnp.matmul(x, _f32(router), precision=HIGHEST),
                           -1)
    top, experts = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / top.sum(-1, keepdims=True)
    return jnp.zeros_like(probs).at[
        jnp.arange(probs.shape[0])[:, None], experts].set(top)


def moe(x, p: dict, cfg: dict, mode: str):
    b, t, d = x.shape
    xt = x.reshape(b * t, d)
    gates = route(xt, p["router"], cfg)

    def expert(y, ew):
        w_gate, w_up, w_down, g = ew
        out = mlp(xt, {"w_gate": w_gate, "w_up": w_up, "w_down": w_down},
                  mode)
        return y + g[:, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(xt),
                        (p["w_gate"], p["w_up"], p["w_down"], gates.T))
    return (y + mlp(xt, p["shared"], mode)).reshape(b, t, d)


def layer(x, p: dict, cfg: dict, mode: str):
    eps = cfg["rms_norm_eps"]
    x = x + mla(rms_norm(x, _f32(p["ln1"]), eps), p["attn"], cfg, mode)
    a = rms_norm(x, _f32(p["ln2"]), eps)
    return x + (moe(a, p["ffn"], cfg, mode) if "router" in p["ffn"]
                else mlp(a, p["ffn"], mode))


class DeepseekV2:
    """A DeepSeek-V2 decoder's logits over whole sequences, one layer at a
    time (one program per layer kind)."""

    def __init__(self, cfg: dict, mode: str = "fp32"):
        self.cfg = cfg
        self._layer = jax.jit(lambda x, p: layer(x, p, cfg, mode))
        self._head = jax.jit(lambda x, a, u: mm(
            rms_norm(x, _f32(a), cfg["rms_norm_eps"]), u, mode))

    def logits(self, tokens, weights):
        """(B, T, V) float32 logits of `tokens` (B, T)."""
        x = _f32(jnp.asarray(weights["embed"])[jnp.asarray(tokens)])
        for p in weights["layers"]:
            x = self._layer(x, p)
        return self._head(x, weights["ln_f"], weights["unembed"])
