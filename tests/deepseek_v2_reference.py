"""A plain DeepSeek-V2 decoder in float32 `jax.numpy`, for the tests.

It follows the published equations (arXiv:2405.04434 and the model's own
modelling code) over whole sequences, with no kernel, cache or batching
trick, every product at `Precision.HIGHEST`:

* RMSNorm before attention and before the feed-forward, and at the end;
* multi-head latent attention in its plain (non-absorbed) form: q from
  `wq`; the latent `c_kv = RMSNorm(x @ w_dkv)`; per-head keys
  `[c_kv @ w_uk, rope(x @ w_krope)]` (one rope key shared by every head)
  and values `c_kv @ w_uv`; causal softmax at scale
  mscale(factor, mscale_all_dim)^2 / sqrt(qk_nope + qk_rope);
* YaRN rope: DeepSeek-V2's inverse frequencies (base frequency below the
  correction range, divided by `factor` above it, a linear ramp between),
  cos and sin scaled by mscale / mscale_all_dim;
* a dense SiLU-gated MLP in the first `first_dense` layers; then softmax
  routing in float32 over all experts, top-k, gates renormalised only
  with `norm_topk_prob`, every expert computed densely over every token
  and weighted by its gate (zero where the expert was not chosen), plus
  the shared experts (one MLP of n_shared x the expert width, as
  published).

Departures from the published model:

* rope rotates halves (dimension i with i + d/2).  The published weights
  store the rope dims interleaved and de-interleave them first; on
  random weights that is a fixed permutation of the rope columns of `wq`
  and `w_krope`, so the two conventions give the same model family;
* `w_uk` (r, H, nope) and `w_uv` (r, H, v) are the two halves of the
  published `kv_b_proj`, held apart.

The benchmark keeps its own copy (`benchmarks/chip/reference_mla_moe.py`)
so that its yardstick does not move with the tests.

`cfg` is a dict of the published config.json's keys; weights are a dict
of "embed" (V, d), "ln_f" (d,), "unembed" (d, V) and "layers": per layer
"ln1", "ln2", "attn" (wq, w_dkv, w_krope, kv_norm, w_uk, w_uv, wo) and
"ffn" (w_gate, w_up, w_down; an MoE layer adds "router" (d, E) and
"shared" (w_gate, w_up, w_down), its expert weights stacked (E, ...)).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


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


def mla(x, p: dict, cfg: dict):
    b, t, _ = x.shape
    h = cfg["num_attention_heads"]
    nd, rd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    q = mm(x, p["wq"]).reshape(b, t, h, nd + rd)
    q_nope, q_rope = q[..., :nd], rope(q[..., nd:], cfg)
    c_kv = rms_norm(mm(x, p["w_dkv"]), p["kv_norm"], cfg["rms_norm_eps"])
    k_rope = rope(mm(x, p["w_krope"])[:, :, None, :], cfg)
    k_nope = jnp.einsum("btr,rhn->bthn", c_kv, p["w_uk"], precision=HIGHEST)
    v = jnp.einsum("btr,rhv->bthv", c_kv, p["w_uv"], precision=HIGHEST)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, t, h, rd))],
                        -1)
    q = jnp.concatenate([q_nope, q_rope], -1)
    s = jnp.einsum("bthd,bshd->bhts", q, k, precision=HIGHEST)
    s = s * softmax_scale(cfg)
    s = jnp.where(np.tril(np.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("bhts,bshv->bthv", jax.nn.softmax(s, -1), v,
                   precision=HIGHEST)
    return mm(o.reshape(b, t, -1), p["wo"])


def mlp(x, p: dict):
    return mm(jax.nn.silu(mm(x, p["w_gate"])) * mm(x, p["w_up"]),
              p["w_down"])


def route(x, router, cfg: dict):
    """(gates (N, E), experts (N, k)): each token's gate per expert, zero
    where the expert is not among its top k."""
    probs = jax.nn.softmax(mm(x, router), -1)
    top, experts = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / top.sum(-1, keepdims=True)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(probs.shape[0])[:, None], experts].set(top)
    return gates, experts


def moe(x, p: dict, cfg: dict):
    b, t, d = x.shape
    xt = x.reshape(b * t, d)
    gates, _ = route(xt, p["router"], cfg)

    def expert(y, ew):
        w_gate, w_up, w_down, g = ew
        out = mlp(xt, {"w_gate": w_gate, "w_up": w_up, "w_down": w_down})
        return y + g[:, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(xt),
                        (p["w_gate"], p["w_up"], p["w_down"], gates.T))
    return (y + mlp(xt, p["shared"])).reshape(b, t, d)


def layer(x, p: dict, cfg: dict):
    eps = cfg["rms_norm_eps"]
    x = x + mla(rms_norm(x, p["ln1"], eps), p["attn"], cfg)
    a = rms_norm(x, p["ln2"], eps)
    return x + (moe(a, p["ffn"], cfg) if "router" in p["ffn"]
                else mlp(a, p["ffn"]))


def logits(tokens, weights: dict, cfg: dict):
    """(B, T, V) float32 logits of `tokens` (B, T)."""
    x = _f32(weights["embed"])[jnp.asarray(tokens)]
    for p in weights["layers"]:
        x = layer(x, _f32(p), cfg)
    x = rms_norm(x, _f32(weights["ln_f"]), cfg["rms_norm_eps"])
    return mm(x, _f32(weights["unembed"]))


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def from_program(params: dict) -> dict:
    """The program's parameter tree (`TransformerModel.init`: the dense
    prologue, then one stacked pattern block) as this module's layers."""
    def attn(a):
        return {k: a[k] for k in ("wq", "w_dkv", "w_krope", "kv_norm",
                                  "w_uk", "w_uv", "wo")}

    layers = [{"ln1": blk["ln1"], "ln2": blk["ln2"],
               "attn": attn(blk["attn"]), "ffn": dict(blk["ffn"])}
              for blk in params["prologue"]]
    stack = params["pattern"][0]
    for i in range(stack["ln1"].shape[0]):
        blk = jax.tree.map(lambda a: a[i], stack)
        layers.append({"ln1": blk["ln1"], "ln2": blk["ln2"],
                       "attn": attn(blk["attn"]), "ffn": blk["ffn"]})
    return {"embed": params["embed"], "unembed": params["unembed"],
            "ln_f": params["ln_f"], "layers": layers}


def published(cfg) -> dict:
    """A program `ModelConfig` as the config.json keys this module reads."""
    rs = cfg.rope_scaling
    return {
        "num_attention_heads": cfg.n_heads, "rms_norm_eps": cfg.norm_eps,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "rope_theta": cfg.rope_theta,
        "num_experts_per_tok": cfg.experts_per_token,
        "norm_topk_prob": cfg.norm_topk_prob,
        "rope_scaling": None if rs is None else {
            "factor": rs.factor, "beta_fast": rs.beta_fast,
            "beta_slow": rs.beta_slow, "mscale": rs.mscale,
            "mscale_all_dim": rs.mscale_all_dim,
            "original_max_position_embeddings":
                rs.original_max_position_embeddings},
    }
