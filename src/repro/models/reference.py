"""Plain float32 reference of DeepSeek-V2 (MLA + MoE), in jax.numpy.

One sequence, the whole causal forward at once: no kernels, no cache, no
batching, no capacity, every product at ``highest`` precision.  It reads the
program's parameter tree (natural layout; ``api.DipWeight`` storage is
de-sheared first) and follows the published model
(huggingface.co/deepseek-ai/DeepSeek-V2-Lite, ``modeling_deepseek.py``):

* RMSNorm before attention and FFN, residuals around both;
* MLA without q compression: q = x W_q split into nope and rope parts; the
  latent c = RMSNorm(x W_dkv) (``kv_a_layernorm``); k_pe = x W_krope shared
  by every head; K = [c W_uk ; k_pe], V = c W_uv; softmax scale
  ``(nope + rope) ** -0.5 * m(mscale_all_dim) ** 2`` (YaRN);
* YaRN rope on q_pe and k_pe: ``yarn_find_correction_range`` ramp over the
  frequencies, cos/sin scaled by ``m(mscale) / m(mscale_all_dim)``;
* the first ``first_k_dense`` layers a SwiGLU of width d_ff, then MoE
  layers: softmax over all routed experts, top-k, gates renormalized only
  under ``norm_topk_prob`` (else scaled by ``routed_scaling_factor``), and
  the shared experts (one SwiGLU of width ``n_shared * d_ff_expert``).

Departures, each fixed by the configuration rather than the mathematics:

* the rope columns of q_pe / k_pe pair channel i with i + rope/2
  (rotate-half); the published code de-interleaves them first.  That is a
  fixed permutation of those weight columns, which random weights do not
  see;
* W_dkv / W_krope and W_uk / W_uv are the published ``kv_a_proj_with_mqa``
  and ``kv_b_proj`` split by column (per head for the latter);
* the chip's share of the experts (``model-configs`` §4): only the experts
  ``moe_first_expert .. + held_experts`` are computed, as in the program;
  the router still scores all ``n_experts``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["forward"]


def _nat(w):
    return w.to_natural() if hasattr(w, "to_natural") else w


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _m(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _rope_tables(s, dim, theta, yarn):
    inv = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    scale = 1.0
    if yarn is not None:
        def corr(rot):
            return dim * math.log(yarn.original_max_position / (rot * 2 * math.pi)) \
                / (2 * math.log(theta))
        low = max(math.floor(corr(yarn.beta_fast)), 0)
        high = min(math.ceil(corr(yarn.beta_slow)), dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
        inv = inv / yarn.factor * ramp + inv * (1 - ramp)
        scale = _m(yarn.factor, yarn.mscale) / _m(yarn.factor, yarn.mscale_all_dim)
    ang = np.arange(s)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang) * scale, jnp.float32),
            jnp.asarray(np.sin(ang) * scale, jnp.float32))


def _rotate(x, cos, sin):
    """x (S, ..., dim): pairs (i, i + dim/2) rotated by the position's angle."""
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (-1,)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    a, b = jnp.split(x, 2, -1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _mla(h, p, cfg, cos, sin):
    s = h.shape[0]
    nh, dn, dr, dv = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    q = (h @ _nat(p["wq"])).reshape(s, nh, dn + dr)
    c = _rms(h @ _nat(p["w_dkv"]), p["kv_norm"], cfg.norm_eps)
    k_pe = _rotate(h @ _nat(p["w_krope"]), cos, sin)                 # (S, dr)
    q = jnp.concatenate([q[..., :dn], _rotate(q[..., dn:], cos, sin)], -1)
    k_nope = jnp.einsum("sr,rhd->shd", c, _nat(p["w_uk"]).reshape(r, nh, dn))
    v = jnp.einsum("sr,rhd->shd", c, _nat(p["w_uv"]).reshape(r, nh, dv))
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe[:, None], (s, nh, dr))], -1)
    scale = (dn + dr) ** -0.5
    if cfg.rope_yarn is not None:
        scale *= _m(cfg.rope_yarn.factor, cfg.rope_yarn.mscale_all_dim) ** 2
    scores = jnp.einsum("qhd,khd->hqk", q, k) * scale
    causal = np.tril(np.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, nh * dv)
    return out @ _nat(p["wo"])


def _swiglu(h, wg, wu, wd):
    return (_silu(h @ _nat(wg)) * (h @ _nat(wu))) @ _nat(wd)


def _moe(h, p, cfg):
    probs = jax.nn.softmax(h @ p["router"], -1)                     # (S, E)
    gates, ids = jax.lax.top_k(probs, cfg.moe_top_k)
    if cfg.norm_topk_prob:
        gates = gates / gates.sum(-1, keepdims=True)
    else:
        gates = gates * cfg.routed_scaling_factor
    out = _swiglu(h, p["shared_w_gate"], p["shared_w_up"], p["shared_w_down"]) \
        if cfg.n_shared_experts else jnp.zeros_like(h)
    for j in range(cfg.held_experts):                  # the experts held here
        weight = jnp.where(ids == cfg.moe_first_expert + j, gates, 0.0).sum(-1)
        out = out + weight[:, None] * _swiglu(
            h, p["w_gate"][j], p["w_up"][j], p["w_down"][j])
    return out


def forward(params, cfg, tokens) -> jax.Array:
    """Logits (S, vocab_size) of the causal forward over ``tokens`` (S,)."""
    tokens = np.asarray(tokens)
    layer = lambda tree, i: jax.tree_util.tree_map(lambda t: t[i], tree)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(params["embed"], jnp.float32)[tokens]
        cos, sin = _rope_tables(tokens.size, cfg.qk_rope_head_dim, cfg.rope_theta,
                                cfg.rope_yarn)
        for i in range(cfg.n_layers):
            lp = layer(params["layers"], i)
            x = x + _mla(_rms(x, lp["attn_norm"], cfg.norm_eps), lp, cfg, cos, sin)
            h = _rms(x, lp["ffn_norm"], cfg.norm_eps)
            if i < cfg.first_k_dense:
                dp = layer(params["dense"], i)
                x = x + _swiglu(h, dp["w_gate"], dp["w_up"], dp["w_down"])
            else:
                mp = (layer(params["moe"], i - cfg.first_k_dense)
                      if cfg.first_k_dense else lp)
                x = x + _moe(h, mp, cfg)
        x = _rms(x, params["final_norm"], cfg.norm_eps)
        logits = x @ _nat(params["lm_head"])
    return logits[:, :cfg.vocab_size]
