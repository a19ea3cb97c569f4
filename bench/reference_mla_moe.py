"""Plain float32 reference of DeepSeek-V2 (MLA + MoE), in jax.numpy.

The published block (huggingface.co/deepseek-ai/DeepSeek-V2-Lite,
``modeling_deepseek.py``): RMSNorm; multi-head latent attention without q
compression, its latent normalized (``kv_a_layernorm``), K = [c W_uk ;
k_pe] and V = c W_uv over every head, YaRN rope on q_pe and k_pe and the
softmax scale (nope + rope) ** -0.5 times m(mscale_all_dim) ** 2; then a
dense SwiGLU in the first ``first_k_dense_replace`` layers and, after them,
a softmax router over all the published experts, top-k gates (renormalized
only under ``norm_topk_prob``, else scaled by ``routed_scaling_factor``),
the experts held here and the shared experts.  It imports nothing of the
program under test and makes its own weights from the seed
(``bench.weights_mla_moe``).  Departures: the rope columns pair channel i
with i + rope/2 where the published code de-interleaves them first (a fixed
permutation of those weight columns); only the held experts are computed,
as on the chip.

It runs layer by layer, making each layer's weights again, and attends in
blocks of query rows, so that it fits on one chip beside nothing else.
Every matrix product runs at ``highest`` precision.  ``mode="int8"`` is the
control, the precision one step below the configuration's bfloat16
compute: every projection (the router and the experts included) multiplies
int8 codes (weights per output column, activations per token, symmetric,
scale max|x|/127) with int32 accumulation, and attention runs in bfloat16
with float32 accumulation.
"""

from __future__ import annotations

import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights_mla_moe as W

__all__ = ["logits_at", "MODES"]

MODES = ("f32", "int8")
Q_BLOCK = 512             # query rows per attention block
PAD = 512                 # sequence lengths round up to this (fewer compiles)


def _int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale).astype(jnp.int8), scale


def _mm(a, b, mode):
    if mode == "f32":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
    qa, sa = _int8(a, -1)
    qb, sb = _int8(b, 0)
    return jnp.matmul(qa, qb, preferred_element_type=jnp.int32) * sa * sb


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _m(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _rope_tables(n, dims):
    """cos, sin (n, rope/2) of YaRN (``yarn_find_correction_range``)."""
    dim, theta, y = dims.rope, dims.theta, dims.yarn
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    scale = 1.0
    if y:
        def bound(rot):
            return dim * math.log(y["original_max_position_embeddings"] / (rot * 2 * math.pi)) \
                / (2 * math.log(theta))
        low = max(math.floor(bound(y["beta_fast"])), 0)
        high = min(math.ceil(bound(y["beta_slow"])), dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
        inv = inv / y["factor"] * ramp + inv * (1.0 - ramp)
        scale = _m(y["factor"], y["mscale"]) / _m(y["factor"], y["mscale_all_dim"])
    ang = np.arange(n, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang) * scale, jnp.float32),
            jnp.asarray(np.sin(ang) * scale, jnp.float32))


def _rotate(x, cos, sin):
    if x.ndim == 3:
        cos, sin = cos[:, None, :], sin[:, None, :]
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _softmax_scale(dims):
    scale = (dims.nope + dims.rope) ** -0.5
    if dims.yarn:
        scale *= _m(dims.yarn["factor"], dims.yarn["mscale_all_dim"]) ** 2
    return scale


def _attention(x, p, dims, tables, mode):
    s, h = x.shape[0], dims.heads
    hn = _rms(x, p["attn_norm"], dims.eps)
    q = _mm(hn, p["wq"], mode).reshape(s, h, dims.nope + dims.rope)
    c = _rms(_mm(hn, p["w_dkv"], mode), p["kv_norm"], dims.eps)
    k_pe = _rotate(_mm(hn, p["w_krope"], mode), *tables)                 # (S, rope)
    q = jnp.concatenate([q[..., :dims.nope], _rotate(q[..., dims.nope:], *tables)], -1)
    k = jnp.concatenate([_mm(c, p["w_uk"], mode).reshape(s, h, dims.nope),
                         jnp.broadcast_to(k_pe[:, None], (s, h, dims.rope))], -1)
    v = _mm(c, p["w_uv"], mode).reshape(s, h, dims.v)
    prec = jax.lax.Precision.HIGHEST if mode == "f32" else None
    cd = jnp.float32 if mode == "f32" else jnp.bfloat16
    kpos = jnp.arange(s)
    outs = []
    for start in range(0, s, Q_BLOCK):
        qb = q[start:start + Q_BLOCK]
        sc = jnp.einsum("qhd,khd->hqk", qb.astype(cd), k.astype(cd), precision=prec,
                        preferred_element_type=jnp.float32) * _softmax_scale(dims)
        qpos = start + jnp.arange(qb.shape[0])
        sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", pr.astype(cd), v.astype(cd), precision=prec,
                               preferred_element_type=jnp.float32))
    out = jnp.concatenate(outs).reshape(s, h * dims.v)
    return x + _mm(out, p["wo"], mode)


def _swiglu(h, wg, wu, wd, mode):
    return _mm(jax.nn.silu(_mm(h, wg, mode)) * _mm(h, wu, mode), wd, mode)


def _ffn(x, ap, fp, dims, dense, mode):
    h = _rms(x, ap["ffn_norm"], dims.eps)
    if dense:
        return x + _swiglu(h, fp["w_gate"], fp["w_up"], fp["w_down"], mode)
    probs = jax.nn.softmax(_mm(h, fp["router"], mode), axis=-1)
    gates, ids = jax.lax.top_k(probs, dims.top_k)
    if dims.norm_topk:
        gates = gates / gates.sum(-1, keepdims=True)
    else:
        gates = gates * dims.scaling
    out = _swiglu(h, fp["shared_w_gate"], fp["shared_w_up"], fp["shared_w_down"], mode)
    for j in range(dims.held):
        w = jnp.where(ids == dims.first + j, gates, 0.0).sum(-1)
        out = out + w[:, None] * _swiglu(h, fp["w_gate"][j], fp["w_up"][j],
                                         fp["w_down"][j], mode)
    return x + out


_PROGRAMS: dict = {}


def _programs(dims, mode):
    """The jitted programs of one configuration and mode, made once."""
    key = (dims.frozen(), mode)
    if key not in _PROGRAMS:
        _PROGRAMS[key] = _build(dims, mode)
    return _PROGRAMS[key]


def _build(dims, mode):
    @jax.jit
    def first(key, tokens):
        return W.embed(key, dims)[tokens]

    def layer(dense):
        @jax.jit
        def run(key, i, x):
            ap = W.attention_weights(key, dims, i)
            x = _attention(x, ap, dims, _rope_tables(x.shape[0], dims), mode)
            return _ffn(x, ap, W.ffn_weights(key, dims, i, dense), dims, dense, mode)
        return run

    @jax.jit
    def last(key, x, rows):
        h = _rms(x[rows], W.final_norm(key, dims), dims.eps)
        return _mm(h, W.head(key, dims), mode)

    return first, layer(True), layer(False), last


def logits_at(key, dims: W.Dims, tokens: np.ndarray, rows: Sequence[int],
              mode: str = "f32", pad_to: int = 0) -> np.ndarray:
    """Logits (len(rows), vocab) of the causal forward over ``tokens`` at the
    positions ``rows``.  The sequence is padded at its end to ``pad_to``
    tokens or more (a multiple of 512), so that sequences of many lengths
    share one compiled program; causality keeps the padding out."""
    first, dense, moe, last = _programs(dims, mode)
    tokens = np.asarray(tokens, np.int32)
    padded = np.zeros(-(-max(tokens.size, pad_to) // PAD) * PAD, np.int32)
    padded[:tokens.size] = tokens
    x = first(key, jnp.asarray(padded))
    for i in range(dims.layers):
        x = (dense if i < dims.dense_layers else moe)(key, jnp.int32(i), x)
    rows = np.asarray(rows, np.int32)
    fixed = np.full(-(-rows.size // PAD) * PAD, rows[-1], np.int32)
    fixed[:rows.size] = rows
    return np.asarray(last(key, x, jnp.asarray(fixed)))[:rows.size]
