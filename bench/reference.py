"""Plain float32 reference of the dense GQA/MHA decoder, in jax.numpy.

The block is the published Llama/Qwen1.5 one: RMSNorm, RoPE (rotate-half
pairs), grouped-query attention with an optional QKV bias, SwiGLU, and
residuals around both halves.  It imports nothing of the program under test
and makes its own weights from the seed (``bench.weights``).

It runs layer by layer, making each layer's weights again, so that it fits
on one chip beside nothing else.  Every matrix product runs at ``highest``
precision.  ``mode="int8"`` is the control, the precision one step below
the configuration's bfloat16 compute: every projection multiplies int8 codes
(weights per output column, activations per token, symmetric, scale
max|x|/127) with int32 accumulation, and attention runs in bfloat16 with
float32 accumulation.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

__all__ = ["logits_at", "MODES"]

MODES = ("f32", "int8")
Q_BLOCK = 1024            # query rows per attention block
PAD = 512                 # sequence lengths round up to this (fewer compiles)


def _mm(a, b, mode):
    if mode == "f32":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
    qa, sa = _int8(a, -1)
    qb, sb = _int8(b, 0)
    return jnp.matmul(qa, qb, preferred_element_type=jnp.int32) * sa * sb


def _int8(x, axis):
    """Symmetric int8 codes and scales, one scale per slice along ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale).astype(jnp.int8), scale


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, mode):
    """Causal attention; q (S, H, hd), k/v (S, KV, hd) -> (S, H*hd)."""
    s, h, hd = q.shape
    g = h // k.shape[1]
    k = jnp.repeat(k, g, axis=1)          # head i reads kv head i // g
    v = jnp.repeat(v, g, axis=1)
    prec = jax.lax.Precision.HIGHEST if mode == "f32" else None
    cd = jnp.float32 if mode == "f32" else jnp.bfloat16
    kpos = jnp.arange(s)
    outs = []
    for start in range(0, s, Q_BLOCK):
        qb = q[start:start + Q_BLOCK]
        sc = jnp.einsum("qhd,khd->hqk", qb.astype(cd), k.astype(cd), precision=prec,
                        preferred_element_type=jnp.float32) * hd ** -0.5
        qpos = start + jnp.arange(qb.shape[0])
        sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p.astype(cd), v.astype(cd), precision=prec,
                               preferred_element_type=jnp.float32))
    return jnp.concatenate(outs).reshape(s, h * hd)


def _block(x, p, dims, mode):
    s = x.shape[0]
    pos = jnp.arange(s)
    h = _rms(x, p["attn_norm"], dims.eps)
    q = _mm(h, p["wq"], mode) + p.get("bq", 0.0)
    k = _mm(h, p["wk"], mode) + p.get("bk", 0.0)
    v = _mm(h, p["wv"], mode) + p.get("bv", 0.0)
    q = _rope(q.reshape(s, dims.heads, dims.hd), pos, dims.theta)
    k = _rope(k.reshape(s, dims.kv, dims.hd), pos, dims.theta)
    v = v.reshape(s, dims.kv, dims.hd)
    x = x + _mm(_attention(q, k, v, mode), p["wo"], mode)
    h = _rms(x, p["ffn_norm"], dims.eps)
    ff = jax.nn.silu(_mm(h, p["w_gate"], mode)) * _mm(h, p["w_up"], mode)
    return x + _mm(ff, p["w_down"], mode)


@functools.lru_cache(maxsize=None)
def _programs(frozen_dims, mode):
    dims = W.Dims(frozen_dims)

    @jax.jit
    def first(key, tokens):
        return W.embed(key, dims)[tokens]

    @jax.jit
    def layer(key, i, x):
        return _block(x, W.layer_weights(key, dims, i), dims, mode)

    @jax.jit
    def last(key, x, rows):
        h = _rms(x[rows], W.final_norm(key, dims), dims.eps)
        return _mm(h, W.head(key, dims), mode)

    return first, layer, last


def _hidden(key, dims, tokens, mode, pad_to):
    first, layer, _ = _programs(tuple(sorted(dims.items())), mode)
    s = len(tokens)
    padded = np.zeros(-(-max(s, pad_to) // PAD) * PAD, np.int32)
    padded[:s] = tokens
    x = first(key, jnp.asarray(padded))
    for i in range(dims.layers):
        x = layer(key, jnp.int32(i), x)
    return x


def logits_at(key, dims: W.Dims, tokens: np.ndarray, rows: Sequence[int],
              mode: str = "f32", pad_to: int = 0) -> np.ndarray:
    """Logits (len(rows), vocab) of the causal forward over ``tokens`` at the
    positions ``rows``.  The sequence is padded at its end to ``pad_to``
    tokens or more (a multiple of 512), so that sequences of many lengths
    share one compiled program; causality keeps the padding out."""
    x = _hidden(key, dims, np.asarray(tokens, np.int32), mode, pad_to)
    _, _, last = _programs(tuple(sorted(dims.items())), mode)
    rows = np.asarray(rows, np.int32)
    fixed = np.full(-(-rows.size // PAD) * PAD, rows[-1], np.int32)
    fixed[:rows.size] = rows
    return np.asarray(last(key, x, jnp.asarray(fixed)))[:rows.size]
