"""Seeded natural-layout weights of a dense GQA/MHA decoder.

The benchmark makes the weights itself, so that the plain reference and the
program under test read the same numbers without the reference taking
anything the program made.  Every leaf of every layer has its own key,
``fold_in(fold_in(seed_key, leaf_id), layer)``, so one layer's weights can be
made again on their own when the reference runs layer by layer (alike to the
last bit or two: XLA may fuse the scaling differently in another program).

Scales: projections N(0, 1/fan_in); embedding N(0, 1); norm gains
1 + 0.1 N(0, 1); QKV biases 0.2 N(0, 1).  Gains and biases are random on
purpose, so a path that drops one shows in the logits.
"""

from __future__ import annotations

import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

__all__ = ["Dims", "seed_key", "layer_shapes", "layer_weights", "embed", "head",
           "final_norm"]


class Dims(dict):
    """The sizes the generator and the reference need, read from a
    configuration file (Hugging Face key names)."""

    d = property(lambda s: s["hidden_size"])
    ff = property(lambda s: s["intermediate_size"])
    heads = property(lambda s: s["num_attention_heads"])
    kv = property(lambda s: s["num_key_value_heads"])
    hd = property(lambda s: s.get("head_dim") or s["hidden_size"] // s["num_attention_heads"])
    layers = property(lambda s: s["num_hidden_layers"])
    vocab = property(lambda s: s["vocab_size"])
    bias = property(lambda s: bool(s.get("attention_bias", False)))
    theta = property(lambda s: float(s["rope_theta"]))
    eps = property(lambda s: float(s["rms_norm_eps"]))


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number, 64 bits of it."""
    seed = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def _leaf_key(key, name: str, layer: int):
    return jax.random.fold_in(jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF),
                              layer)


def layer_shapes(dims: Dims) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (natural shape, kind) of one layer's leaves."""
    d, hd = dims.d, dims.hd
    q, kv = dims.heads * hd, dims.kv * hd
    out = {
        "attn_norm": ((d,), "gain"), "ffn_norm": ((d,), "gain"),
        "wq": ((d, q), "proj"), "wk": ((d, kv), "proj"), "wv": ((d, kv), "proj"),
        "wo": ((q, d), "proj"),
        "w_gate": ((d, dims.ff), "proj"), "w_up": ((d, dims.ff), "proj"),
        "w_down": ((dims.ff, d), "proj"),
    }
    if dims.bias:
        out.update(bq=((q,), "bias"), bk=((kv,), "bias"), bv=((kv,), "bias"))
    return out


def _make(key, shape, kind):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "proj":
        return z * (shape[0] ** -0.5)
    if kind == "gain":
        return 1.0 + 0.1 * z
    if kind == "bias":
        return 0.2 * z
    return z                                           # embedding


def layer_weights(key, dims: Dims, layer) -> Dict[str, jax.Array]:
    """One layer's natural float32 weights (``layer`` may be traced)."""
    return {nm: _make(_leaf_key(key, nm, layer), shp, kind)
            for nm, (shp, kind) in layer_shapes(dims).items()}


def embed(key, dims: Dims) -> jax.Array:
    return _make(_leaf_key(key, "embed", 0), (dims.vocab, dims.d), "embed")


def head(key, dims: Dims) -> jax.Array:
    return _make(_leaf_key(key, "lm_head", 0), (dims.d, dims.vocab), "proj")


def final_norm(key, dims: Dims) -> jax.Array:
    return _make(_leaf_key(key, "final_norm", 0), (dims.d,), "gain")
