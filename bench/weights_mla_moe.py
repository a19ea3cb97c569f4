"""Seeded natural-layout weights of a DeepSeek-V2 (MLA + MoE) decoder.

As ``bench/weights.py`` does for the dense pair: every leaf of every layer
has its own key, ``fold_in(fold_in(seed_key, leaf_id), layer)``, and each
routed expert one more ``fold_in`` by its index among all the router's
experts, so a chip's share is the same experts whatever it holds and one
layer's weights can be made again on their own.  Scales: projections
N(0, 1/fan_in), embedding N(0, 1), norm gains 1 + 0.1 N(0, 1).

Sizes come from the configuration file (Hugging Face key names): the
attention and norms of every layer; a dense SwiGLU of width
``intermediate_size`` in the first ``first_k_dense_replace`` layers; then a
router over ``published.n_routed_experts`` outputs, the
``n_routed_experts`` experts held here from ``first_held_expert``, and the
shared experts as one SwiGLU of width ``n_shared_experts`` x
``moe_intermediate_size``.
"""

from __future__ import annotations

import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from bench.weights import seed_key

__all__ = ["Dims", "seed_key", "attention_shapes", "ffn_shapes", "attention_weights",
           "ffn_weights", "embed", "head", "final_norm"]


class Dims(dict):
    """The sizes of a configuration file, by the names the code uses."""

    d = property(lambda s: s["hidden_size"])
    heads = property(lambda s: s["num_attention_heads"])
    nope = property(lambda s: s["qk_nope_head_dim"])
    rope = property(lambda s: s["qk_rope_head_dim"])
    v = property(lambda s: s["v_head_dim"])
    rank = property(lambda s: s["kv_lora_rank"])
    ff = property(lambda s: s["intermediate_size"])
    ffe = property(lambda s: s["moe_intermediate_size"])
    layers = property(lambda s: s["num_hidden_layers"])
    dense_layers = property(lambda s: s["first_k_dense_replace"])
    vocab = property(lambda s: s["vocab_size"])
    eps = property(lambda s: float(s["rms_norm_eps"]))
    theta = property(lambda s: float(s["rope_theta"]))
    top_k = property(lambda s: s["num_experts_per_tok"])
    held = property(lambda s: s["n_routed_experts"])
    first = property(lambda s: s["first_held_expert"])
    experts = property(lambda s: s["published"]["n_routed_experts"])
    shared_ff = property(lambda s: s["n_shared_experts"] * s["moe_intermediate_size"])
    norm_topk = property(lambda s: bool(s["norm_topk_prob"]))
    scaling = property(lambda s: float(s["routed_scaling_factor"]))
    yarn = property(lambda s: s.get("rope_scaling"))

    def frozen(self) -> tuple:
        """A hashable copy (for caches of compiled programs)."""
        return tuple(sorted((k, repr(v)) for k, v in self.items()))


def _leaf_key(key, name: str, layer):
    return jax.random.fold_in(jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF),
                              layer)


def _make(key, shape, kind):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "proj":
        return z * (shape[-2] ** -0.5)
    if kind == "gain":
        return 1.0 + 0.1 * z
    return z                                           # embedding


def attention_shapes(dims: Dims) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (natural shape, kind) of every layer's attention half and norms."""
    d, h = dims.d, dims.heads
    return {
        "attn_norm": ((d,), "gain"), "ffn_norm": ((d,), "gain"),
        "wq": ((d, h * (dims.nope + dims.rope)), "proj"),
        "w_dkv": ((d, dims.rank), "proj"), "kv_norm": ((dims.rank,), "gain"),
        "w_krope": ((d, dims.rope), "proj"),
        "w_uk": ((dims.rank, h * dims.nope), "proj"),
        "w_uv": ((dims.rank, h * dims.v), "proj"),
        "wo": ((h * dims.v, d), "proj"),
    }


def ffn_shapes(dims: Dims, dense: bool) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (natural shape, kind) of a dense or an MoE layer's FFN; the
    routed experts' banks lead with the experts held here."""
    d = dims.d
    if dense:
        return {"w_gate": ((d, dims.ff), "proj"), "w_up": ((d, dims.ff), "proj"),
                "w_down": ((dims.ff, d), "proj")}
    e, f, sf = dims.held, dims.ffe, dims.shared_ff
    return {
        "router": ((d, dims.experts), "proj"),
        "w_gate": ((e, d, f), "expert"), "w_up": ((e, d, f), "expert"),
        "w_down": ((e, f, d), "expert"),
        "shared_w_gate": ((d, sf), "proj"), "shared_w_up": ((d, sf), "proj"),
        "shared_w_down": ((sf, d), "proj"),
    }


def attention_weights(key, dims: Dims, layer) -> Dict[str, jax.Array]:
    """Layer ``layer``'s attention weights and norm gains (``layer`` may be
    traced)."""
    return {nm: _make(_leaf_key(key, nm, layer), shp, kind)
            for nm, (shp, kind) in attention_shapes(dims).items()}


def ffn_weights(key, dims: Dims, layer, dense: bool) -> Dict[str, jax.Array]:
    """The FFN weights of layer ``layer`` (which may be traced), a dense
    layer's or an MoE layer's."""
    out = {}
    for nm, (shp, kind) in ffn_shapes(dims, dense).items():
        k = _leaf_key(key, "ffn." + nm, layer)
        if kind == "expert":
            out[nm] = jnp.stack([_make(jax.random.fold_in(k, dims.first + j), shp[1:], "proj")
                                 for j in range(shp[0])])
        else:
            out[nm] = _make(k, shp, kind)
    return out


def embed(key, dims: Dims) -> jax.Array:
    return _make(_leaf_key(key, "embed", 0), (dims.vocab, dims.d), "embed")


def head(key, dims: Dims) -> jax.Array:
    return _make(_leaf_key(key, "lm_head", 0), (dims.d, dims.vocab), "proj")


def final_norm(key, dims: Dims) -> jax.Array:
    return _make(_leaf_key(key, "final_norm", 0), (dims.d,), "gain")
