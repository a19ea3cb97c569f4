"""Operations and bytes of the MLA + MoE family's work, counted from shapes.

As ``bench/work.py`` counts the dense pair's: the mathematics, whatever
implements it, each operand read and each result written once at the
bfloat16 compute width.  Attention is the expanded form, causal: every
head scores a (query, key) pair over ``nope + rope`` channels and sums
``v`` channels of it.  The routed experts count by the (token, slot) pairs
the program reports routed to the experts held here (its ``moe_routed``
counter), not by a dense formula: how many reach this chip's experts is
what the router decided.
"""

from __future__ import annotations

from bench.work import WIDTH

__all__ = ["attention_work", "shared_weights", "expert_flops", "model_flops",
           "counts_between"]


def attention_work(dims, q_len: int, offset: int) -> tuple:
    """(flops, bytes) of one layer's expanded latent attention for
    ``q_len`` queries that follow ``offset`` cached positions: queries and
    outputs of the chunk, per-head K and V of every key it meets."""
    h, qk, v = dims.heads, dims.nope + dims.rope, dims.v
    pairs = q_len * offset + q_len * (q_len + 1) // 2
    flops = 2 * h * (qk + v) * pairs
    nbytes = WIDTH * h * (q_len * (qk + v) + (offset + q_len) * (qk + v))
    return flops, nbytes


def shared_weights(dims) -> int:
    """Weights every token multiplies: attention, the dense layers' FFN,
    the MoE layers' router and shared experts, and the output head."""
    d, h, r = dims.d, dims.heads, dims.rank
    attn = (d * h * (dims.nope + dims.rope) + d * r + d * dims.rope
            + r * h * (dims.nope + dims.v) + h * dims.v * d)
    moe_layers = dims.layers - dims.dense_layers
    return (dims.layers * attn + dims.dense_layers * 3 * d * dims.ff
            + moe_layers * (d * dims.experts + 3 * d * dims.shared_ff) + d * dims.vocab)


def expert_flops(dims) -> int:
    """Operations of one (token, slot) pair through a routed expert."""
    return 2 * 3 * dims.d * dims.ffe


def model_flops(dims, tokens: int, context_sum: int, routed: int) -> float:
    """Model operations of ``tokens`` tokens whose contexts (keys each query
    meets, itself included) add up to ``context_sum``, with ``routed``
    (token, slot) pairs through the experts held here."""
    h, qk, v = dims.heads, dims.nope + dims.rope, dims.v
    return (2 * shared_weights(dims) * tokens + expert_flops(dims) * routed
            + 2 * h * (qk + v) * context_sum * dims.layers)


def counts_between(series, lo: float, hi: float):
    """(routed, rows, dropped) the program counted in the host interval
    [lo, hi), from a series of (time, routed, rows, dropped) readings of
    its running totals; None without two readings in it."""
    inside = [s for s in series if lo <= s[0] <= hi]
    if len(inside) < 2:
        return None
    a, b = inside[0], inside[-1]
    return tuple(y - x for x, y in zip(a[1:], b[1:]))
