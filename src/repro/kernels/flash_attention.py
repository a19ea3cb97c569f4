"""Fused flash-attention kernel (forward) — the §Perf pair-3 lever.

The 32k-prefill cells are memory-bound because the unfused online-softmax
streams (b, h, Sq, chunk) score tensors through HBM ~10x per layer
(docs/benchmarks.md).  This kernel keeps the running max / denominator /
accumulator in VMEM scratch across the KV-block grid dimension, so scores
never leave VMEM — the canonical flash-attention structure, and the same
lesson as DiP one level down: keep the hot tile resident in the fast tier.

Grid: (batch*heads, Sq/block_q, Sk/block_k), KV innermost ("arbitrary").
Blocks: q (block_q, d), k (block_k, d), v (block_k, dv), out (block_q, dv);
scratch: m/l (block_q, 1) f32, acc (block_q, dv) f32 — all VMEM.

Causal masking via absolute positions: ``q_offset`` (per batch*head row,
*traced* — one compile serves every prefill offset) places the query block
anywhere in the key sequence, which is exactly the serving chunked-prefill
shape: Sq new tokens attending a cache of ``q_offset`` earlier keys.
``kv_len`` bounds the live keys per row (cache capacity / Sk padding).
Both ride as scalar-prefetch operands: (BH,) int32 arrays in SMEM that the
kernel indexes with its batch*head grid index.  KV blocks entirely above the
causal diagonal or past ``kv_len`` are skipped (no MXU work, no VMEM
traffic for masked tiles — the block-diagonal savings that make causal
flash ~2x the throughput of the masked-dense form).

Serving-oriented: forward-only (prefill/decode have no backward); training
attention keeps the XLA online-softmax path.  Registered behind
``repro.api.attention`` (backend "flash") with tuning-table block sizes;
use that entry point unless you are benchmarking the raw kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas"]

NEG_INF = -1e30


def _kernel(qo_ref, kvl_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, scale: float, block_q: int, block_k: int, causal: bool):
    q_idx = pl.program_id(1)
    kv_idx = pl.program_id(2)

    @pl.when(kv_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    b = pl.program_id(0)
    qo = qo_ref[b]
    kvl = kvl_ref[b]
    kv_start = kv_idx * block_k

    # block skipping: a KV block entirely above the causal diagonal (its
    # first key is newer than this q block's newest query) or entirely past
    # the live keys contributes nothing — skip the matmuls outright.  The
    # init/flush stay outside the predicate so scratch and output are
    # always well-defined.
    relevant = kv_start < kvl
    if causal:
        relevant = jnp.logical_and(
            relevant, kv_start <= qo + (q_idx + 1) * block_q - 1
        )

    @pl.when(relevant)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale              # (bq, d)
        k = k_ref[0].astype(jnp.float32)                      # (bk, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)

        k_pos = kv_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        live = k_pos < kvl
        if causal:
            q_pos = qo + q_idx * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0
            )
            live = jnp.logical_and(live, q_pos >= k_pos)
        s = jnp.where(live, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        # a fully-masked row keeps m_new == NEG_INF, where exp(s - m_new)
        # would be exp(0) = 1 lane-wide — zero those lanes explicitly so the
        # row's denominator stays 0 and the flush emits 0, not garbage
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new))
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v_ref[0].astype(jnp.float32), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(kv_idx == pl.num_programs(2) - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _per_row_i32(val, bh: int, default: int) -> jax.Array:
    """Broadcast a None / scalar / (BH,) value to the (BH,) SMEM layout."""
    if val is None:
        val = default
    arr = jnp.asarray(val, jnp.int32)
    if arr.ndim == 0:
        arr = jnp.broadcast_to(arr, (bh,))
    return arr.reshape(bh)


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_k", "causal", "interpret", "scale")
)
def flash_attention_pallas(
    q: jax.Array,    # (BH, Sq, D) — batch*heads flattened
    k: jax.Array,    # (BH, Sk, D)
    v: jax.Array,    # (BH, Sk, Dv)
    *,
    q_offset=None,   # None | int | (BH,) — absolute key position of q row 0
    kv_len=None,     # None | int | (BH,) — live keys per row (defaults to Sk)
    block_q: int = 512,
    block_k: int = 512,
    causal: bool = True,
    scale: float = None,   # None -> D ** -0.5 (pass 1.0 for pre-scaled q)
    interpret: bool = False,
):
    """Pads Sq/Sk up to the block sizes and crops; padded keys are masked
    through ``kv_len``, padded query rows are cropped from the output."""
    bh, sq, d = q.shape
    _, sk, dv = v.shape
    if k.shape != (bh, sk, d):
        raise ValueError(f"k {k.shape} does not match q {q.shape} / v {v.shape}")
    scale = d ** -0.5 if scale is None else scale

    bq = max(8, min(block_q, sq + (-sq) % 8))
    bk = max(128, min(block_k, sk + (-sk) % 128))
    sqp = sq + (-sq) % bq
    skp = sk + (-sk) % bk
    if sqp != sq:
        q = jnp.pad(q, ((0, 0), (0, sqp - sq), (0, 0)))
    if skp != sk:
        k = jnp.pad(k, ((0, 0), (0, skp - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, skp - sk), (0, 0)))

    qo = _per_row_i32(q_offset, bh, 0)
    kvl = _per_row_i32(kv_len, bh, sk)
    grid = (bh, sqp // bq, skp // bk)

    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, block_q=bq, block_k=bk, causal=causal
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bq, d), lambda b, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, bk, d), lambda b, i, j, *_: (b, j, 0)),
                pl.BlockSpec((1, bk, dv), lambda b, i, j, *_: (b, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, bq, dv), lambda b, i, j, *_: (b, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((bh, sqp, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qo, kvl, q, k, v)
    return out[:, :sq]
