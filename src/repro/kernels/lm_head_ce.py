"""Fused lm_head + cross-entropy: the (B, S, V) logits never reach HBM.

The training loss is the one place the model materializes a vocab-wide
tensor: an unfused ``hidden @ head`` writes (B*S, V) float32 logits to HBM
only for the loss to immediately reduce them to one scalar per token.  At
production vocabularies that single intermediate dwarfs every activation in
the network (V >> d_model), and it is pure synchronization tax in the
paper's sense — a producer/consumer hand-off buffer, the FIFO the DiP
dataflow exists to delete.

This kernel streams the head matmul through an online-logsumexp reduction
instead (same recurrence as flash attention's running softmax): the grid
walks vocab chunks innermost, each chunk's (block_t, block_v) logit tile
lives only in VMEM, and per token just two scalars survive to HBM —

    logz_t  = logsumexp_v(x_t @ W)        (the softmax normalizer)
    lab_t   = (x_t @ W)[labels_t]         (the label's raw logit)

from which the caller assembles ``loss_t = logz - lab + z_loss * logz^2``.

The backward pass never materializes the logits either: ``d z = g_logz *
softmax(z) + g_lab * onehot(labels)`` is recomputed chunk-by-chunk in a
pure-XLA scan over the vocab (``dx += dz_c @ W_c^T``, ``dW_c = x^T @
dz_c``), so peak memory is one (T, block_v) tile plus the weight-sized
gradient that must exist anyway.

Masking contract (shared with ``layers.cross_entropy_loss``): tokens whose
label equals ``ignore_index`` (default -100) and tokens zeroed by ``mask``
contribute neither to the mean nor to gradients; the mean divides by the
valid-token count.  Vocab padding columns (``col >= vocab_size``) are
masked to -inf inside the kernel, mirroring the -1e30 lane mask the
unfused ``transformer.forward`` applies to its logits.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "DEFAULT_BLOCK_T",
    "DEFAULT_BLOCK_V",
    "IGNORE_INDEX",
    "lm_head_ce_pallas",
    "fused_cross_entropy_loss",
    "reference_lm_head_ce",
]

NEG_INF = -1e30
IGNORE_INDEX = -100
DEFAULT_BLOCK_T = 256
DEFAULT_BLOCK_V = 512


def _kernel(x_ref, w_ref, lab_ref, logz_ref, labl_ref, m_ref, l_ref, a_ref,
            *, block_v: int, vocab_size: int):
    """One (block_t, block_v) logit tile: fold into the online logsumexp.

    Grid is (T / block_t, Vp / block_v) with the vocab dim innermost and
    "arbitrary" (sequential), so the m/l/label scratch carries across vocab
    chunks exactly like the matmul kernels' accumulator carries across K.
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        a_ref[...] = jnp.zeros_like(a_ref)

    z = jnp.dot(x_ref[...], w_ref[...], preferred_element_type=jnp.float32)
    col = j * block_v + jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
    z = jnp.where(col < vocab_size, z, NEG_INF)

    # online logsumexp: every block holds >= 1 real column (the padding
    # Vp - V is < block_v), so m_new stays finite and the masked lanes'
    # exp(NEG_INF - m_new) underflows to exactly 0 — no exp(0) hazard.
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(z, axis=-1, keepdims=True))
    l_ref[...] = (l_ref[...] * jnp.exp(m_prev - m_new)
                  + jnp.sum(jnp.exp(z - m_new), axis=-1, keepdims=True))
    m_ref[...] = m_new

    # label logit: compare absolute column ids, so ignore_index (< 0) simply
    # never matches and its accumulator stays 0 (masked out by the caller)
    hit = col == lab_ref[...]
    a_ref[...] += jnp.sum(jnp.where(hit, z, 0.0), axis=-1, keepdims=True)

    @pl.when(j == pl.num_programs(1) - 1)
    def _flush():
        logz_ref[...] = m_ref[...] + jnp.log(l_ref[...])
        labl_ref[...] = a_ref[...]


def lm_head_ce_pallas(
    x: jax.Array,          # (T, D) hidden states
    w: jax.Array,          # (D, Vp) natural head weight
    labels: jax.Array,     # (T,) int32 token ids (or ignore_index)
    *,
    vocab_size: Optional[int] = None,
    block_t: int = DEFAULT_BLOCK_T,
    block_v: int = DEFAULT_BLOCK_V,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Forward kernel: returns per-token ``(logz, label_logit)`` float32 (T,).

    Pads T up to ``block_t`` (labels with ``ignore_index``) and Vp up to
    ``block_v`` (zero columns — masked inside the kernel together with any
    vocab padding already present in ``w``), then crops.
    """
    t, d = x.shape
    d2, vp = w.shape
    if d != d2:
        raise ValueError(f"contraction mismatch {x.shape} @ {w.shape}")
    if labels.shape != (t,):
        raise ValueError(f"labels {labels.shape} do not match x rows {t}")
    vocab = vp if vocab_size is None else vocab_size
    if vocab > vp:
        raise ValueError(f"vocab_size {vocab} exceeds head width {vp}")

    # the MXU runs at the activations' width: a wider head (an f32 master
    # weight under bf16 compute) would promote every tile to f32 and its
    # double-buffered (d, block_v) blocks overflow the kernel's VMEM
    w = w.astype(x.dtype)
    bt = max(8, min(block_t, -(-t // 8) * 8))
    bv = max(128, min(block_v, -(-vp // 128) * 128))
    tp = -(-t // bt) * bt
    vpp = -(-vp // bv) * bv
    if tp != t:
        x = jnp.pad(x, ((0, tp - t), (0, 0)))
        labels = jnp.pad(labels, (0, tp - t), constant_values=IGNORE_INDEX)
    if vpp != vp:
        w = jnp.pad(w, ((0, 0), (0, vpp - vp)))

    lab2 = labels.astype(jnp.int32).reshape(tp, 1)
    logz, labl = pl.pallas_call(
        functools.partial(_kernel, block_v=bv, vocab_size=vocab),
        grid=(tp // bt, vpp // bv),
        in_specs=[
            pl.BlockSpec((bt, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, bv), lambda i, j: (0, j)),
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tp, 1), jnp.float32),
            jax.ShapeDtypeStruct((tp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bt, 1), jnp.float32),
            pltpu.VMEM((bt, 1), jnp.float32),
            pltpu.VMEM((bt, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, w, lab2)
    return logz[:t, 0], labl[:t, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _logz_and_label(x, w, labels, opts):
    vocab, block_t, block_v, interpret = opts
    return lm_head_ce_pallas(
        x, w, labels, vocab_size=vocab,
        block_t=block_t, block_v=block_v, interpret=interpret,
    )


def _logz_and_label_fwd(x, w, labels, opts):
    out = _logz_and_label(x, w, labels, opts)
    return out, (x, w, labels, out[0])


def _logz_and_label_bwd(opts, res, g):
    """Chunked recompute backward — the (T, V) logits never materialize.

    ``dz = g_logz * softmax(z) + g_lab * onehot(labels)`` per vocab chunk;
    dx accumulates across chunks, dW is stacked chunk-wise and reassembled
    (weight-sized, which the optimizer materializes anyway).
    """
    vocab, _, block_v, _ = opts
    x, w, labels, logz = res
    g_logz, g_lab = g
    t, d = x.shape
    vp = w.shape[1]
    x32 = x.astype(jnp.float32)
    w32 = w.astype(jnp.float32)
    bv = max(128, min(block_v, -(-vp // 128) * 128))
    vpp = -(-vp // bv) * bv
    if vpp != vp:
        w32 = jnp.pad(w32, ((0, 0), (0, vpp - vp)))
    gz = g_logz[:, None]
    gl = g_lab[:, None]
    lab = labels.astype(jnp.int32)[:, None]
    logz_col = logz[:, None]

    def body(dx, c):
        w_c = jax.lax.dynamic_slice_in_dim(w32, c * bv, bv, axis=1)
        z_c = x32 @ w_c
        col = c * bv + jnp.arange(bv, dtype=jnp.int32)[None, :]
        p_c = jnp.where(col < vocab, jnp.exp(z_c - logz_col), 0.0)
        dz_c = gz * p_c + gl * (col == lab).astype(jnp.float32)
        dw_c = x32.T @ dz_c
        return dx + dz_c @ w_c.T, dw_c

    dx, dw_chunks = jax.lax.scan(
        body, jnp.zeros((t, d), jnp.float32),
        jnp.arange(vpp // bv, dtype=jnp.int32),
    )
    dw = jnp.moveaxis(dw_chunks, 0, 1).reshape(d, vpp)[:, :vp]
    dlab = np.zeros(labels.shape, dtype=jax.dtypes.float0)
    return dx.astype(x.dtype), dw.astype(w.dtype), dlab


_logz_and_label.defvjp(_logz_and_label_fwd, _logz_and_label_bwd)


def fused_cross_entropy_loss(
    x: jax.Array,                     # (..., D) hidden states
    w: jax.Array,                     # (D, Vp) natural head weight
    labels: jax.Array,                # (...) int32
    *,
    z_loss: float = 1e-4,
    mask: Optional[jax.Array] = None,
    ignore_index: int = IGNORE_INDEX,
    vocab_size: Optional[int] = None,
    block_t: int = DEFAULT_BLOCK_T,
    block_v: int = DEFAULT_BLOCK_V,
    interpret: bool = False,
) -> jax.Array:
    """Mean token cross entropy straight from hidden states — no logits.

    Same value and masking contract as ``layers.cross_entropy_loss(x @ w,
    labels, ...)`` with padding lanes masked, but the (..., V) logits exist
    only as VMEM tiles (forward) / scan chunks (backward).  Differentiable
    in ``x`` and ``w`` via the chunked-recompute VJP.
    """
    d = x.shape[-1]
    x2 = x.reshape(-1, d)
    lab2 = labels.reshape(-1).astype(jnp.int32)
    vocab = int(w.shape[1]) if vocab_size is None else int(vocab_size)
    opts = (vocab, int(block_t), int(block_v), bool(interpret))
    logz, lab_logit = _logz_and_label(x2, w, lab2, opts)

    valid = lab2 != ignore_index
    if mask is not None:
        valid = valid & (mask.reshape(-1) != 0)
    loss_t = logz - lab_logit
    if z_loss:
        loss_t = loss_t + z_loss * jnp.square(logz)
    loss_t = jnp.where(valid, loss_t, 0.0)
    return jnp.sum(loss_t) / jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)


def reference_lm_head_ce(
    x: jax.Array,
    w: jax.Array,
    labels: jax.Array,
    *,
    z_loss: float = 1e-4,
    mask: Optional[jax.Array] = None,
    ignore_index: int = IGNORE_INDEX,
    vocab_size: Optional[int] = None,
) -> jax.Array:
    """Unfused oracle: materializes the logits, same arithmetic contract."""
    vocab = int(w.shape[1]) if vocab_size is None else int(vocab_size)
    logits = jnp.matmul(
        x.astype(jnp.float32), w.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    lane = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    logits = jnp.where(lane < vocab, logits, NEG_INF)

    lab = labels.astype(jnp.int32)
    valid = lab != ignore_index
    if mask is not None:
        valid = valid & (mask != 0)
    safe = jnp.where(valid, lab, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    label_logits = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    loss = logz - label_logits
    if z_loss:
        loss = loss + z_loss * jnp.square(logz)
    loss = jnp.where(valid, loss, 0.0)
    return jnp.sum(loss) / jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
