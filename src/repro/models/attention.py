"""Attention: GQA with KV cache, chunked (online-softmax) prefill, and
DeepSeek-style MLA (multi-head latent attention) with absorbed decode.

All functions are pure; distribution enters only through the ``constrain``
callback (a `with_sharding_constraint` hook supplied by repro.distributed —
identity on a single device).  Semantic tags passed to ``constrain``:

    "act_btd"    (batch, seq, d_model) residual-stream activations
    "q_bthd"     (batch, seq, heads, head_dim)
    "kv_bthd"    (batch, seq, kv_heads, head_dim)
    "scores"     attention scores
    "cache_bhsd" KV cache (batch, kv_heads, max_seq, head_dim)
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import api
from repro.models import layers

Constrain = Callable[[jax.Array, str], jax.Array]
_id: Constrain = lambda x, tag: x


def _natural(w):
    """Natural-layout view of a weight (de-shears an ``api.DipWeight``;
    dequantizes an ``api.QuantizedDipWeight`` first — MLA's absorbed form
    contracts these per-head, so the permutated/quantized storage cannot be
    consumed directly)."""
    if isinstance(w, (api.DipWeight, api.QuantizedDipWeight)):
        return w.to_natural()
    return w

__all__ = [
    "attention_core",
    "gqa_attention",
    "mla_attention",
    "init_gqa_cache",
    "init_mla_cache",
    "init_paged_gqa_cache",
    "init_paged_mla_cache",
    "paged_gqa_attention",
    "paged_mla_attention",
    "paged_write",
    "paged_read",
]

NEG_INF = -1e30


def _causal_mask(q_pos: jax.Array, k_pos: jax.Array) -> jax.Array:
    """(..., Sq, Sk) additive mask from absolute positions."""
    return jnp.where(q_pos[..., :, None] >= k_pos[..., None, :], 0.0, NEG_INF)


def attention_core(
    q: jax.Array,           # (B, Sq, H, D)
    k: jax.Array,           # (B, Sk, KV, D)
    v: jax.Array,           # (B, Sk, KV, Dv)
    q_pos: jax.Array,       # (Sq,)
    k_pos: jax.Array,       # (Sk,)
    *,
    kv_valid_len: Optional[jax.Array] = None,  # decode: live cache length
    kv_chunk: int = 0,
    constrain: Constrain = _id,
    unroll: bool = False,   # cost-probe mode: unroll the chunk scan so XLA
                            # cost analysis counts every chunk (launch/dryrun)
    backend: Optional[str] = None,  # api.attention backend name; None = the
                                    # XLA dense/chunked paths below
    scale: Optional[float] = None,  # softmax scale; None = D ** -0.5
) -> jax.Array:
    """Scaled-dot-product GQA attention, optionally KV-chunked.

    ``kv_chunk > 0`` streams KV in chunks with an online softmax
    (flash-attention recurrence) — O(Sq * chunk) live scores instead of
    O(Sq * Sk).  Exact (not approximate); validated against the dense path.

    ``backend`` routes through the ``api.attention`` registry instead
    (e.g. the fused ``"flash"`` kernel for serving prefill — forward-only).
    That path requires the contiguous-position layout every caller here
    uses (``q_pos``/``k_pos`` are aranges; the query block sits at offset
    ``q_pos[0] - k_pos[0]`` in the key sequence) and subsumes ``kv_chunk``:
    the kernel streams KV blocks internally.
    """
    b, sq, h, d = q.shape
    _, sk, kv, dv = v.shape
    groups = h // kv
    scale = d ** -0.5 if scale is None else scale
    q = (q * scale).astype(q.dtype)
    # GQA: broadcast kv heads up to h.  The expanded form keeps one clean
    # head axis, which shards over the TP axis without the (kv, group)
    # factorization that forces GSPMD reshards (measured in §Perf iter 2).
    if groups > 1:
        k = jnp.broadcast_to(k[:, :, :, None, :], (b, sk, kv, groups, d)).reshape(b, sk, h, d)
        v = jnp.broadcast_to(v[:, :, :, None, :], (b, sk, kv, groups, dv)).reshape(b, sk, h, dv)

    if backend is not None:
        q_f = jnp.moveaxis(q, 2, 1).reshape(b * h, sq, d)
        k_f = jnp.moveaxis(k, 2, 1).reshape(b * h, sk, d)
        v_f = jnp.moveaxis(v, 2, 1).reshape(b * h, sk, dv)
        out = api.attention(
            q_f, k_f, v_f, backend=backend, causal=True,
            q_offset=(q_pos[0] - k_pos[0]).astype(jnp.int32),
            kv_len=kv_valid_len, scale=1.0,  # q pre-scaled above
        )
        return jnp.moveaxis(out.reshape(b, h, sq, dv), 1, 2).astype(v.dtype)

    def dense(k, v, k_pos):
        scores = jnp.einsum("bqhd,bshd->bhqs", q.astype(jnp.float32), k.astype(jnp.float32))
        scores = scores + _causal_mask(q_pos, k_pos)[None, None]
        if kv_valid_len is not None:
            live = (k_pos < kv_valid_len)[None, None, None, :]
            scores = jnp.where(live, scores, NEG_INF)
        scores = constrain(scores, "scores")
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqs,bshd->bqhd", probs.astype(v.dtype), v)
        return out

    if kv_chunk <= 0 or sk <= kv_chunk:
        return dense(k, v, k_pos)

    # ---- online-softmax over KV chunks (flash-attention recurrence) ----
    n_chunks = sk // kv_chunk
    assert sk % kv_chunk == 0, "pad KV to chunk multiple"
    k_c = jnp.moveaxis(k.reshape(b, n_chunks, kv_chunk, h, d), 1, 0)
    v_c = jnp.moveaxis(v.reshape(b, n_chunks, kv_chunk, h, dv), 1, 0)
    kp_c = k_pos.reshape(n_chunks, kv_chunk)

    def step(carry, xs):
        m_prev, l_prev, acc = carry
        kc, vc, kpc = xs
        s = jnp.einsum("bqhd,bshd->bhqs", q.astype(jnp.float32), kc.astype(jnp.float32))
        s = s + _causal_mask(q_pos, kpc)[None, None]
        if kv_valid_len is not None:
            live = (kpc < kv_valid_len)[None, None, None, :]
            s = jnp.where(live, s, NEG_INF)
        s = constrain(s, "scores")
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l_prev * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bhqs,bshd->bhqd", p, vc.astype(jnp.float32))
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    a0 = jnp.zeros((b, h, sq, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (k_c, v_c, kp_c),
                                  unroll=n_chunks if unroll else 1)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return jnp.moveaxis(out, 1, 2).astype(v.dtype)


# --------------------------------------------------------------------- GQA --
def init_gqa_cache(batch: int, kv_heads: int, max_seq: int, head_dim: int, dtype) -> Dict:
    return {
        "k": jnp.zeros((batch, max_seq, kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, max_seq, kv_heads, head_dim), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def gqa_attention(
    x: jax.Array,
    p: Dict,                       # layer params: wq, wk, wv, wo (+ biases)
    cfg,
    *,
    positions: jax.Array,          # (S,) absolute positions of x's tokens
    cache: Optional[Dict] = None,
    kv_chunk: int = 0,
    plan=None,                     # repro.distributed.ShardingPlan
    constrain: Optional[Constrain] = None,  # legacy hook; plan wins
    unroll: bool = False,
    rope=None,                     # precomputed layers.rope_tables (hoisted)
    residual: Optional[jax.Array] = None,  # fused into the out-projection
    norm: Optional[jax.Array] = None,  # attn_norm gain fused as a prologue
    attn_backend: Optional[str] = None,  # api.attention backend (e.g. "flash")
) -> Tuple[jax.Array, Optional[Dict]]:
    """Full GQA block: projections + RoPE + cache update + attention + out.

    ``rope`` takes the per-forward cos/sin tables so layers stop recomputing
    them; ``residual`` fuses the block's ``x + attn(x)`` into the
    out-projection's flush-stage epilogue (the returned tensor then IS the
    updated residual stream).  QKV biases ride the projections' fused bias
    epilogue.  ``norm`` takes the pre-attention RMSNorm gain when the
    backend fuses prologues: ``x`` then arrives UN-normalized and each
    q/k/v projection normalizes it in its kernel's load stage (the normed
    (B, S, d) tensor never reaches HBM) — callers without fusion normalize
    first and pass ``norm=None``.
    """
    constrain = layers.resolve_constrain(plan, constrain)
    b, s, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    lk = dict(backend=cfg.matmul_backend, compute_dtype=x.dtype)
    nk = dict(lk) if norm is None else dict(
        lk, prologue="rmsnorm", prologue_operands=(norm,),
        prologue_eps=cfg.norm_eps,
    )
    q = layers.linear(x, p["wq"], p.get("bq"), **nk).reshape(b, s, h, hd)
    k = layers.linear(x, p["wk"], p.get("bk"), **nk).reshape(b, s, kv, hd)
    v = layers.linear(x, p["wv"], p.get("bv"), **nk).reshape(b, s, kv, hd)

    q = layers.apply_rope(q, positions, cfg.rope_theta, tables=rope)
    k = layers.apply_rope(k, positions, cfg.rope_theta, tables=rope)
    q = constrain(q, "q_bthd")
    k = constrain(k, "kv_bthd")
    v = constrain(v, "kv_bthd")

    if cache is None:
        out = attention_core(
            q, k, v, positions, positions, kv_chunk=kv_chunk, constrain=constrain,
            unroll=unroll, backend=attn_backend,
        )
        new_cache = None
    else:
        pos = cache["pos"]
        ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, pos, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, pos, 0, 0))
        ck = constrain(ck, "cache_bshd")
        cv = constrain(cv, "cache_bshd")
        max_seq = ck.shape[1]
        k_pos = jnp.arange(max_seq, dtype=jnp.int32)
        out = attention_core(
            q, ck, cv, positions, k_pos,
            kv_valid_len=pos + s, kv_chunk=kv_chunk, constrain=constrain,
            unroll=unroll, backend=attn_backend,
        )
        new_cache = {"k": ck, "v": cv, "pos": pos + s}

    out = out.reshape(b, s, h * hd)
    if residual is not None:
        # The fused sum IS the mid-block residual — left to propagation like
        # the explicit `x + a` was (constraining the sum forces an extra
        # scatter/gather pair per layer — §Perf iter 4, refuted).  The pin
        # the unfused path puts on the projection output alone is
        # unreachable once the add happens inside the kernel; propagation
        # stays bounded by the pins on `residual` (previous block end) and
        # on the block output downstream.
        out = layers.linear(out, p["wo"], epilogue="residual",
                            epilogue_operands=(residual,), **lk)
        return out, new_cache
    out = layers.linear(out, p["wo"], **lk)
    return constrain(out, "act_btd"), new_cache


# --------------------------------------------------------------------- MLA --
def init_mla_cache(batch: int, max_seq: int, cfg, dtype) -> Dict:
    return {
        "c_kv": jnp.zeros((batch, max_seq, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_seq, cfg.qk_rope_head_dim), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def _mla_inputs(x, p, cfg, pos, rope, nk):
    """q (B, S, H, nope + rope) with its rope half rotated, the normalized
    latent c_kv (B, S, r) and the shared rotated k_rope (B, S, dr): what
    both MLA forms start from.  ``pos`` broadcasts like ``rope``'s rows."""
    b, s, _ = x.shape
    h, dn, dr = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = layers.linear(x, p["wq"], **nk).reshape(b, s, h, dn + dr)
    q_rope = layers.apply_rope(q[..., dn:], pos, cfg.rope_theta, tables=rope)
    q = jnp.concatenate([q[..., :dn], q_rope], -1)
    # kv_a_layernorm: the cache holds the normalized latent
    c_kv = layers.rms_norm(layers.linear(x, p["w_dkv"], **nk), p["kv_norm"],
                           cfg.norm_eps)
    k_rope = layers.linear(x, p["w_krope"], **nk)
    k_rope = layers.apply_rope(
        k_rope[:, :, None, :], pos, cfg.rope_theta, tables=rope)[:, :, 0, :]
    return q, c_kv, k_rope


def _mla_up(p, cfg, dtype):
    """Natural-layout (r, H, nope) and (r, H, v) up-projections: both MLA
    forms contract them per head, so permutated/quantized storage cannot be
    consumed directly."""
    h, r = cfg.n_heads, cfg.kv_lora_rank
    w_uk = _natural(p["w_uk"]).astype(dtype).reshape(r, h, cfg.qk_nope_head_dim)
    w_uv = _natural(p["w_uv"]).astype(dtype).reshape(r, h, cfg.v_head_dim)
    return w_uk, w_uv


def _expand_prefix(c_kv, k_rope, w_uk, w_uv, live):
    """Per-head K = [c_kv W_uk ; k_rope] (B, H, Smax, nope + rope) and
    V = c_kv W_uv (B, H, Smax, v) of a contiguous latent cache, computed in
    blocks over the ``live`` (traced) prefix only; rows past it stay 0."""
    b, smax, _ = c_kv.shape
    h, dn, dv = w_uk.shape[1], w_uk.shape[2], w_uv.shape[2]
    dr = k_rope.shape[-1]
    blk = next((n for n in (2048, 1024, 512, 256, 128) if smax % n == 0), smax)

    def body(i, kv):
        k, v = kv
        c = jax.lax.dynamic_slice_in_dim(c_kv, i * blk, blk, axis=1)
        kr = jax.lax.dynamic_slice_in_dim(k_rope, i * blk, blk, axis=1)
        kb = jnp.concatenate([
            jnp.einsum("bsr,rhd->bhsd", c, w_uk),
            jnp.broadcast_to(kr[:, None], (b, h, blk, dr)).astype(c.dtype)], -1)
        vb = jnp.einsum("bsr,rhd->bhsd", c, w_uv)
        return (jax.lax.dynamic_update_slice_in_dim(k, kb, i * blk, axis=2),
                jax.lax.dynamic_update_slice_in_dim(v, vb, i * blk, axis=2))

    zeros = (jnp.zeros((b, h, smax, dn + dr), c_kv.dtype),
             jnp.zeros((b, h, smax, dv), c_kv.dtype))
    return jax.lax.fori_loop(0, (live + blk - 1) // blk, body, zeros)


def mla_attention(
    x: jax.Array,
    p: Dict,
    cfg,
    *,
    positions: jax.Array,
    cache: Optional[Dict] = None,
    kv_chunk: int = 0,
    plan=None,                     # repro.distributed.ShardingPlan
    constrain: Optional[Constrain] = None,  # legacy hook; plan wins
    unroll: bool = False,
    rope=None,                     # precomputed layers.rope_tables (hoisted)
    residual: Optional[jax.Array] = None,  # fused into the out-projection
    norm: Optional[jax.Array] = None,  # attn_norm gain fused as a prologue
    attn_backend: Optional[str] = None,  # api.attention backend (e.g. "flash")
) -> Tuple[jax.Array, Optional[Dict]]:
    """DeepSeek-V2 multi-head latent attention, expanded form.

    Params: wq -> (d, H*(nope+rope)); w_dkv -> (d, kv_lora) with its RMSNorm
    gain kv_norm; w_krope -> (d, rope); w_uk -> (kv_lora, H*nope);
    w_uv -> (kv_lora, H*v_dim); wo -> (H*v_dim, d).

    Per-head K = [c_kv W_uk ; k_rope] and V = c_kv W_uv are materialized and
    the heads attend as in MHA (query width nope+rope, value width v_dim),
    with the softmax scale of ``layers.attention_scale`` (YaRN's mscale).
    With a contiguous cache (chunked prefill) the chunk's latent is written
    first and K/V are expanded over the cached prefix plus the chunk
    (``_expand_prefix``); ``attn_backend`` ("flash" for serving) attends at
    the traced offset ``pos``.  Paged decode takes the absorbed form instead
    (``paged_mla_attention``).
    """
    constrain = layers.resolve_constrain(plan, constrain)
    b, s, _ = x.shape
    h, dv_ = cfg.n_heads, cfg.v_head_dim
    qd = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    scale = layers.attention_scale(qd, cfg.rope_yarn)
    lk = dict(backend=cfg.matmul_backend, compute_dtype=x.dtype)
    # fused attn_norm (see gqa_attention): every projection reading x
    # normalizes it in its kernel's load stage
    nk = dict(lk) if norm is None else dict(
        lk, prologue="rmsnorm", prologue_operands=(norm,),
        prologue_eps=cfg.norm_eps,
    )
    q, c_kv, k_rope = _mla_inputs(x, p, cfg, positions, rope, nk)
    w_uk, w_uv = _mla_up(p, cfg, x.dtype)

    if cache is None:
        k = jnp.concatenate([
            jnp.einsum("bsr,rhd->bshd", c_kv, w_uk),
            jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, k_rope.shape[-1]))], -1)
        v = jnp.einsum("bsr,rhd->bshd", c_kv, w_uv)
        q, k, v = constrain(q, "q_bthd"), constrain(k, "q_bthd"), constrain(v, "q_bthd")
        out = attention_core(q, k, v, positions, positions, kv_chunk=kv_chunk,
                             constrain=constrain, unroll=unroll,
                             backend=attn_backend, scale=scale)
        new_cache = None
    else:
        pos = cache["pos"]
        cc = jax.lax.dynamic_update_slice(cache["c_kv"], c_kv.astype(cache["c_kv"].dtype),
                                          (0, pos, 0))
        cr = jax.lax.dynamic_update_slice(cache["k_rope"], k_rope.astype(cache["k_rope"].dtype),
                                          (0, pos, 0))
        cc, cr = constrain(cc, "cache_bsr"), constrain(cr, "cache_bsr")
        k, v = _expand_prefix(cc.astype(x.dtype), cr.astype(x.dtype), w_uk, w_uv, pos + s)
        smax = k.shape[2]
        out = api.attention(
            jnp.moveaxis(q, 2, 1).reshape(b * h, s, qd),
            k.reshape(b * h, smax, qd), v.reshape(b * h, smax, dv_),
            backend=attn_backend or "xla", causal=True, q_offset=pos,
            kv_len=pos + s, scale=scale,
        )
        out = jnp.moveaxis(out.reshape(b, h, s, dv_), 1, 2).astype(x.dtype)
        new_cache = {"c_kv": cc, "k_rope": cr, "pos": pos + s}

    out = out.reshape(b, s, h * dv_)
    if residual is not None:
        # fused mid-block residual: left to propagation (see gqa_attention)
        out = layers.linear(out, p["wo"], epilogue="residual",
                            epilogue_operands=(residual,), **lk)
        return out, new_cache
    out = layers.linear(out, p["wo"], **lk)
    return constrain(out, "act_btd"), new_cache


# ------------------------------------------------------------------- paged --
# Block-table-indexed KV cache for the serving engine (repro.serving): K/V
# live in a pool of fixed-size blocks shared by every sequence; a per-slot
# block table maps logical token position p to physical storage
# (table[p // block_size], p % block_size).  All shapes are static — ONE
# compiled decode step serves the whole slot pool regardless of which slots
# are live or how long each sequence is — and storage is optionally int8
# (per-token/head symmetric scales via ``api.quant.quantize_rows``).
# The host-side allocator that hands out blocks lives in
# ``repro.serving.kv_cache``; see docs/serving.md §Paged KV layout.

def init_paged_gqa_cache(num_blocks: int, block_size: int, kv_heads: int,
                         head_dim: int, dtype, kv_quant: str = "none") -> Dict:
    """GQA block pool: k/v (num_blocks, block_size, kv_heads, head_dim);
    int8 mode adds per-token/head f32 scales (num_blocks, block_size, kv_heads)."""
    if kv_quant == "none":
        return {
            "k": jnp.zeros((num_blocks, block_size, kv_heads, head_dim), dtype),
            "v": jnp.zeros((num_blocks, block_size, kv_heads, head_dim), dtype),
        }
    sdt = jnp.dtype(api.quant.scheme_info(kv_quant).storage_dtype)
    return {
        "k": jnp.zeros((num_blocks, block_size, kv_heads, head_dim), sdt),
        "v": jnp.zeros((num_blocks, block_size, kv_heads, head_dim), sdt),
        "k_scale": jnp.zeros((num_blocks, block_size, kv_heads), jnp.float32),
        "v_scale": jnp.zeros((num_blocks, block_size, kv_heads), jnp.float32),
    }


def init_paged_mla_cache(num_blocks: int, block_size: int, cfg, dtype,
                         kv_quant: str = "none") -> Dict:
    """MLA block pool: the latent c_kv and shared k_rope are paged the same
    way; int8 scales are per token (one row = the whole latent/rope vector)."""
    if kv_quant == "none":
        return {
            "c_kv": jnp.zeros((num_blocks, block_size, cfg.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((num_blocks, block_size, cfg.qk_rope_head_dim), dtype),
        }
    sdt = jnp.dtype(api.quant.scheme_info(kv_quant).storage_dtype)
    return {
        "c_kv": jnp.zeros((num_blocks, block_size, cfg.kv_lora_rank), sdt),
        "k_rope": jnp.zeros((num_blocks, block_size, cfg.qk_rope_head_dim), sdt),
        "c_kv_scale": jnp.zeros((num_blocks, block_size), jnp.float32),
        "k_rope_scale": jnp.zeros((num_blocks, block_size), jnp.float32),
    }


def paged_write(pool: jax.Array, phys: jax.Array, vals: jax.Array,
                *, scale_pool: Optional[jax.Array] = None,
                kv_quant: str = "none"):
    """Scatter per-token vectors into a block pool at flat physical indices.

    ``pool``: (num_blocks, block_size, ...); ``phys``: (N,) flat token indices
    (``num_blocks * block_size`` acts as a drop sentinel for padding / dead
    rows); ``vals``: (N, ...).  Returns ``(pool, scale_pool)`` updated; int8
    mode quantizes each row (last axis) and records its scale.
    """
    nb, bs = pool.shape[:2]
    flat = pool.reshape((nb * bs,) + pool.shape[2:])
    if kv_quant != "none":
        q, scale = api.quant.quantize_rows(vals, kv_quant)
        flat = flat.at[phys].set(q, mode="drop")
        sflat = scale_pool.reshape((nb * bs,) + scale_pool.shape[2:])
        sflat = sflat.at[phys].set(scale[..., 0], mode="drop")
        return flat.reshape(pool.shape), sflat.reshape(scale_pool.shape)
    flat = flat.at[phys].set(vals.astype(pool.dtype), mode="drop")
    return flat.reshape(pool.shape), scale_pool


def paged_read(pool: jax.Array, idx: jax.Array,
               *, scale_pool: Optional[jax.Array] = None,
               dtype=jnp.float32) -> jax.Array:
    """Gather token vectors at flat physical indices ``idx`` (any shape),
    dequantizing against ``scale_pool`` when the pool is quantized."""
    nb, bs = pool.shape[:2]
    flat = pool.reshape((nb * bs,) + pool.shape[2:])
    vals = flat[idx]
    if scale_pool is not None:
        sflat = scale_pool.reshape((nb * bs,) + scale_pool.shape[2:])
        return api.quant.dequantize_rows(vals, sflat[idx][..., None], dtype)
    return vals.astype(dtype)


def _gather_indices(block_tables: jax.Array, block_size: int) -> jax.Array:
    """(B, n_blocks) block tables -> (B, n_blocks * block_size) flat token
    indices in logical order."""
    b, nblk = block_tables.shape
    idx = block_tables[:, :, None] * block_size + jnp.arange(
        block_size, dtype=block_tables.dtype
    )[None, None, :]
    return idx.reshape(b, nblk * block_size)


def paged_gqa_attention(
    x: jax.Array,                  # (B, 1, d) — one decode token per slot
    p: Dict,
    cfg,
    *,
    positions: jax.Array,          # (B,) per-slot absolute positions
    cache: Dict,                   # paged pool (init_paged_gqa_cache)
    block_tables: jax.Array,       # (B, n_blocks_per_seq) int32
    kv_quant: str = "none",
    constrain: Optional[Constrain] = None,
    rope=None,
    residual: Optional[jax.Array] = None,
    norm: Optional[jax.Array] = None,  # attn_norm gain fused as a prologue
) -> Tuple[jax.Array, Dict]:
    """GQA decode against the paged pool: write this token's K/V into its
    slot's block, gather the slot's whole context, attend with per-row valid
    lengths.  Rows whose slot is free write to the reserved null block 0 and
    their output is ignored by the engine."""
    constrain = constrain if constrain is not None else _id
    b, s, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    bs = cache["k"].shape[1]
    lk = dict(backend=cfg.matmul_backend, compute_dtype=x.dtype)
    nk = dict(lk) if norm is None else dict(
        lk, prologue="rmsnorm", prologue_operands=(norm,),
        prologue_eps=cfg.norm_eps,
    )
    q = layers.linear(x, p["wq"], p.get("bq"), **nk).reshape(b, s, h, hd)
    k = layers.linear(x, p["wk"], p.get("bk"), **nk).reshape(b, s, kv, hd)
    v = layers.linear(x, p["wv"], p.get("bv"), **nk).reshape(b, s, kv, hd)

    pos2 = positions[:, None]                                   # (B, 1)
    q = layers.apply_rope(q, pos2, cfg.rope_theta, tables=rope)
    k = layers.apply_rope(k, pos2, cfg.rope_theta, tables=rope)
    q = constrain(q, "q_bthd")

    phys = block_tables[jnp.arange(b), positions // bs] * bs + positions % bs
    ck, cks = paged_write(cache["k"], phys, k[:, 0],
                          scale_pool=cache.get("k_scale"), kv_quant=kv_quant)
    cv, cvs = paged_write(cache["v"], phys, v[:, 0],
                          scale_pool=cache.get("v_scale"), kv_quant=kv_quant)
    new_cache = {"k": ck, "v": cv}
    if kv_quant != "none":
        new_cache.update(k_scale=cks, v_scale=cvs)

    idx = _gather_indices(block_tables, bs)                     # (B, Smax)
    k_all = paged_read(ck, idx, scale_pool=cks, dtype=x.dtype)  # (B, Smax, KV, hd)
    v_all = paged_read(cv, idx, scale_pool=cvs, dtype=x.dtype)

    smax = k_all.shape[1]
    groups = h // kv
    if groups > 1:
        k_all = jnp.broadcast_to(
            k_all[:, :, :, None, :], (b, smax, kv, groups, hd)
        ).reshape(b, smax, h, hd)
        v_all = jnp.broadcast_to(
            v_all[:, :, :, None, :], (b, smax, kv, groups, hd)
        ).reshape(b, smax, h, hd)

    scale = hd ** -0.5
    scores = jnp.einsum(
        "bqhd,bshd->bhqs",
        (q * scale).astype(jnp.float32), k_all.astype(jnp.float32),
    )
    # logical position t is live iff t <= pos (the current token, just
    # written, attends to itself and everything before it)
    live = (jnp.arange(smax, dtype=jnp.int32)[None, :] <= positions[:, None])
    scores = jnp.where(live[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqs,bshd->bqhd", probs.astype(v_all.dtype), v_all)

    out = out.reshape(b, s, h * hd)
    if residual is not None:
        out = layers.linear(out, p["wo"], epilogue="residual",
                            epilogue_operands=(residual,), **lk)
        return out, new_cache
    out = layers.linear(out, p["wo"], **lk)
    return constrain(out, "act_btd"), new_cache


def paged_mla_attention(
    x: jax.Array,                  # (B, 1, d)
    p: Dict,
    cfg,
    *,
    positions: jax.Array,          # (B,)
    cache: Dict,                   # paged pool (init_paged_mla_cache)
    block_tables: jax.Array,
    kv_quant: str = "none",
    constrain: Optional[Constrain] = None,
    rope=None,
    residual: Optional[jax.Array] = None,
    norm: Optional[jax.Array] = None,  # attn_norm gain fused as a prologue
) -> Tuple[jax.Array, Dict]:
    """Absorbed-form MLA decode against the paged latent pool (the normalized
    latent c_kv and the shared k_rope page exactly like K/V — one row per
    token): scores are q_nope W_uk · c_kv + q_rope · k_rope and the output
    (probs · c_kv) W_uv, so per-head K/V are never formed over the context."""
    constrain = constrain if constrain is not None else _id
    b, s, _ = x.shape
    h, dn, dv_ = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    bs = cache["c_kv"].shape[1]
    lk = dict(backend=cfg.matmul_backend, compute_dtype=x.dtype)
    nk = dict(lk) if norm is None else dict(
        lk, prologue="rmsnorm", prologue_operands=(norm,),
        prologue_eps=cfg.norm_eps,
    )
    q, c_kv, k_rope = _mla_inputs(x, p, cfg, positions[:, None], rope, nk)
    q_nope, q_rope = q[..., :dn], q[..., dn:]

    phys = block_tables[jnp.arange(b), positions // bs] * bs + positions % bs
    cc, ccs = paged_write(cache["c_kv"], phys, c_kv[:, 0],
                          scale_pool=cache.get("c_kv_scale"), kv_quant=kv_quant)
    cr, crs = paged_write(cache["k_rope"], phys, k_rope[:, 0],
                          scale_pool=cache.get("k_rope_scale"), kv_quant=kv_quant)
    new_cache = {"c_kv": cc, "k_rope": cr}
    if kv_quant != "none":
        new_cache.update(c_kv_scale=ccs, k_rope_scale=crs)

    idx = _gather_indices(block_tables, bs)
    cc_all = paged_read(cc, idx, scale_pool=ccs, dtype=x.dtype)  # (B, Smax, r)
    cr_all = paged_read(cr, idx, scale_pool=crs, dtype=x.dtype)  # (B, Smax, dr)
    smax = cc_all.shape[1]
    w_uk, w_uv = _mla_up(p, cfg, x.dtype)

    # absorbed: W_uk folds into the query, W_uv applies after the latent sum
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, w_uk)
    s_lat = jnp.einsum("bshr,btr->bhst", q_lat.astype(jnp.float32),
                       cc_all.astype(jnp.float32))
    s_rope = jnp.einsum("bshd,btd->bhst", q_rope.astype(jnp.float32),
                        cr_all.astype(jnp.float32))
    scores = (s_lat + s_rope) * layers.attention_scale(q.shape[-1], cfg.rope_yarn)
    live = (jnp.arange(smax, dtype=jnp.int32)[None, :] <= positions[:, None])
    scores = jnp.where(live[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out_lat = jnp.einsum("bhst,btr->bshr", probs.astype(cc_all.dtype), cc_all)
    out = jnp.einsum("bshr,rhd->bshd", out_lat, w_uv)

    out = out.reshape(b, s, h * dv_)
    if residual is not None:
        out = layers.linear(out, p["wo"], epilogue="residual",
                            epilogue_operands=(residual,), **lk)
        return out, new_cache
    out = layers.linear(out, p["wo"], **lk)
    return constrain(out, "act_btd"), new_cache
