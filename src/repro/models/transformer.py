"""Model assembly: parameter templates, scan-based stacks, train/decode steps.

One code path covers all ten assigned architectures:

  dense / vlm / audio   — GQA attention + SwiGLU FFN blocks
  moe                   — GQA or MLA attention + routed expert FFN
  ssm                   — Mamba2 SSD blocks (attention-free)
  hybrid                — Mamba2 blocks + a single *shared* attention+FFN
                          block applied every ``attn_every`` layers (Zamba2)

Parameters are layer-stacked pytrees (leading axis = n_layers) consumed by
``jax.lax.scan`` — constant compile time in depth, which is what makes the
512-device dry-run of a 94-layer MoE tractable.  ``param_specs`` builds the
same pytree as ShapeDtypeStructs (no allocation) for the dry-run;
``init_params`` materializes it for real runs.

[vlm]/[audio] frontends are stubs per the assignment: ``forward`` accepts
precomputed ``embeddings`` in place of token ids.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.kernels import lm_head_ce
from repro.models import attention, layers, moe, ssm

Constrain = Callable[[jax.Array, str], jax.Array]
_id: Constrain = lambda x, tag: x

__all__ = [
    "param_template",
    "COMPUTE_DTYPE_ROLES",
    "serving_params",
    "init_params",
    "param_specs",
    "quantize_params",
    "forward",
    "forward_counted",
    "init_cache",
    "init_paged_cache",
    "loss_fn",
    "train_step_fn",
    "decode_step_fn",
    "paged_decode_step_fn",
]


# ------------------------------------------------------------ param layout --
def _lin(cfg, d_in, d_out):
    """(storage_shape, fan_in, dip_meta) for a linear under the config's
    weight storage.  ``dip_meta`` is ``(d_in, d_out, perm_tile)`` when the
    weight lives as an ``api.DipWeight``, else None."""
    if cfg.uses_dip_storage:
        shape = api.DipWeight.storage_dims(d_in, d_out)
        return shape, d_in, (d_in, d_out, api.PERM_TILE)
    return (d_in, d_out), d_in, None


def param_template(cfg) -> Dict[str, Any]:
    """Nested dict: leaf = (shape, dtype_str, fan_in[, dip_meta]).
    Layer-stacked; ``shape`` is the *storage* shape (padded for DiP)."""
    d, v = cfg.d_model, cfg.padded_vocab
    pdt = cfg.param_dtype
    t: Dict[str, Any] = {
        "embed": ((v, d), pdt, d),
        "final_norm": ((d,), pdt, None),
    }
    if not cfg.tie_embeddings:
        shape, fan, dip = _lin(cfg, d, v)
        t["lm_head"] = (shape, pdt, fan, dip)

    def stacked(shape, fan, L, dip=None):
        return ((L,) + shape, pdt, fan, dip)

    L = cfg.n_layers
    blk: Dict[str, Any] = {}

    if cfg.ssm_state:  # mamba2 blocks (ssm and hybrid families)
        dims = ssm.ssm_dims(cfg)
        nl = L
        s_in, f_in, dip_in = _lin(cfg, d, dims["in_dim"])
        s_out, f_out, dip_out = _lin(cfg, dims["d_inner"], d)
        blk.update(
            norm_in=stacked((d,), None, nl),
            in_proj=stacked(s_in, f_in, nl, dip_in),
            conv_w=stacked((cfg.ssm_conv, dims["conv_dim"]), cfg.ssm_conv, nl),
            conv_b=stacked((dims["conv_dim"],), None, nl),
            dt_bias=stacked((dims["heads"],), None, nl),
            A_log=stacked((dims["heads"],), None, nl),
            D=stacked((dims["heads"],), None, nl),
            norm=stacked((dims["d_inner"],), None, nl),
            out_proj=stacked(s_out, f_out, nl, dip_out),
        )
        t["layers"] = blk
        if cfg.is_hybrid:
            hd = cfg.resolved_head_dim
            sh: Dict[str, Any] = {"attn_norm": ((d,), pdt, None), "ffn_norm": ((d,), pdt, None)}
            for nm, (di, do) in dict(
                wq=(d, cfg.n_heads * hd), wk=(d, cfg.n_kv_heads * hd),
                wv=(d, cfg.n_kv_heads * hd), wo=(cfg.n_heads * hd, d),
                w_gate=(d, cfg.d_ff), w_up=(d, cfg.d_ff), w_down=(cfg.d_ff, d),
            ).items():
                shape, fan, dip = _lin(cfg, di, do)
                sh[nm] = (shape, pdt, fan, dip)
            t["shared_attn"] = sh
        return t

    # transformer families
    hd = cfg.resolved_head_dim
    blk["attn_norm"] = stacked((d,), None, L)
    blk["ffn_norm"] = stacked((d,), None, L)
    if cfg.use_mla:
        dn, dr, dvh = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        rr = cfg.kv_lora_rank
        for nm, (di, do) in dict(
            wq=(d, cfg.n_heads * (dn + dr)), w_dkv=(d, rr), w_krope=(d, dr),
            w_uk=(rr, cfg.n_heads * dn), w_uv=(rr, cfg.n_heads * dvh),
            wo=(cfg.n_heads * dvh, d),
        ).items():
            shape, fan, dip = _lin(cfg, di, do)
            blk[nm] = stacked(shape, fan, L, dip)
        blk["kv_norm"] = stacked((rr,), None, L)       # kv_a_layernorm gain
    else:
        for nm, (di, do) in dict(
            wq=(d, cfg.n_heads * hd), wk=(d, cfg.n_kv_heads * hd),
            wv=(d, cfg.n_kv_heads * hd), wo=(cfg.n_heads * hd, d),
        ).items():
            shape, fan, dip = _lin(cfg, di, do)
            blk[nm] = stacked(shape, fan, L, dip)
        if cfg.qkv_bias:
            blk["bq"] = stacked((cfg.n_heads * hd,), None, L)
            blk["bk"] = stacked((cfg.n_kv_heads * hd,), None, L)
            blk["bv"] = stacked((cfg.n_kv_heads * hd,), None, L)

    if cfg.is_moe:
        # leading dense layers keep their FFN in t["dense"] and the MoE
        # layers theirs in t["moe"], each stacked over its own layers; the
        # attention half stays in t["layers"], stacked over all of them
        kd = cfg.first_k_dense
        moe_blk = {} if kd else blk
        lm = L - kd
        e, eh, ffe = cfg.n_experts, cfg.held_experts, cfg.d_ff_expert
        moe_blk["router"] = stacked((d, e), d, lm)
        moe_blk["w_gate"] = stacked((eh, d, ffe), d, lm)
        moe_blk["w_up"] = stacked((eh, d, ffe), d, lm)
        moe_blk["w_down"] = stacked((eh, ffe, d), ffe, lm)
        if cfg.n_shared_experts:
            sff = cfg.n_shared_experts * ffe
            for nm, (di, do) in dict(
                shared_w_gate=(d, sff), shared_w_up=(d, sff), shared_w_down=(sff, d)
            ).items():
                shape, fan, dip = _lin(cfg, di, do)
                moe_blk[nm] = stacked(shape, fan, lm, dip)
        if kd:
            t["moe"] = moe_blk
            t["dense"] = {}
            for nm, (di, do) in dict(
                w_gate=(d, cfg.d_ff), w_up=(d, cfg.d_ff), w_down=(cfg.d_ff, d)
            ).items():
                shape, fan, dip = _lin(cfg, di, do)
                t["dense"][nm] = stacked(shape, fan, kd, dip)
    else:
        for nm, (di, do) in dict(
            w_gate=(d, cfg.d_ff), w_up=(d, cfg.d_ff), w_down=(cfg.d_ff, d)
        ).items():
            shape, fan, dip = _lin(cfg, di, do)
            blk[nm] = stacked(shape, fan, L, dip)

    t["layers"] = blk
    return t


# The roles of ``param_template`` that the forward reads only through a cast
# to the compute dtype: the projections (``layers.linear``), the embedding
# (cast before its gather, and as the tied head), the MoE expert banks (cast
# before ``ragged_dot`` / ``einsum``) and MLA's absorbed up-projections.
# Every other role is read in float32 somewhere: norm gains (``rms_norm``,
# the fused prologue), QKV biases (the f32 epilogue), the router (f32
# scores) and the SSM leaves (``conv_w``, ``conv_b``, ``dt_bias``, ``A_log``,
# ``D``).
COMPUTE_DTYPE_ROLES = frozenset({
    "embed", "lm_head",
    "wq", "wk", "wv", "wo", "w_dkv", "w_krope", "w_uk", "w_uv",
    "w_gate", "w_up", "w_down",
    "shared_w_gate", "shared_w_up", "shared_w_down",
    "in_proj", "out_proj",
})


def serving_params(cfg, params: Dict[str, Any]) -> Dict[str, Any]:
    """``params`` with each leaf of a ``COMPUTE_DTYPE_ROLES`` role rounded
    once to ``cfg.compute_dtype``: what the serving engine keeps.

    The forward's per-call ``.astype`` then has nothing left to do, and
    rounding once gives the values it gave, so the logits are bit-identical
    while each step reads half the weight bytes (from float32).  Only a
    narrowing is made: nothing is cast when the compute dtype is as wide as
    the storage.  Quantized weights and every other role keep their storage.
    The tree is rebuilt, never written to, so the caller's stays as it was
    (a trainer keeps its float32 master).  A cast ``DipWeight`` keeps its
    metadata and plan and drops its ABFT checksum, which described the old
    storage; the serving programs verify none."""
    cd = jnp.dtype(cfg.compute_dtype)

    def cast(path, leaf):
        if (getattr(path[-1], "key", None) not in COMPUTE_DTYPE_ROLES
                or isinstance(leaf, api.QuantizedDipWeight)):
            return leaf
        dt = jnp.dtype(leaf.dtype)
        if jnp.issubdtype(dt, jnp.floating) and dt.itemsize > cd.itemsize:
            return leaf.astype(cd)
        return leaf

    return jax.tree_util.tree_map_with_path(
        cast, params,
        is_leaf=lambda x: isinstance(x, (api.DipWeight, api.QuantizedDipWeight)))


def _map_template(t, fn):
    if isinstance(t, dict):
        return {k: _map_template(v, fn) for k, v in t.items()}
    return fn(*t)


def param_specs(cfg) -> Dict[str, Any]:
    """ShapeDtypeStruct pytree — used by the dry-run (no allocation).
    DiP-stored linears appear as ``DipWeight`` (or, under
    ``cfg.quantization``, ``QuantizedDipWeight``) nodes wrapping the spec of
    their (padded) storage, mirroring ``init_params`` exactly."""
    scheme = cfg.quant_scheme

    def mk(shape, dt, fan, dip=None):
        if dip is not None and scheme is not None:
            info = api.quant.scheme_info(scheme)
            data = jax.ShapeDtypeStruct(shape, jnp.dtype(info.storage_dtype))
            scale = jax.ShapeDtypeStruct(shape[:-2] + (1, shape[-1]), jnp.float32)
            return api.QuantizedDipWeight(data, scale, *dip, scheme=scheme)
        spec = jax.ShapeDtypeStruct(shape, jnp.dtype(dt))
        return api.DipWeight(spec, *dip) if dip is not None else spec

    return _map_template(param_template(cfg), mk)


def quantize_params(params: Dict[str, Any], scheme: str) -> Dict[str, Any]:
    """Quantize every DiP-stored projection to ``scheme`` storage.

    Only ``DipWeight`` nodes are quantized (embeddings, norms, biases, and
    the SSM scalars stay float — they are not DiP-array operands); already
    quantized nodes pass through ``quant.quantize`` untouched.  This is the
    offline calibration step: run it once at init / checkpoint load, never
    per forward.
    """
    dip_types = (api.DipWeight, api.QuantizedDipWeight)
    return jax.tree_util.tree_map(
        lambda t: api.quant.quantize(t, scheme) if isinstance(t, dip_types) else t,
        params,
        is_leaf=lambda t: isinstance(t, dip_types),
    )


def init_params(key: jax.Array, cfg) -> Dict[str, Any]:
    """Materialized parameters (truncated-normal fan-in scaling; norms at 1).

    DiP-stored weights are initialized in natural layout then converted with
    ``api.DipWeight.from_natural`` — the offline permutation step of paper
    Fig. 3, run once at init / checkpoint-load, never per step.
    """
    template = param_template(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(
        template, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple)
    )
    keys = jax.random.split(key, len(leaves))

    def make(leaf, k):
        shape, dt, fan = leaf[:3]
        dip = leaf[3] if len(leaf) > 3 else None
        dt = jnp.dtype(dt)
        if fan is None:  # norms / biases / scalars
            init = jnp.ones(shape, dt)
            return init

        # special-cased SSM scalars by shape heuristics handled below
        scale = (1.0 / max(1, fan)) ** 0.5
        if dip is not None:
            d_in, d_out, perm_tile = dip
            nat_shape = shape[:-2] + (d_in, d_out)
            nat = (
                jax.random.truncated_normal(k, -2, 2, nat_shape, jnp.float32) * scale
            ).astype(dt)
            return api.DipWeight.from_natural(nat, perm_tile)
        return (jax.random.truncated_normal(k, -2, 2, shape, jnp.float32) * scale).astype(dt)

    params = jax.tree_util.tree_unflatten(treedef, [make(l, k) for l, k in zip(leaves, keys)])

    # SSM-specific parameter semantics
    if cfg.ssm_state:
        lyr = params["layers"]
        nl = cfg.n_layers
        dims = ssm.ssm_dims(cfg)
        k1, k2 = jax.random.split(key)
        lyr["A_log"] = jnp.log(
            jax.random.uniform(k1, (nl, dims["heads"]), jnp.float32, 1.0, 16.0)
        ).astype(jnp.dtype(cfg.param_dtype))
        dt0 = jax.random.uniform(k2, (nl, dims["heads"]), jnp.float32, 1e-3, 0.1)
        lyr["dt_bias"] = (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(jnp.dtype(cfg.param_dtype))
        lyr["conv_b"] = jnp.zeros_like(lyr["conv_b"])
    if cfg.qkv_bias and "bq" in params.get("layers", {}):
        for nm in ("bq", "bk", "bv"):
            params["layers"][nm] = jnp.zeros_like(params["layers"][nm])
    if cfg.quant_scheme is not None:
        params = quantize_params(params, cfg.quant_scheme)
    return params


# ---------------------------------------------------------------- forward ---
def _fuses_rmsnorm(cfg) -> bool:
    """Whether the configured backend fuses the RMSNorm prologue into its
    kernels' load stage (``api.backend_prologues``).  When it does, the
    blocks hand the UN-normalized residual stream plus the norm gain to the
    projections and the normed (B, S, d) tensor never round-trips HBM; when
    it does not, the blocks normalize up front exactly as before (passing
    the prologue anyway would decompose to one rms_norm PER projection)."""
    return "rmsnorm" in api.get_backend(cfg.matmul_backend).prologues


def _rope(cfg, positions):
    """The per-forward rope tables of the attention layers."""
    dim = cfg.qk_rope_head_dim if cfg.use_mla else cfg.resolved_head_dim
    return layers.rope_tables(positions, dim, cfg.rope_theta, cfg.rope_yarn)


def _ffn_half(x, lp, i, cfg, ffn_params, *, fuse_norm, constrain, plan=None,
              dropless=False):
    """The FFN half of layer ``i``, skip connection included: (x, aux,
    moe stats (routed, rows, dropped)).

    Dense configs run ``lp``'s SwiGLU; MoE configs ``moe.routed_ffn``
    (``dropless`` at inference).  With ``cfg.first_k_dense`` leading dense
    layers the FFN weights are not in ``lp`` but stacked apart
    (``ffn_params``: the params' "dense" and "moe" trees), and the layer
    index picks the half under ``lax.cond``: attention and the cache stay
    one scan over all layers."""
    zero = jnp.zeros((), jnp.float32)

    def dense(x, wp):
        ffn_in, ffn_g = (
            (x, lp["ffn_norm"]) if fuse_norm
            else (layers.rms_norm(x, lp["ffn_norm"], cfg.norm_eps), None)
        )
        # skip connection fused into the down-projection
        return (moe.dense_ffn(ffn_in, wp, cfg, constrain=constrain, residual=x,
                              norm=ffn_g), zero, jnp.zeros((3,), jnp.int32))

    def routed(x, wp):
        # the router and the experts both read the normed stream; MoE keeps
        # the explicit norm (fusing it into each expert dispatch would
        # recompute it per projection)
        ffn_in = layers.rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
        f, aux, stats = moe.routed_ffn(ffn_in, wp, cfg, plan=plan,
                                       constrain=constrain, dropless=dropless)
        return x + f, aux, stats

    if not cfg.is_moe:
        return dense(x, lp)
    kd = cfg.first_k_dense
    if not kd:
        return routed(x, lp)

    def layer_of(tree, j):
        return jax.tree_util.tree_map(
            lambda t: jax.lax.dynamic_index_in_dim(t, j, keepdims=False), tree)

    return jax.lax.cond(
        i < kd,
        lambda x: dense(x, layer_of(ffn_params["dense"], jnp.minimum(i, kd - 1))),
        lambda x: routed(x, layer_of(ffn_params["moe"], jnp.maximum(i - kd, 0))),
        x)


def _ffn_params(params):
    return {k: params[k] for k in ("dense", "moe") if k in params}


def _transformer_block(x, lp, i, cfg, ffn_params, *, positions, rope, cache,
                       kv_chunk, constrain, plan=None, unroll=False,
                       attn_backend=None):
    fuse_norm = _fuses_rmsnorm(cfg)
    attn_in, attn_g = (
        (x, lp["attn_norm"]) if fuse_norm
        else (layers.rms_norm(x, lp["attn_norm"], cfg.norm_eps), None)
    )
    # mid-block residual fused into the attention out-projection's flush
    # (one HBM write instead of write + re-read + add); the fused result is
    # left to propagation like the explicit add was (constraining it forces
    # an extra scatter/gather pair per layer — §Perf iter 4, refuted)
    attn = attention.mla_attention if cfg.use_mla else attention.gqa_attention
    x, new_cache = attn(
        attn_in, lp, cfg, positions=positions, cache=cache,
        kv_chunk=kv_chunk, constrain=constrain, unroll=unroll,
        rope=rope, residual=x, norm=attn_g, attn_backend=attn_backend,
    )
    # a cache means inference, where the experts drop nothing
    x, aux, stats = _ffn_half(x, lp, i, cfg, ffn_params, fuse_norm=fuse_norm,
                              constrain=constrain, plan=plan,
                              dropless=cache is not None)
    # the scan carry is saved per layer for backward — constraining it keeps
    # the saved residuals in the sequence-sharded layout (16x less memory)
    return constrain(x, "act_btd"), new_cache, aux, stats


def _mamba_block(x, lp, cfg, *, cache, constrain):
    inner_in = layers.rms_norm(x, lp["norm_in"], cfg.norm_eps)
    # skip connection fused into ssd_block's out-projection
    return ssm.ssd_block(inner_in, lp, cfg, cache=cache, constrain=constrain,
                         residual=x)


def forward(*args, **kwargs) -> Tuple[jax.Array, Optional[Dict], jax.Array]:
    """Returns (logits, new_cache, aux_loss); see :func:`forward_counted`,
    which also returns the MoE layers' counts."""
    return forward_counted(*args, **kwargs)[:3]


def forward_counted(
    params: Dict[str, Any],
    cfg,
    *,
    tokens: Optional[jax.Array] = None,        # (B, S) int32
    embeddings: Optional[jax.Array] = None,    # (B, S, d) — [vlm]/[audio] stubs
    cache: Optional[Dict] = None,              # layer-stacked cache pytree
    kv_chunk: int = 0,
    plan=None,                                 # repro.distributed.ShardingPlan
    constrain: Optional[Constrain] = None,     # legacy hook; plan wins
    unroll: bool = False,                      # dry-run cost-probe mode: unroll
                                               # layer scans so XLA cost analysis
                                               # counts every layer (see
                                               # launch/dryrun.py probe logic)
    logits_positions: str = "all",             # "all" | "last" — serving prefill
                                               # needs only the next-token logits
    return_hidden: bool = False,               # skip the lm_head: return the
                                               # final-normed hidden states for
                                               # the fused lm_head+CE loss
                                               # (kernels.lm_head_ce)
    attn_backend: Optional[str] = None,        # api.attention backend for the
                                               # attention core ("flash" routes
                                               # serving prefill through the
                                               # fused kernel; forward-only)
) -> Tuple[jax.Array, Optional[Dict], jax.Array, jax.Array]:
    """Returns (logits, new_cache, aux_loss, moe_stats): moe_stats sums the
    MoE layers' (routed, rows, dropped) counts (``moe.routed_ffn``; zeros
    without MoE).

    Distribution enters through ``plan``: its activation constraints replace
    the old bare ``constrain`` callback, and DiP weights that carry the
    plan's per-weight metadata dispatch the explicit sharded backends when
    ``cfg.matmul_backend`` names one (``dip_tp`` / ``dip_sp`` /
    ``dip_fsdp`` / ``dip_ep``)."""
    constrain = layers.resolve_constrain(plan, constrain)
    cd = jnp.dtype(cfg.compute_dtype)
    if embeddings is not None:
        x = embeddings.astype(cd)
    else:
        x = params["embed"].astype(cd)[tokens]
    x = constrain(x, "act_btd")
    b, s = x.shape[:2]

    start = cache["pos"] if cache is not None else jnp.zeros((), jnp.int32)
    positions = start + jnp.arange(s, dtype=jnp.int32)

    remat = cfg.remat == "block"
    stats_total = jnp.zeros((3,), jnp.int32)

    if cfg.ssm_state:
        x, new_layer_caches = _scan_mamba(params, cfg, x, cache, remat, constrain,
                                          unroll, kv_chunk, attn_backend)
        aux_total = jnp.zeros((), jnp.float32)
    else:
        # RoPE cos/sin hoisted out of the per-layer path: position-only, so
        # ONE table per forward (a scan constant) instead of n_layers
        # transcendental sweeps
        rope = _rope(cfg, positions)
        ffn_params = _ffn_params(params)

        def block(carry, xs):
            x, aux, stats = carry
            lp, lcache, i = xs
            if lcache is not None:
                lcache = dict(lcache, pos=start)  # all layers share the position
            x, new_cache, aux_i, stats_i = _transformer_block(
                x, lp, i, cfg, ffn_params, positions=positions, rope=rope,
                cache=lcache, kv_chunk=kv_chunk, constrain=constrain, plan=plan,
                unroll=unroll, attn_backend=attn_backend,
            )
            if new_cache is not None:
                new_cache = _strip_pos(new_cache)
            return (x, aux + aux_i, stats + stats_i), new_cache

        block_fn = jax.checkpoint(block) if remat else block
        layer_caches = cache["layers"] if cache is not None else None
        (x, aux_total, stats_total), new_layer_caches = jax.lax.scan(
            block_fn, (x, jnp.zeros((), jnp.float32), stats_total),
            (params["layers"], layer_caches, jnp.arange(cfg.n_layers)),
            unroll=cfg.n_layers if unroll else 1,
        )

    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if logits_positions == "last":
        # serving prefill: one row through the lm_head instead of S rows —
        # removes the (B, S, V) logits and their gathers (§Perf pair 3)
        x = x[:, -1:]

    new_cache = None
    if cache is not None:
        new_cache = dict(cache)
        new_cache["layers"] = new_layer_caches
        new_cache["pos"] = cache["pos"] + s

    if return_hidden:
        # fused lm_head+CE training path: the caller feeds these hidden
        # states straight into kernels.lm_head_ce, so the (B, S, V) logits
        # are never formed at all
        return x, new_cache, aux_total, stats_total

    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    if cfg.tie_embeddings:
        logits = jnp.matmul(
            x, head.astype(cd), preferred_element_type=jnp.float32
        ).astype(jnp.float32)
    else:
        logits = layers.linear(
            x, head, backend=cfg.matmul_backend, compute_dtype=cd,
        ).astype(jnp.float32)
    if cfg.padded_vocab != cfg.vocab_size:
        # mask the padding lanes (never sampled, -inf in the softmax/loss);
        # keeping the padded width lets the vocab dim shard over any axis
        lane = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
        logits = jnp.where(lane < cfg.vocab_size, logits, -1e30)
    logits = constrain(logits, "logits")
    return logits, new_cache, aux_total, stats_total


def _scan_mamba(params, cfg, x, cache, remat, constrain, unroll=False,
                kv_chunk=0, attn_backend=None):
    """Scan over mamba blocks; hybrid: shared attn applied per superblock."""
    lp_all = params["layers"]
    lcaches = cache["layers"] if cache is not None else None

    pos_now = cache["pos"] if cache is not None else jnp.zeros((), jnp.int32)

    def mblock(x, lp, lcache):
        if lcache is not None:
            lcache = dict(lcache, pos=pos_now)
        x, nc = _mamba_block(x, lp, cfg, cache=lcache, constrain=constrain)
        return x, (_strip_pos(nc) if nc is not None else None)

    mblock = jax.checkpoint(mblock) if remat else mblock

    if not cfg.is_hybrid:
        def body(x, xs):
            lp, lc = xs
            return mblock(x, lp, lc)
        return jax.lax.scan(body, x, (lp_all, lcaches),
                            unroll=cfg.n_layers if unroll else 1)

    # hybrid: group layers into superblocks of attn_every mamba layers,
    # each followed by the single shared attention+FFN block.
    ae = cfg.attn_every
    n_super = cfg.n_layers // ae
    shared = params["shared_attn"]
    b, s = x.shape[:2]
    positions = pos_now + jnp.arange(s, dtype=jnp.int32)
    # hoisted RoPE tables for the shared attention block (scan constant)
    rope = layers.rope_tables(positions, cfg.resolved_head_dim, cfg.rope_theta)

    def regroup(t):
        return t.reshape((n_super, ae) + t.shape[1:])

    lp_grp = jax.tree_util.tree_map(regroup, lp_all)
    # split cache: mamba caches (stacked L) + shared-attn caches (stacked n_super)
    mcache_grp = (
        jax.tree_util.tree_map(regroup, {k: v for k, v in lcaches.items() if k != "attn"})
        if lcaches is not None else None
    )
    acache = lcaches["attn"] if lcaches is not None else None

    fuse_norm = _fuses_rmsnorm(cfg)

    def shared_block(x, sc):
        if sc is not None:
            sc = dict(sc, pos=pos_now)
        attn_in, attn_g = (
            (x, shared["attn_norm"]) if fuse_norm
            else (layers.rms_norm(x, shared["attn_norm"], cfg.norm_eps), None)
        )
        x, new_sc = attention.gqa_attention(
            attn_in, shared, cfg, positions=positions, cache=sc,
            kv_chunk=kv_chunk, constrain=constrain, unroll=unroll,
            rope=rope, residual=x, norm=attn_g, attn_backend=attn_backend,
        )
        ffn_in, ffn_g = (
            (x, shared["ffn_norm"]) if fuse_norm
            else (layers.rms_norm(x, shared["ffn_norm"], cfg.norm_eps), None)
        )
        x = moe.dense_ffn(ffn_in, shared, cfg, constrain=constrain, residual=x,
                          norm=ffn_g)
        return x, (_strip_pos(new_sc) if new_sc is not None else None)

    def superblock(x, xs):
        lp, mc, ac = xs
        def inner(x, ys):
            ilp, imc = ys
            return mblock(x, ilp, imc)
        x, new_mc = jax.lax.scan(inner, x, (lp, mc), unroll=ae if unroll else 1)
        x, new_ac = shared_block(x, ac)
        return x, (new_mc, new_ac)

    x, (new_mc, new_ac) = jax.lax.scan(
        superblock, x, (lp_grp, mcache_grp, acache),
        unroll=n_super if unroll else 1,
    )
    if cache is None:
        return x, None
    new_mc = jax.tree_util.tree_map(
        lambda t: t.reshape((cfg.n_layers,) + t.shape[2:]), new_mc
    )
    new_mc["attn"] = new_ac
    return x, new_mc


# ------------------------------------------------------------------ caches --
def init_cache(cfg, batch: int, max_seq: int) -> Dict[str, Any]:
    """Layer-stacked decode cache (leading axis = n_layers / n_super)."""
    cd = jnp.dtype(cfg.compute_dtype)

    def stack(make, n):
        caches = [make() for _ in range(n)]
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *caches)

    if cfg.ssm_state:
        base = stack(lambda: _strip_pos(ssm.init_ssm_cache(batch, cfg, cd)), cfg.n_layers)
        if cfg.is_hybrid:
            n_super = cfg.n_layers // cfg.attn_every
            base["attn"] = stack(
                lambda: _strip_pos(
                    attention.init_gqa_cache(
                        batch, cfg.n_kv_heads, max_seq, cfg.resolved_head_dim, cd
                    )
                ),
                n_super,
            )
        layers_cache = base
    elif cfg.use_mla:
        layers_cache = stack(
            lambda: _strip_pos(attention.init_mla_cache(batch, max_seq, cfg, cd)),
            cfg.n_layers,
        )
    else:
        layers_cache = stack(
            lambda: _strip_pos(
                attention.init_gqa_cache(
                    batch, cfg.n_kv_heads, max_seq, cfg.resolved_head_dim, cd
                )
            ),
            cfg.n_layers,
        )
    return {"layers": layers_cache, "pos": jnp.zeros((), jnp.int32)}


def _strip_pos(c: Dict) -> Dict:
    return {k: v for k, v in c.items() if k != "pos"}


def init_paged_cache(cfg, num_blocks: int, block_size: int, *, slots: int,
                     kv_quant: str = "none") -> Dict[str, Any]:
    """Layer-stacked *paged* decode cache for the serving engine.

    Attention K/V (and the MLA latent) live in a shared pool of
    ``num_blocks`` fixed-size blocks indexed through per-slot block tables;
    SSM conv/scan state is O(1) per sequence, so it gets a plain per-slot
    pool (batch axis = ``slots``) rather than pages.  There is no global
    ``pos`` — positions are per-slot and passed to each decode step.  Block 0
    is reserved as the null block (see ``repro.serving.kv_cache``).
    """
    cd = jnp.dtype(cfg.compute_dtype)

    def stack(make, n):
        caches = [make() for _ in range(n)]
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *caches)

    def gqa_pool():
        return attention.init_paged_gqa_cache(
            num_blocks, block_size, cfg.n_kv_heads, cfg.resolved_head_dim,
            cd, kv_quant,
        )

    if cfg.ssm_state:
        base = stack(lambda: _strip_pos(ssm.init_ssm_cache(slots, cfg, cd)),
                     cfg.n_layers)
        if cfg.is_hybrid:
            n_super = cfg.n_layers // cfg.attn_every
            base["attn"] = stack(gqa_pool, n_super)
        layers_cache = base
    elif cfg.use_mla:
        layers_cache = stack(
            lambda: attention.init_paged_mla_cache(
                num_blocks, block_size, cfg, cd, kv_quant
            ),
            cfg.n_layers,
        )
    else:
        layers_cache = stack(gqa_pool, cfg.n_layers)
    return {"layers": layers_cache}


def paged_decode_step_fn(cfg, *, plan=None, constrain: Optional[Constrain] = None,
                         moe_stats: bool = False):
    """Returns ``step(params, cache, tokens, positions, block_tables)``
    -> ``(logits, cache)`` — the serving engine's one compiled decode step;
    with ``moe_stats`` also the MoE layers' summed (routed, rows, dropped)
    counts.  The experts drop nothing here (``moe.routed_ffn`` dropless).

    ``tokens``: (slots, 1) int32; ``positions``: (slots,) int32 absolute
    position of each slot's current token; ``block_tables``:
    (slots, blocks_per_seq) int32 into the paged pools of ``cache`` (from
    :func:`init_paged_cache`).  Each slot attends only to its own blocks with
    its own positions, so the rows are fully independent — free slots point
    at the reserved null block and their logits are garbage the engine
    ignores.  All shapes are static: one compilation serves the pool for the
    whole engine lifetime.
    """
    constrain = layers.resolve_constrain(plan, constrain)
    kvq = cfg.kv_quant

    def step(params, cache, tokens, positions, block_tables):
        cd = jnp.dtype(cfg.compute_dtype)
        x = params["embed"].astype(cd)[tokens]          # (B, 1, d)
        x = constrain(x, "act_btd")
        stats = jnp.zeros((3,), jnp.int32)

        if cfg.ssm_state:
            x, new_layer_caches = _paged_scan_mamba(
                params, cfg, x, cache, positions, block_tables, kvq, constrain
            )
        else:
            rope = _rope(cfg, positions[:, None])
            attn = (attention.paged_mla_attention if cfg.use_mla
                    else attention.paged_gqa_attention)
            fuse_norm = _fuses_rmsnorm(cfg)
            ffn_params = _ffn_params(params)

            def block(carry, xs):
                x, stats = carry
                lp, lcache, i = xs
                attn_in, attn_g = (
                    (x, lp["attn_norm"]) if fuse_norm
                    else (layers.rms_norm(x, lp["attn_norm"], cfg.norm_eps),
                          None)
                )
                x, new_cache = attn(
                    attn_in, lp, cfg, positions=positions, cache=lcache,
                    block_tables=block_tables, kv_quant=kvq,
                    constrain=constrain, rope=rope, residual=x, norm=attn_g,
                )
                x, _, stats_i = _ffn_half(
                    x, lp, i, cfg, ffn_params, fuse_norm=fuse_norm,
                    constrain=constrain, plan=plan, dropless=True)
                return (constrain(x, "act_btd"), stats + stats_i), new_cache

            (x, stats), new_layer_caches = jax.lax.scan(
                block, (x, stats),
                (params["layers"], cache["layers"], jnp.arange(cfg.n_layers))
            )

        x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        if cfg.tie_embeddings:
            logits = jnp.matmul(
                x, head.astype(cd), preferred_element_type=jnp.float32
            ).astype(jnp.float32)
        else:
            logits = layers.linear(
                x, head, backend=cfg.matmul_backend, compute_dtype=cd,
            ).astype(jnp.float32)
        if cfg.padded_vocab != cfg.vocab_size:
            lane = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
            logits = jnp.where(lane < cfg.vocab_size, logits, -1e30)
        logits = constrain(logits, "logits")
        if moe_stats:
            return logits, {"layers": new_layer_caches}, stats
        return logits, {"layers": new_layer_caches}

    return step


def _paged_scan_mamba(params, cfg, x, cache, positions, block_tables, kvq,
                      constrain):
    """Paged-serving analogue of :func:`_scan_mamba`: mamba state is a plain
    per-slot pool (the O(1) decode never reads positions), the hybrid shared
    attention goes through the paged path."""
    lp_all = params["layers"]
    lcaches = cache["layers"]
    zero = jnp.zeros((), jnp.int32)   # ssd_block's pos bookkeeping — unused here

    def mblock(x, lp, lcache):
        x, nc = _mamba_block(x, lp, cfg, cache=dict(lcache, pos=zero),
                             constrain=constrain)
        return x, _strip_pos(nc)

    if not cfg.is_hybrid:
        def body(x, xs):
            lp, lc = xs
            return mblock(x, lp, lc)
        x, new_mc = jax.lax.scan(body, x, (lp_all, lcaches))
        return x, new_mc

    ae = cfg.attn_every
    n_super = cfg.n_layers // ae
    shared = params["shared_attn"]
    rope = layers.rope_tables(positions[:, None], cfg.resolved_head_dim,
                              cfg.rope_theta)

    def regroup(t):
        return t.reshape((n_super, ae) + t.shape[1:])

    lp_grp = jax.tree_util.tree_map(regroup, lp_all)
    mcache_grp = jax.tree_util.tree_map(
        regroup, {k: v for k, v in lcaches.items() if k != "attn"}
    )
    acache = lcaches["attn"]

    fuse_norm = _fuses_rmsnorm(cfg)

    def shared_block(x, sc):
        attn_in, attn_g = (
            (x, shared["attn_norm"]) if fuse_norm
            else (layers.rms_norm(x, shared["attn_norm"], cfg.norm_eps), None)
        )
        x, new_sc = attention.paged_gqa_attention(
            attn_in, shared, cfg, positions=positions, cache=sc,
            block_tables=block_tables, kv_quant=kvq,
            constrain=constrain, rope=rope, residual=x, norm=attn_g,
        )
        ffn_in, ffn_g = (
            (x, shared["ffn_norm"]) if fuse_norm
            else (layers.rms_norm(x, shared["ffn_norm"], cfg.norm_eps), None)
        )
        x = moe.dense_ffn(ffn_in, shared, cfg, constrain=constrain, residual=x,
                          norm=ffn_g)
        return x, new_sc

    def superblock(x, xs):
        lp, mc, ac = xs
        def inner(x, ys):
            ilp, imc = ys
            return mblock(x, ilp, imc)
        x, new_mc = jax.lax.scan(inner, x, (lp, mc))
        x, new_ac = shared_block(x, ac)
        return x, (new_mc, new_ac)

    x, (new_mc, new_ac) = jax.lax.scan(superblock, x, (lp_grp, mcache_grp, acache))
    new_mc = jax.tree_util.tree_map(
        lambda t: t.reshape((cfg.n_layers,) + t.shape[2:]), new_mc
    )
    new_mc["attn"] = new_ac
    return x, new_mc


# ------------------------------------------------------------- objectives ---
def _natural_head(params, cfg):
    """The lm_head as a natural (D, padded_vocab) array for the fused loss."""
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    if isinstance(head, api.QuantizedDipWeight):
        return head.to_natural(jnp.float32)
    if isinstance(head, api.DipWeight):
        return head.to_natural()
    return head


def loss_fn(params, cfg, batch, *, plan=None, constrain: Optional[Constrain] = None,
            unroll: bool = False, kv_chunk: int = 0,
            fused_ce: Optional[bool] = None) -> jax.Array:
    """Next-token cross entropy (+ router aux).  ``batch["loss_mask"]``
    (optional, (B, S), nonzero = train on this position) and the -100
    ``ignore_index`` convention in ``labels`` both exclude tokens from the
    loss mean and gradient.

    ``fused_ce=None`` auto-selects the fused lm_head+cross-entropy kernel
    (``kernels.lm_head_ce``) whenever no sharding plan / constrain hook
    needs to see the logits: the (B, S, V) logits then never reach HBM in
    either direction.  Pass ``False`` to force the unfused path (oracle for
    parity tests), ``True`` to force fusion.
    """
    mask = batch.get("loss_mask")
    shift_mask = None if mask is None else mask[:, 1:]
    if fused_ce is None:
        fused_ce = plan is None and constrain is None
    if fused_ce:
        hidden, _, aux = forward(
            params, cfg,
            tokens=batch.get("tokens"), embeddings=batch.get("embeddings"),
            plan=plan, constrain=constrain, unroll=unroll, kv_chunk=kv_chunk,
            return_hidden=True,
        )
        loss = lm_head_ce.fused_cross_entropy_loss(
            hidden[:, :-1], _natural_head(params, cfg),
            batch["labels"][:, 1:], mask=shift_mask,
            vocab_size=cfg.vocab_size, interpret=api.default_interpret(),
        )
        return loss + aux
    logits, _, aux = forward(
        params, cfg,
        tokens=batch.get("tokens"), embeddings=batch.get("embeddings"),
        plan=plan, constrain=constrain, unroll=unroll, kv_chunk=kv_chunk,
    )
    loss = layers.cross_entropy_loss(
        logits[:, :-1], batch["labels"][:, 1:], mask=shift_mask,
    )
    return loss + aux


def train_step_fn(cfg, optimizer, *, plan=None, constrain: Optional[Constrain] = None,
                  unroll: bool = False, kv_chunk: int = 0, microbatch: int = 1,
                  fused_ce: Optional[bool] = None, guard: bool = False):
    """Returns step(state, batch) -> (state, metrics).  Pure; jit at call site.

    ``plan`` carries the distribution decisions (see :func:`forward`).
    ``microbatch > 1`` enables gradient accumulation: the global batch is
    split into ``microbatch`` slices scanned sequentially with the summed
    gradient applied once — live activation memory scales with the slice
    size (the standard fit-the-HBM lever for the biggest train cells).

    ``guard=True`` wraps the step in the reliability guard
    (``repro.reliability.guard``): nonfinite loss/grad screening plus the
    parameter-fingerprint integrity check, with poisoned steps skipped
    (update discarded, counters advanced).  The guarded state carries the
    fingerprint side-car next to params/opt_state/step — initialize it
    with ``reliability.init_guard_state``.
    """

    def grad_of(params, batch):
        return jax.value_and_grad(
            lambda p: loss_fn(p, cfg, batch, plan=plan, constrain=constrain,
                              unroll=unroll, kv_chunk=kv_chunk,
                              fused_ce=fused_ce)
        )(params)

    def step(state, batch):
        params, opt_state, step_no = state["params"], state["opt_state"], state["step"]
        if microbatch <= 1:
            loss, grads = grad_of(params, batch)
        else:
            def split(t):
                b = t.shape[0]
                return t.reshape((microbatch, b // microbatch) + t.shape[1:])

            micro = jax.tree_util.tree_map(split, batch)

            def acc_step(carry, mb):
                loss_acc, g_acc = carry
                loss_i, g_i = grad_of(params, mb)
                return (
                    loss_acc + loss_i,
                    jax.tree_util.tree_map(jnp.add, g_acc, g_i),
                ), None

            zero_g = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            (loss, grads), _ = jax.lax.scan(
                acc_step, (jnp.zeros((), jnp.float32), zero_g), micro
            )
            inv = 1.0 / microbatch
            loss = loss * inv
            grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(lambda p, u: p + u, params, updates)
        gnorm = optimizer.last_grad_norm(opt_state)
        new_state = {"params": params, "opt_state": opt_state, "step": step_no + 1}
        return new_state, {"loss": loss, "grad_norm": gnorm, "step": step_no + 1}

    if guard:
        from repro.reliability import guard as guard_lib  # lazy: no cycle

        return guard_lib.guarded_step_fn(step)
    return step


def decode_step_fn(cfg, *, plan=None, constrain: Optional[Constrain] = None,
                   unroll: bool = False, attn_backend: Optional[str] = None,
                   moe_stats: bool = False):
    """Returns serve_step(params, cache, tokens) -> (logits, cache), and
    with ``moe_stats`` the MoE layers' summed (routed, rows, dropped)
    counts as a third output.

    ``attn_backend="flash"`` routes the attention core through the fused
    ``api.attention`` kernel — the serving chunked-prefill path (forward
    only, so decode/prefill steps qualify; training does not)."""

    def step(params, cache, tokens):
        logits, new_cache, _, stats = forward_counted(
            params, cfg, tokens=tokens, cache=cache, plan=plan,
            constrain=constrain, unroll=unroll, attn_backend=attn_backend,
        )
        return (logits, new_cache, stats) if moe_stats else (logits, new_cache)

    return step
