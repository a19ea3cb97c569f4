"""Token-choice top-k Mixture-of-Experts with grouped capacity dispatch.

Dispatch is scatter/gather based and *grouped by sequence* (no (T, E, C)
one-hot einsum tensor — that would not fit HBM at 1M-token global batches,
and no global-token cumsum — that forces GSPMD to replicate dispatch state):
within each sequence, every (token, slot) computes its rank inside its
expert's per-group buffer via a batch-local cumsum, tokens are scatter-added
into a (B, E, C, d) buffer, the expert FFNs run as one batched einsum, and
results are gathered back and combined with renormalized gates.  Tokens past
an expert's per-group capacity C = ceil(S*k*cf / E) are dropped (standard
GShard/Switch semantics, applied per group) — the drop COUNT is returned so
callers can assert capacity is ample (``moe_ffn`` -> (out, aux, dropped)).

Sharding has two modes, decided by the :class:`~repro.distributed.plan.
ShardingPlan` threaded through ``plan=``:

* **Dense-style (default)**: groups (B) over the DP axes, experts (E) over
  the "model" axis for both buffers and weights; all routing math is
  shard-local and the token<->expert exchange is the batched scatter/gather
  GSPMD lowers to dispatch collectives.
* **Expert parallel** (``plan.expert_plan`` set, i.e. strategy "ep"): ONE
  explicit shard_map over the model axis per MoE layer.  Tokens shard over
  batch (or sequence), experts over the axis; the body routes locally,
  issues the dispatch ``all_to_all`` FIRST, then runs the shared-expert
  compute the transfer hides behind, then the local expert einsums, then
  the combine ``all_to_all`` — exactly TWO all-to-alls per layer, with the
  aux/drop stats folded into one psum.  See docs/distributed.md.

Aux losses: load-balance (Switch) + router z-loss, returned for the trainer.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers

Constrain = Callable[[jax.Array, str], jax.Array]
_id: Constrain = lambda x, tag: x

__all__ = ["moe_ffn", "dense_ffn", "moe_capacity"]


def dense_ffn(
    x: jax.Array, p: Dict, cfg, *, plan=None,
    constrain: Optional[Constrain] = None, residual: jax.Array = None,
    norm: Optional[jax.Array] = None, backend: Optional[str] = None,
) -> jax.Array:
    """SwiGLU MLP (dense archs and MoE shared experts).

    The gate and up projections run as ONE dual-weight ``swiglu`` dispatch:
    on fused backends that is a single kernel reading x once and writing
    only the activated product (no intermediate gate/up arrays in HBM); on
    other backends ``api.matmul`` decomposes with identical semantics.
    Under the explicit ``dip_tp`` backend this is the canonical Megatron
    pair: the column-parallel gate/up swiglu runs collective-free and the
    row-parallel down-projection pays the block's single psum.
    ``residual`` fuses the block's skip connection into the down-projection
    the same way.  ``norm`` takes the pre-FFN RMSNorm gain when the backend
    fuses prologues: x arrives UN-normalized and the swiglu dispatch
    normalizes it in its load stage — rmsnorm + gate + up + silu·mul in ONE
    kernel launch.  ``backend`` overrides ``cfg.matmul_backend`` (the EP
    shard_map body runs shared experts on the per-device inner backend).
    """
    constrain = layers.resolve_constrain(plan, constrain)
    lk = dict(backend=backend or cfg.matmul_backend, compute_dtype=x.dtype)
    gk = dict(lk) if norm is None else dict(
        lk, prologue="rmsnorm", prologue_operands=(norm,),
        prologue_eps=cfg.norm_eps,
    )
    h = layers.linear(x, (p["w_gate"], p["w_up"]), epilogue="swiglu", **gk)
    h = constrain(h, "ffn_hidden")
    if residual is not None:
        return layers.linear(h, p["w_down"], epilogue="residual",
                             epilogue_operands=(residual,), **lk)
    return layers.linear(h, p["w_down"], **lk)


def moe_capacity(tokens: int, cfg) -> int:
    cap = math.ceil(tokens * cfg.moe_top_k * cfg.capacity_factor / cfg.n_experts)
    return max(8, -(-cap // 8) * 8)  # round up to 8 for TPU-friendly shapes


# --------------------------------------------------------------------------
# routing / dispatch / combine building blocks — shared by the dense-style
# path (global arrays, GSPMD shards them) and the EP shard_map body (local
# shards, collectives placed by hand)
def _route(x: jax.Array, router: jax.Array, cfg, cap: int) -> Dict[str, jax.Array]:
    """Group-local routing state for ``x`` (G groups of S tokens each).

    fp32 router softmax + top-k with renormalized gates, the Switch
    load-balance + z-loss aux, and the sort-by-expert dispatch order
    (gather-only — GSPMD partitions batched take_along_axis gathers along
    the group dim, but replicates multi-index scatters; §Perf pair-2).
    ``dropped`` counts (token, slot) pairs past an expert's capacity."""
    g, sl, d = x.shape
    e, k = cfg.n_experts, cfg.moe_top_k
    cd = x.dtype
    logits = jnp.einsum(
        "bsd,de->bse", x.astype(jnp.float32), router.astype(jnp.float32)
    )                                                            # (G, S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, k)                         # (G, S, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # load-balance loss (Switch): E * mean(frac_tokens_e * mean_prob_e)
    load = jax.nn.one_hot(ids[..., 0], e, dtype=jnp.float32).mean((0, 1))
    importance = probs.mean((0, 1))
    aux = cfg.router_aux_loss * e * jnp.sum(load * importance)
    aux = aux + 1e-4 * jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))

    flat_ids = ids.reshape(g, sl * k)                            # slot-major
    gates_flat = gates.reshape(g, sl * k).astype(cd)
    order = jnp.argsort(flat_ids, axis=1)                        # stable
    inv_order = jnp.argsort(order, axis=1)
    sorted_ids = jnp.take_along_axis(flat_ids, order, axis=1)
    src = jnp.repeat(x, k, axis=1)                               # (G, S*k, d)
    sorted_src = jnp.take_along_axis(src, order[..., None], axis=1)

    # expert run boundaries within each group
    erange = jnp.arange(e, dtype=jnp.int32)
    start = jax.vmap(
        lambda row: jnp.searchsorted(row, erange, side="left")
    )(sorted_ids)
    end = jax.vmap(
        lambda row: jnp.searchsorted(row, erange, side="right")
    )(sorted_ids)
    counts = end - start                                         # (G, E)
    dropped = jnp.maximum(counts - cap, 0).sum().astype(jnp.int32)
    return dict(
        gates_flat=gates_flat, order=order, inv_order=inv_order,
        sorted_ids=sorted_ids, sorted_src=sorted_src, start=start,
        counts=counts, aux=aux, dropped=dropped,
    )


def _fill_buffer(r: Dict[str, jax.Array], cap: int) -> jax.Array:
    """Gather each expert's first C tokens into the (G, E, C, d) buffer."""
    sorted_src, start, counts = r["sorted_src"], r["start"], r["counts"]
    g, sk, d = sorted_src.shape
    e = counts.shape[1]
    c_iota = jnp.arange(cap, dtype=jnp.int32)
    gidx = start[:, :, None] + c_iota[None, None, :]             # (G, E, C)
    valid = c_iota[None, None, :] < jnp.minimum(counts, cap)[:, :, None]
    gidx = jnp.clip(gidx, 0, sk - 1).reshape(g, e * cap)
    buf = jnp.take_along_axis(
        sorted_src, gidx[..., None], axis=1
    ).reshape(g, e, cap, d)
    return buf * valid[..., None].astype(sorted_src.dtype)


def _combine(y: jax.Array, r: Dict[str, jax.Array], cap: int, k: int) -> jax.Array:
    """Gather expert outputs back per sorted slot, unsort, gate, sum k."""
    g, e, _, d = y.shape
    cd = y.dtype
    sk = r["sorted_ids"].shape[1]
    j_iota = jnp.arange(sk, dtype=jnp.int32)[None, :]
    pos_sorted = j_iota - jnp.take_along_axis(r["start"], r["sorted_ids"], axis=1)
    keep_sorted = pos_sorted < cap
    slot = r["sorted_ids"] * cap + jnp.where(keep_sorted, pos_sorted, 0)
    out_sorted = jnp.take_along_axis(
        y.reshape(g, e * cap, d), slot[..., None], axis=1
    ) * keep_sorted[..., None].astype(cd)
    out = jnp.take_along_axis(out_sorted, r["inv_order"][..., None], axis=1)
    return (out * r["gates_flat"][..., None]).reshape(g, sk // k, k, d).sum(axis=2)


def _shared_params(p: Dict) -> Optional[Dict]:
    return {
        "w_gate": p["shared_w_gate"],
        "w_up": p["shared_w_up"],
        "w_down": p["shared_w_down"],
    } if "shared_w_gate" in p else None


# --------------------------------------------------------------------------
def moe_ffn(
    x: jax.Array, p: Dict, cfg, *, plan=None,
    constrain: Optional[Constrain] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Routed expert FFN.  Returns (output, aux_loss, dropped_token_count).

    ``dropped`` is the number of (token, slot) pairs past capacity this
    layer (int32 scalar) — zero when ``capacity_factor`` is ample; the
    conformance tests assert it stays zero where exact parity is claimed.

    Dispatch is *grouped by sequence*: every routing tensor (one-hot, cumsum,
    scatter/gather indices) carries the batch dim, so under the sharding
    policy all routing math is shard-local (B over DP), the (B, E, C, d)
    expert buffers shard E over TP, and the only cross-device movement is
    the unavoidable token<->expert exchange GSPMD derives from the batched
    scatter/gather.  When the plan carries an :attr:`~repro.distributed.
    plan.ShardingPlan.expert_plan` (strategy "ep") the exchange is instead
    placed by hand: see :func:`_moe_ffn_ep`.
    """
    eplan = getattr(plan, "expert_plan", None)
    if eplan is not None and eplan.mesh is not None:
        t = eplan.mesh.shape[eplan.axis]
        b, s, _ = x.shape
        if t > 1 and cfg.n_experts % t == 0 and (b % t == 0 or s % t == 0):
            return _moe_ffn_ep(x, p, cfg, eplan, plan=plan,
                               constrain=constrain)

    constrain = layers.resolve_constrain(plan, constrain)
    b, s, d = x.shape
    cd = x.dtype
    cap = moe_capacity(s, cfg)                                   # per-group capacity

    r = _route(x, p["router"], cfg, cap)
    buf = constrain(_fill_buffer(r, cap), "expert_buf")

    # ---- batched per-expert SwiGLU: weights (E, d, ffe) / (E, ffe, d) ----
    gate_h = jnp.einsum("becd,edf->becf", buf, p["w_gate"].astype(cd))
    up_h = jnp.einsum("becd,edf->becf", buf, p["w_up"].astype(cd))
    h = layers.swiglu(gate_h, up_h)
    h = constrain(h, "expert_hidden")
    y = jnp.einsum("becf,efd->becd", h, p["w_down"].astype(cd))  # (B, E, C, d)
    y = constrain(y, "expert_buf")

    out = _combine(y, r, cap, cfg.moe_top_k)

    # shared experts (DeepSeek-style), computed densely for every token
    shared = _shared_params(p)
    if cfg.n_shared_experts and shared is not None:
        out = out + dense_ffn(x, shared, cfg, constrain=constrain)
    return constrain(out, "act_btd"), r["aux"], r["dropped"]


# --------------------------------------------------------------------------
# expert parallelism: explicit all-to-all dispatch/combine
def _ep_payload(w):
    """(storage, scale) payloads of a possibly-DiP/quantized shared-expert
    weight, so the shard_map body can rebuild it plan-FREE (an attached plan
    would re-enter the sharded dispatch from inside the per-device body)."""
    if hasattr(w, "data"):
        return w.data, getattr(w, "scale", None)
    return w, None


def _moe_ffn_ep(
    x: jax.Array, p: Dict, cfg, eplan, *, plan=None,
    constrain: Optional[Constrain] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Expert-parallel MoE layer: ONE shard_map over the expert axis.

    Tokens shard over batch when it divides the axis (groups stay whole, so
    capacity semantics match the dense-style path exactly), else over
    sequence.  Inside the body, per device:

        1. route the LOCAL tokens (router replicated — tiny),
        2. build the (G_loc, E, C, d) buffer and issue the dispatch
           ``all_to_all`` (experts split over the axis, tokens concatenated)
           — issued FIRST so the transfer runs while step 3 computes,
        3. shared-expert SwiGLU on the local tokens (the compute the
           dispatch hides behind; weights replicated, rebuilt plan-free),
        4. local expert-bank einsums over the E/T experts this device owns,
        5. combine ``all_to_all`` back, unsort, gate, add shared,
        6. ONE psum folding (aux, dropped) stats.

    Exactly TWO all-to-alls per MoE layer — the jaxpr contract the fleet
    validator and the multidevice suite assert.
    """
    from repro.kernels.dip_matmul_sharded import _inner_backend, _local_weight

    mesh, ax = eplan.mesh, eplan.axis
    t = mesh.shape[ax]
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.moe_top_k
    e_loc = e // t
    cd = x.dtype
    batch_split = b % t == 0
    sl = s if batch_split else s // t                 # tokens per local group
    cap = moe_capacity(sl, cfg)
    P = jax.sharding.PartitionSpec
    from repro.kernels.common import shard_map

    shared = _shared_params(p) if cfg.n_shared_experts else None
    if shared is not None:
        s_payloads = tuple(_ep_payload(shared[n])
                           for n in ("w_gate", "w_up", "w_down"))
        s_datas = tuple(pl[0] for pl in s_payloads)
        s_scales = tuple(pl[1] for pl in s_payloads if pl[1] is not None)
    else:
        s_datas, s_scales = (), ()

    banks = (p["w_gate"], p["w_up"], p["w_down"])     # (E, d, ffe) x2, (E, ffe, d)

    def body(xl, router, banks_l, s_datas_l, s_scales_l):
        g = xl.shape[0]
        r = _route(xl, router, cfg, cap)
        buf = _fill_buffer(r, cap)                    # (G_loc, E, C, d)
        # experts split over the axis, local tokens concatenated: every
        # device ends up holding ALL tokens destined for ITS E/T experts.
        # Issued before the shared-expert compute below — trace order is
        # dispatch order, so the transfer overlaps that compute.
        bufe = jnp.swapaxes(buf, 0, 1).reshape(e, g * cap, d)
        disp = jax.lax.all_to_all(
            bufe, ax, split_axis=0, concat_axis=1, tiled=True
        )                                             # (E/T, T*G_loc*C, d)

        # shared experts on the LOCAL tokens, hidden behind the dispatch
        if shared is not None:
            it = iter(s_scales_l)
            sw = {
                n: _local_weight(
                    shared[n], dat,
                    next(it) if _ep_payload(shared[n])[1] is not None else None,
                    getattr(shared[n], "d_in", dat.shape[-2]),
                    getattr(shared[n], "d_out", dat.shape[-1]),
                ) if hasattr(shared[n], "data") else dat
                for n, dat in zip(("w_gate", "w_up", "w_down"), s_datas_l)
            }
            inner = (
                _inner_backend(shared["w_gate"])
                if hasattr(shared["w_gate"], "data") else "xla"
            )
            shared_out = dense_ffn(xl, sw, cfg, constrain=_id, backend=inner)
        else:
            shared_out = None

        # local expert banks: this device's E/T experts over every token
        wg, wu, wd = (bl.astype(cd) for bl in banks_l)
        gate_h = jnp.einsum("etd,edf->etf", disp, wg)
        up_h = jnp.einsum("etd,edf->etf", disp, wu)
        y = jnp.einsum("etf,efd->etd", layers.swiglu(gate_h, up_h), wd)

        comb = jax.lax.all_to_all(
            y, ax, split_axis=1, concat_axis=0, tiled=True
        )                                             # (E, G_loc*C, d)
        yl = jnp.swapaxes(comb.reshape(e, g, cap, d), 0, 1)
        out = _combine(yl, r, cap, k)
        if shared_out is not None:
            out = out + shared_out
        # ONE psum for the stats pair: aux averages over devices (each saw
        # 1/T of the tokens), drops sum.  The pair is stacked in f32 (a
        # tuple binds one psum per leaf); drop counts stay exact below 2**24
        stats = jax.lax.psum(
            jnp.stack([r["aux"].astype(jnp.float32),
                       r["dropped"].astype(jnp.float32)]), ax
        )
        dropped = jnp.round(stats[1]).astype(r["dropped"].dtype)
        return out, stats[0] / t, dropped

    xspec = P(ax, None, None) if batch_split else P(None, ax, None)
    out, aux, dropped = shard_map(
        body, mesh=mesh,
        in_specs=(
            xspec,
            P(None, None),                            # router: replicated
            tuple(P(ax, None, None) for _ in banks),  # expert dim split
            tuple(P(None, None) for _ in s_datas),    # shared: replicated
            tuple(P(None, None) for _ in s_scales),
        ),
        out_specs=(xspec, P(), P()),
    )(x, p["router"], banks, s_datas, s_scales)
    constrain = layers.resolve_constrain(plan, constrain)
    return constrain(out, "act_btd"), aux, dropped
