"""Explicit multi-chip DiP matmul backends: `shard_map` over the tiled kernels.

The paper's scaling section (Sec. V) tiles the array from 4x4 to 64x64 with
per-array efficiency intact but stops at one array; the system question for
many chips is *who owns the partitioning and when the collectives fire*.
The implicit answer so far was GSPMD: a plain dot over sharded operands and
XLA chooses.  This module is the explicit answer — two backends that consume
the :class:`~repro.distributed.plan.WeightPlan` carried on a weight's
metadata and place every collective by hand, as ``shard_map`` wrappers over
the *existing* tiled kernels (nothing here re-implements a matmul):

``dip_tp`` (tensor parallel, Megatron-style):
    column  (weight plan kind "column", e.g. wq/wk/wv/w_gate/w_up)
            storage N sharded over the TP axis; x replicated; every shard
            runs ONE fused kernel launch (epilogue included — bias /
            activation / residual operands shard with N).  **Zero
            collectives**: the output stays N-sharded for the next
            row-parallel projection.
    row     (kind "row", e.g. wo/w_down) storage K sharded; x arrives
            K-sharded (exactly what a column-parallel predecessor + local
            elementwise produces); each shard runs ONE kernel launch with
            ``epilogue="none"``, the partial accumulators are combined with
            **exactly one psum** (a single equation even for the dual-weight
            swiglu pair), and the epilogue is applied to the *reduced* f32
            value — the bias/residual is added once, not once per shard, and
            XLA fuses the epilogue arithmetic into the psum's consumer.

``dip_sp`` (sequence parallel, Megatron-SP-style):
    column  x arrives **sequence(M)-sharded** (what the norm/dropout region
            of an SP transformer produces) and the gather of the other
            shards' rows happens *inside* the dispatch as a T-step ring:
            each step first forwards the currently-held x block to the next
            device (``ppermute`` — the transfer the NEXT launch overlaps
            with) and then runs ONE fused launch multiplying that block by
            the local N shard.  **Zero all_gathers, zero psums** — T
            launches, T-1 ppermutes, output N-sharded with full M (the SP
            gather point).  On TPU hardware the ppermute lowers to the ICI
            async remote copy of the ring all-gather pattern; the schedule
            here is that pattern expressed at the shard_map level.
    row     like ``dip_tp`` row, but the combine is ``psum_scatter`` (ONE
            reduce_scatter per weight) so the output returns sequence(M)-
            sharded — the SP scatter point.  The epilogue runs post-
            reduction on the local rows only.

``dip_fsdp`` (ZeRO-3, all-gather-on-load):
    storage K sharded over the FSDP ("data") axis — each device holds
    1/N of every weight's bytes (quantized storage gathers at int8/fp8
    width) — and x is batch(M)-sharded over the same axis.  The body
    all-gathers the weight (**exactly one all_gather per weight**, the
    "on-load" gather ZeRO-3 pays), then runs ONE fused kernel launch over
    the local M rows.  Zero psums.

Block sizes for the per-shard kernel launches come from the ordinary tuning
table, keyed on the *local shard shapes* (N/tp or K/tp, M/fsdp) — the shard
is the shape the hardware actually sees, so measured entries transfer.

Dispatch contract (see ``repro.api.registry``): ``api.matmul`` routes here
when ``backend`` is ``dip_tp``/``dip_sp``/``dip_fsdp`` AND the weight
carries a plan
with a mesh; with no plan attached it decomposes to the implicit GSPMD
path.  See ``docs/distributed.md`` for the collective-placement diagrams.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import epilogue as epilogue_lib
from repro.kernels import prologue as prologue_lib
from repro.kernels.common import shard_map

__all__ = ["dip_tp_matmul", "dip_fsdp_matmul", "dip_sp_matmul",
           "count_collectives", "collective_schedule"]

_COLLECTIVES = ("psum", "all_gather", "all_to_all", "ppermute",
                "reduce_scatter")


# --------------------------------------------------------------------------
# structural evidence: collective/launch counts straight from the jaxpr
def count_collectives(fn, *args) -> Dict[str, int]:
    """Count collective and pallas_call equations a traced call would issue
    (recursing through shard_map/pjit/custom_vjp/scan sub-jaxprs).  The
    conformance tests assert the placement contract with this: one psum for
    row-parallel, zero collectives for column-parallel, one all_gather per
    weight for fsdp."""
    closed = jax.make_jaxpr(fn)(*args)
    counts = {name: 0 for name in _COLLECTIVES + ("pallas_call",)}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in counts:
                counts[eqn.primitive.name] += 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(closed.jaxpr)
    return counts


def collective_schedule(fn, *args) -> List[str]:
    """The collective/launch equations a traced call would issue, in program
    order (depth-first through sub-jaxprs — trace order, which is the order
    the runtime dispatches them).  The overlap tests assert *placement* with
    this where counts alone cannot: ``dip_sp`` must interleave each ring
    ppermute BEFORE the launch it overlaps with, and ``dip_ep`` must issue
    the dispatch all-to-all before the shared-expert launches it hides
    behind."""
    closed = jax.make_jaxpr(fn)(*args)
    watched = set(_COLLECTIVES + ("pallas_call",))
    order: List[str] = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in watched:
                order.append(eqn.primitive.name)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(closed.jaxpr)
    return order


# --------------------------------------------------------------------------
# shared plumbing
def _pad_dim(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _pad_cols_to(a: jax.Array, width: int) -> jax.Array:
    pad = width - a.shape[-1]
    if pad == 0:
        return a
    return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])


def _local_weight(w, data, scale, d_in: int, d_out: int):
    """Rebuild a (plan-free) weight object around local shard payloads —
    plan-free so the inner ``api.matmul`` dispatch cannot recurse back here."""
    from repro import api

    if isinstance(w, api.QuantizedDipWeight):
        return api.QuantizedDipWeight(data, scale, d_in, d_out, w.perm_tile,
                                      w.scheme)
    return api.DipWeight(data, d_in, d_out, w.perm_tile)


def _inner_backend(w) -> Optional[str]:
    """Backend for the per-shard launch: the paper fast path for float DiP
    storage, the scheme's quantized kernel (backend=None dispatch) for
    quantized storage."""
    from repro import api

    return None if isinstance(w, api.QuantizedDipWeight) else "pallas_dip"


def _payloads(weights) -> Tuple[Tuple[jax.Array, ...], Tuple[jax.Array, ...]]:
    """(storages, scales) — scales empty for float weights."""
    from repro import api

    datas = tuple(w.data for w in weights)
    if isinstance(weights[0], api.QuantizedDipWeight):
        return datas, tuple(w.scale for w in weights)
    return datas, ()


def _validate(weights, plan, backend: str) -> None:
    if any(type(w) is not type(weights[0]) for w in weights):
        raise ValueError(f"{backend}: weight pair must share a type")
    if any(getattr(w, "plan", None) != plan for w in weights):
        raise ValueError(
            f"{backend}: weight pair must share one WeightPlan, got "
            f"{[getattr(w, 'plan', None) for w in weights]}"
        )
    if plan is None or plan.mesh is None:
        raise ValueError(
            f"{backend} needs a WeightPlan with a mesh on the weight "
            "(ShardingPlan.attach_params); plan-free weights decompose to "
            "GSPMD through api.matmul"
        )


def _epilogue_out_dtype(x: jax.Array) -> jnp.dtype:
    # same rule as the fused kernels: epilogue arithmetic is f32, so the
    # output is float even when the matmul accumulates in int32
    return x.dtype if jnp.issubdtype(x.dtype, jnp.floating) else jnp.float32


def _resolve_prologue(prologue, pro_operands, prologue_eps, x, w0,
                      full_k_local: bool):
    """Decide where the prologue runs.  Full-K shards with perm-tile-aligned
    d_in fuse it into the per-shard kernel launch (inner ``api.matmul`` —
    the normalized block never round-trips HBM).  Row plans split K across
    shards (no shard sees a whole row to normalize) and unaligned d_in
    would lose the logical sum-of-squares divisor inside the shard, so
    those normalize ONCE here before the shard_map — same arithmetic,
    unfused.  Returns (normalized-or-original x, fuse flag)."""
    if prologue == "none":
        return x, False
    if full_k_local and w0.d_in == w0.data.shape[-2]:
        return x, True
    xn = prologue_lib.apply(
        prologue, x, *(g.reshape(-1) for g in pro_operands), eps=prologue_eps
    )
    return xn, False


# --------------------------------------------------------------------------
def dip_tp_matmul(
    x: jax.Array,
    weights: Sequence,
    operands: Sequence[jax.Array],
    *,
    plan,
    epilogue: str = "none",
    prologue: str = "none",
    prologue_operands: Sequence[jax.Array] = (),
    prologue_eps: float = prologue_lib.DEFAULT_EPS,
    interpret: Optional[bool] = None,
    block_m: Optional[int] = None,
    block_n: Optional[int] = None,
    block_k: Optional[int] = None,
) -> jax.Array:
    """Tensor-parallel dispatch of ``epilogue(prologue(x) @ w ...)`` per the
    weight's plan kind (column / row) — see the module doc for collective
    placement.  Column plans keep the full contraction on every shard, so
    the prologue fuses into the per-shard launch; row plans split K, so the
    prologue runs once before the shard_map (``_resolve_prologue``)."""
    from repro import api

    _validate(weights, plan, "dip_tp")
    if plan.kind not in ("column", "row"):
        raise ValueError(
            f"dip_tp consumes column/row WeightPlans, got kind={plan.kind!r} "
            "(replicated weights decompose to GSPMD through api.matmul)"
        )
    mesh, ax = plan.mesh, plan.axis
    tp = mesh.shape[ax]
    w0 = weights[0]
    if w0.data.ndim != 2:
        raise ValueError(
            f"sharded matmul weight must be 2-D (got storage "
            f"{w0.data.shape}); index the stacked axis first"
        )
    kp, np_ = w0.data.shape
    if x.shape[-1] != w0.d_in:
        raise ValueError(
            f"x contraction {x.shape[-1]} does not match {type(w0).__name__} "
            f"d_in={w0.d_in} (storage {w0.data.shape})"
        )
    spec = epilogue_lib.spec(epilogue)
    blocks = dict(block_m=block_m, block_n=block_n, block_k=block_k,
                  interpret=interpret)
    x, fuse_pro = _resolve_prologue(
        prologue, prologue_operands, prologue_eps, x, w0,
        full_k_local=plan.kind == "column",
    )
    pops = (
        tuple(g.reshape(1, -1) for g in prologue_operands) if fuse_pro else ()
    )

    lead = x.shape[:-1]
    x2 = _pad_dim(x.reshape((-1, x.shape[-1])), 1, w0.perm_tile)
    m2 = x2.shape[0]
    datas, scales = _payloads(weights)

    if plan.kind == "column":
        if np_ % tp or (np_ // tp) % w0.perm_tile:
            raise ValueError(
                f"dip_tp column: storage N={np_} must split into "
                f"perm-tile-aligned shards over {ax!r}={tp}"
            )
        n_loc = np_ // tp
        # epilogue operands shard with N: bias rides as a (1, Np) row,
        # residual as the (m2, Np) output-aligned block
        if spec.bias:
            eops = (_pad_cols_to(operands[0].reshape(1, w0.d_out), np_),)
            eop_specs = (P(None, ax),)
        elif spec.residual:
            r2 = operands[0].reshape(-1, w0.d_out)
            eops = (_pad_cols_to(r2, np_),)
            eop_specs = (P(None, ax),)
        else:
            eops = ()
            eop_specs = ()

        def body(xl, datas_l, scales_l, pops_l, eops_l):
            # local d_in = Kp (x arrives already padded): the shard storage
            # keeps the full contraction, only N is split
            wl = tuple(
                _local_weight(w, d, s, kp, n_loc)
                for w, d, s in zip(
                    weights, datas_l, scales_l or (None,) * len(datas_l)
                )
            )
            wl = wl[0] if not spec.dual_weight else wl
            # ONE fused launch per shard: disjoint output columns, so the
            # prologue (gain replicated, full K local) and epilogue
            # (bias/activation/residual shards included) fuse fully
            return api.matmul(
                xl, wl, backend=_inner_backend(w0),
                epilogue=epilogue if epilogue != "none" else None,
                epilogue_operands=eops_l,
                prologue=prologue if fuse_pro else None,
                prologue_operands=pops_l, prologue_eps=prologue_eps,
                **blocks,
            )

        out2 = shard_map(
            body, mesh=mesh,
            in_specs=(
                P(None, None),
                tuple(P(None, ax) for _ in datas),
                tuple(P(None, ax) for _ in scales),
                tuple(P(None, None) for _ in pops),
                tuple(eop_specs),
            ),
            out_specs=P(None, ax),
        )(x2, datas, scales, pops, eops)
        return out2[:m2, : w0.d_out].reshape(lead + (w0.d_out,))

    # ---- row-parallel: K sharded, ONE psum, epilogue post-reduction -------
    if kp % tp or (kp // tp) % w0.perm_tile:
        raise ValueError(
            f"dip_tp row: storage K={kp} must split into perm-tile-aligned "
            f"shards over {ax!r}={tp}"
        )
    k_loc = kp // tp
    if spec.bias:
        eops = (_pad_cols_to(operands[0].reshape(1, w0.d_out), np_),)
    elif spec.residual:
        eops = (_pad_cols_to(operands[0].reshape(-1, w0.d_out), np_),)
    else:
        eops = ()

    def body(xl, datas_l, scales_l, eops_l):
        wl = tuple(
            _local_weight(w, d, s, k_loc, np_)
            for w, d, s in zip(
                weights, datas_l, scales_l or (None,) * len(datas_l)
            )
        )
        # Low-precision activations widen to f32 for the PARTIAL launches:
        # bf16 values embed exactly in f32, so the products are unchanged
        # while the per-shard output skips the bf16 round-trip a kernel
        # flush would apply BEFORE the reduction — the psummed value then
        # matches the single-device kernel's f32 accumulator, with the one
        # cast to the activation dtype applied after the reduction.
        floating = jnp.issubdtype(xl.dtype, jnp.floating)
        xl_in = (
            xl.astype(jnp.float32)
            if floating and xl.dtype != jnp.float32 else xl
        )
        # one launch per weight, epilogue deferred past the reduction
        partials = tuple(
            api.matmul(xl_in, w, backend=_inner_backend(w0), **blocks)
            for w in wl
        )
        if epilogue == "none" and not jnp.issubdtype(
            partials[0].dtype, jnp.floating
        ):
            return jax.lax.psum(partials[0], ax)  # exact int32 reduction
        # ONE psum equation, even for the dual-weight swiglu pair: the pair
        # is stacked, since a tuple binds one psum per leaf
        zs = jax.lax.psum(
            jnp.stack([p.astype(jnp.float32) for p in partials]), ax
        )
        if epilogue == "none":
            return zs[0].astype(xl.dtype if floating else partials[0].dtype)
        aux = (zs[1],) if spec.dual_weight else tuple(
            e.astype(jnp.float32) for e in eops_l
        )
        out = epilogue_lib.apply(epilogue, zs[0], *aux)
        return out.astype(_epilogue_out_dtype(xl))

    out2 = shard_map(
        body, mesh=mesh,
        in_specs=(
            P(None, ax),
            tuple(P(ax, None) for _ in datas),
            # per-output-channel scales span full N; every K shard needs them
            tuple(P(None, None) for _ in scales),
            tuple(P(None, None) for _ in eops),
        ),
        out_specs=P(None, None),
    )(x2, datas, scales, eops)
    return out2[:m2, : w0.d_out].reshape(lead + (w0.d_out,))


def dip_fsdp_matmul(
    x: jax.Array,
    weights: Sequence,
    operands: Sequence[jax.Array],
    *,
    plan,
    epilogue: str = "none",
    prologue: str = "none",
    prologue_operands: Sequence[jax.Array] = (),
    prologue_eps: float = prologue_lib.DEFAULT_EPS,
    interpret: Optional[bool] = None,
    block_m: Optional[int] = None,
    block_n: Optional[int] = None,
    block_k: Optional[int] = None,
) -> jax.Array:
    """ZeRO-3 dispatch: K-sharded storage, all-gather-on-load, batch-sharded
    compute — see the module doc for collective placement.  Each shard owns
    whole x rows (M split, K whole), so the prologue fuses into the local
    launch with the gain replicated (``_resolve_prologue``)."""
    from repro import api

    _validate(weights, plan, "dip_fsdp")
    if plan.fsdp is None:
        raise ValueError(
            "dip_fsdp needs a WeightPlan with an fsdp axis "
            "(ShardingPlan.attach_params on a mesh with a 'data' axis)"
        )
    mesh, ax = plan.mesh, plan.fsdp
    n_sh = mesh.shape[ax]
    w0 = weights[0]
    if w0.data.ndim != 2:
        raise ValueError(
            f"sharded matmul weight must be 2-D (got storage "
            f"{w0.data.shape}); index the stacked axis first"
        )
    kp, np_ = w0.data.shape
    if x.shape[-1] != w0.d_in:
        raise ValueError(
            f"x contraction {x.shape[-1]} does not match {type(w0).__name__} "
            f"d_in={w0.d_in} (storage {w0.data.shape})"
        )
    if kp % n_sh:
        raise ValueError(
            f"dip_fsdp: storage K={kp} must divide the fsdp axis {ax!r}={n_sh}"
        )
    spec = epilogue_lib.spec(epilogue)
    blocks = dict(block_m=block_m, block_n=block_n, block_k=block_k,
                  interpret=interpret)
    x, fuse_pro = _resolve_prologue(
        prologue, prologue_operands, prologue_eps, x, w0, full_k_local=True
    )
    pops = (
        tuple(g.reshape(1, -1) for g in prologue_operands) if fuse_pro else ()
    )

    lead = x.shape[:-1]
    x2 = _pad_dim(x.reshape((-1, x.shape[-1])), 1, w0.perm_tile)
    m2 = x2.shape[0]
    x2p = _pad_dim(x2, 0, n_sh)  # M rows split over the fsdp axis
    datas, scales = _payloads(weights)

    if spec.bias:
        eops = (operands[0].reshape(1, w0.d_out),)
        eop_specs = (P(None, None),)
    elif spec.residual:
        # rides with x's M rows (padding rows compute a discarded epilogue)
        eops = (_pad_dim(operands[0].reshape(-1, w0.d_out), 0, n_sh),)
        eop_specs = (P(ax, None),)
    else:
        eops = ()
        eop_specs = ()

    def body(xl, datas_l, scales_l, pops_l, eops_l):
        # the ZeRO-3 "on-load" gather: one all_gather per weight, at the
        # storage width (int8/fp8 bytes for quantized weights)
        full = tuple(
            jax.lax.all_gather(d, ax, axis=0, tiled=True) for d in datas_l
        )
        # local d_in = Kp: x arrives padded, the gathered storage is whole
        wl = tuple(
            _local_weight(w, d, s, kp, w0.d_out)
            for w, d, s in zip(
                weights, full, scales_l or (None,) * len(full)
            )
        )
        wl = wl[0] if not spec.dual_weight else wl
        # ONE fused launch over the local M rows, prologue and epilogue
        # included (x rows are whole per shard, so the per-row norm is local)
        return api.matmul(
            xl, wl, backend=_inner_backend(w0),
            epilogue=epilogue if epilogue != "none" else None,
            epilogue_operands=eops_l,
            prologue=prologue if fuse_pro else None,
            prologue_operands=pops_l, prologue_eps=prologue_eps,
            **blocks,
        )

    out2 = shard_map(
        body, mesh=mesh,
        in_specs=(
            P(ax, None),
            tuple(P(ax, None) for _ in datas),
            tuple(P(None, None) for _ in scales),
            tuple(P(None, None) for _ in pops),
            tuple(eop_specs),
        ),
        out_specs=P(ax, None),
    )(x2p, datas, scales, pops, eops)
    return out2[:m2, : w0.d_out].reshape(lead + (w0.d_out,))


def dip_sp_matmul(
    x: jax.Array,
    weights: Sequence,
    operands: Sequence[jax.Array],
    *,
    plan,
    epilogue: str = "none",
    prologue: str = "none",
    prologue_operands: Sequence[jax.Array] = (),
    prologue_eps: float = prologue_lib.DEFAULT_EPS,
    interpret: Optional[bool] = None,
    block_m: Optional[int] = None,
    block_n: Optional[int] = None,
    block_k: Optional[int] = None,
) -> jax.Array:
    """Sequence-parallel dispatch: the column path streams the M-sharded x
    around a ring *inside* the dispatch (ppermute issued before each launch
    so the transfer overlaps the multiply), the row path combines with
    psum_scatter so the output returns sequence-sharded — see the module doc
    for collective placement."""
    from repro import api

    _validate(weights, plan, "dip_sp")
    if plan.kind not in ("column", "row"):
        raise ValueError(
            f"dip_sp consumes column/row WeightPlans, got kind={plan.kind!r} "
            "(replicated weights decompose to GSPMD through api.matmul)"
        )
    mesh, ax = plan.mesh, plan.axis
    tp = mesh.shape[ax]
    w0 = weights[0]
    if w0.data.ndim != 2:
        raise ValueError(
            f"sharded matmul weight must be 2-D (got storage "
            f"{w0.data.shape}); index the stacked axis first"
        )
    kp, np_ = w0.data.shape
    if x.shape[-1] != w0.d_in:
        raise ValueError(
            f"x contraction {x.shape[-1]} does not match {type(w0).__name__} "
            f"d_in={w0.d_in} (storage {w0.data.shape})"
        )
    spec = epilogue_lib.spec(epilogue)
    blocks = dict(block_m=block_m, block_n=block_n, block_k=block_k,
                  interpret=interpret)
    x, fuse_pro = _resolve_prologue(
        prologue, prologue_operands, prologue_eps, x, w0,
        full_k_local=plan.kind == "column",
    )
    pops = (
        tuple(g.reshape(1, -1) for g in prologue_operands) if fuse_pro else ()
    )

    lead = x.shape[:-1]
    x2 = _pad_dim(x.reshape((-1, x.shape[-1])), 1, w0.perm_tile)
    m2 = x2.shape[0]
    datas, scales = _payloads(weights)
    perm = [(j, (j + 1) % tp) for j in range(tp)]

    if plan.kind == "column":
        if np_ % tp or (np_ // tp) % w0.perm_tile:
            raise ValueError(
                f"dip_sp column: storage N={np_} must split into "
                f"perm-tile-aligned shards over {ax!r}={tp}"
            )
        x2p = _pad_dim(x2, 0, tp)  # sequence(M) rows split over the TP axis
        m_pad = x2p.shape[0]
        m_loc = m_pad // tp
        n_loc = np_ // tp
        if spec.bias:
            eops = (_pad_cols_to(operands[0].reshape(1, w0.d_out), np_),)
            eop_specs = (P(None, ax),)
        elif spec.residual:
            r2 = _pad_dim(
                _pad_cols_to(operands[0].reshape(-1, w0.d_out), np_), 0, tp
            )
            eops = (r2,)
            eop_specs = (P(None, ax),)
        else:
            eops = ()
            eop_specs = ()

        def body(xl, datas_l, scales_l, pops_l, eops_l):
            wl = tuple(
                _local_weight(w, d, s, kp, n_loc)
                for w, d, s in zip(
                    weights, datas_l, scales_l or (None,) * len(datas_l)
                )
            )
            wl = wl[0] if not spec.dual_weight else wl
            me = jax.lax.axis_index(ax)
            out = None  # allocated from the first launch's dtype
            # the ring: at step s this device holds the x block that
            # originated on device (me - s) mod tp.  The FORWARD of that
            # block to the next device is issued FIRST — data-independent of
            # the multiply, so it overlaps the launch that follows it (on
            # TPU, the ICI remote copy of the ring all-gather pattern).
            cur = xl
            for s in range(tp):
                nxt = jax.lax.ppermute(cur, ax, perm) if s < tp - 1 else None
                src = jax.lax.rem(me - s + tp, tp)
                if spec.residual:
                    step_eops = tuple(
                        jax.lax.dynamic_slice_in_dim(e, src * m_loc, m_loc, 0)
                        for e in eops_l
                    )
                else:
                    step_eops = eops_l
                # ONE fused launch per ring step: this block's complete
                # output rows for the local N columns, prologue (full K
                # local, gain replicated) and epilogue included
                y = api.matmul(
                    cur, wl, backend=_inner_backend(w0),
                    epilogue=epilogue if epilogue != "none" else None,
                    epilogue_operands=step_eops,
                    prologue=prologue if fuse_pro else None,
                    prologue_operands=pops_l, prologue_eps=prologue_eps,
                    **blocks,
                )
                if out is None:
                    out = jnp.zeros((m_pad, n_loc), y.dtype)
                out = jax.lax.dynamic_update_slice_in_dim(
                    out, y, src * m_loc, 0
                )
                cur = nxt
            return out

        out2 = shard_map(
            body, mesh=mesh,
            in_specs=(
                P(ax, None),
                tuple(P(None, ax) for _ in datas),
                tuple(P(None, ax) for _ in scales),
                tuple(P(None, None) for _ in pops),
                tuple(eop_specs),
            ),
            out_specs=P(None, ax),
        )(x2p, datas, scales, pops, eops)
        return out2[:m2, : w0.d_out].reshape(lead + (w0.d_out,))

    # ---- row-parallel: K sharded, psum_scatter, output sequence-sharded ----
    if kp % tp or (kp // tp) % w0.perm_tile:
        raise ValueError(
            f"dip_sp row: storage K={kp} must split into perm-tile-aligned "
            f"shards over {ax!r}={tp}"
        )
    k_loc = kp // tp
    x2p = _pad_dim(x2, 0, tp)  # output rows must split over the axis
    m_pad = x2p.shape[0]
    m_loc = m_pad // tp
    if spec.bias:
        eops = (_pad_cols_to(operands[0].reshape(1, w0.d_out), np_),)
        eop_specs = (P(None, None),)
    elif spec.residual:
        # rides with the SCATTERED output rows: sequence-sharded like them
        r2 = _pad_dim(
            _pad_cols_to(operands[0].reshape(-1, w0.d_out), np_), 0, tp
        )
        eops = (r2,)
        eop_specs = (P(ax, None),)
    else:
        eops = ()
        eop_specs = ()

    def body(xl, datas_l, scales_l, eops_l):
        wl = tuple(
            _local_weight(w, d, s, k_loc, np_)
            for w, d, s in zip(
                weights, datas_l, scales_l or (None,) * len(datas_l)
            )
        )
        # same f32-widening rule as dip_tp row: the reduce must see the
        # un-rounded f32 partials (see that body's comment)
        floating = jnp.issubdtype(xl.dtype, jnp.floating)
        xl_in = (
            xl.astype(jnp.float32)
            if floating and xl.dtype != jnp.float32 else xl
        )
        partials = tuple(
            api.matmul(xl_in, w, backend=_inner_backend(w0), **blocks)
            for w in wl
        )
        if epilogue == "none" and not jnp.issubdtype(
            partials[0].dtype, jnp.floating
        ):
            return jax.lax.psum_scatter(
                partials[0], ax, scatter_dimension=0, tiled=True
            )  # exact int32 reduction
        # ONE reduce_scatter per weight: each device keeps only its M rows
        # of the reduced value (the SP scatter point)
        zs = tuple(
            jax.lax.psum_scatter(
                p.astype(jnp.float32), ax, scatter_dimension=0, tiled=True
            )
            for p in partials
        )
        if epilogue == "none":
            return zs[0].astype(xl.dtype if floating else partials[0].dtype)
        aux = (zs[1],) if spec.dual_weight else tuple(
            e.astype(jnp.float32) for e in eops_l
        )
        out = epilogue_lib.apply(epilogue, zs[0], *aux)
        return out.astype(_epilogue_out_dtype(xl))

    out2 = shard_map(
        body, mesh=mesh,
        in_specs=(
            P(None, ax),
            tuple(P(ax, None) for _ in datas),
            tuple(P(None, None) for _ in scales),
            tuple(eop_specs),
        ),
        out_specs=P(ax, None),
    )(x2p, datas, scales, eops)
    return out2[:m2, : w0.d_out].reshape(lead + (w0.d_out,))
