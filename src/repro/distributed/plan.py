"""`ShardingPlan` — the distributed layer as a first-class, declarative object.

Historically the distributed stack was three loosely-coupled pieces: mesh
construction in ``launch/mesh.py``, a ``ShardingPolicy`` whose
``param_pspec`` walked template leaf *names* through an if/elif ladder, and
``with_sharding_constraint`` hooks threaded as bare callbacks.  This module
unifies them:

* **Mesh construction** — :func:`make_production_mesh` / :func:`make_local_mesh`
  live here (``repro.launch`` re-exports them for launcher convenience).
* **Per-weight partition decisions** — the leaf-name ladder is now the
  declarative :data:`LAYER_RULES` table (name -> role); a role resolves to a
  concrete :class:`WeightPlan` (column / row / replicated + the mesh axes it
  uses) against this plan's mesh.
* **Plan metadata on the weights themselves** — :meth:`ShardingPlan.attach_params`
  stamps each ``DipWeight`` / ``QuantizedDipWeight`` with its
  :class:`WeightPlan` (static pytree aux data), so the decision survives
  ``jit`` / ``scan`` / checkpoint round-trips and ``api.matmul`` can dispatch
  on ``(weight.plan, backend, epilogue)``: the explicit ``shard_map``
  backends (``dip_tp`` / ``dip_sp`` / ``dip_fsdp`` / ``dip_ep``, see
  ``kernels/dip_matmul_sharded.py``) consume it, and a weight with no plan
  decomposes to the implicit GSPMD-on-xla path unchanged.

Mesh convention (unchanged):
    single-pod : (16, 16)      axes ("data", "model")
    multi-pod  : (2, 16, 16)   axes ("pod", "data", "model")

Parallelism mapping:
    batch          -> ("pod", "data")   pure DP across pods (DCN), DP within
                                        a pod (ICI)
    FSDP (ZeRO-3)  -> "data"            params + optimizer moments sharded on
                                        a non-TP dim; all-gathers stay on ICI
    TP             -> "model"           column/row-parallel pairs; MoE
                                        experts (EP) also live on "model"
    SP             -> "model"           sequence sharding for decode KV caches
                                        and archs whose head count does not
                                        divide the TP size

Divisibility fallbacks are *surfaced*: a leaf whose dimension does not
divide the mesh axis replicates (as before) but now warns once with the
leaf name and axis sizes; ``strict=True`` raises instead.  See
``docs/distributed.md``.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Dict, Optional, Set, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.api import DipWeight, QuantizedDipWeight

__all__ = [
    "WeightPlan",
    "LAYER_RULES",
    "ShardingPlan",
    "make_plan",
    "make_mesh",
    "make_production_mesh",
    "make_local_mesh",
    "STRATEGIES",
]

# plan strategies an ArchConfig.sharding field can declare
STRATEGIES = ("gspmd", "tp", "fsdp", "sp", "ep", "pp")


# --------------------------------------------------------------------------
# mesh construction
def make_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``: the plans place arrays with
    sharding constraints and hand-written ``shard_map`` bodies, and leave
    the rest to GSPMD propagation rather than to sharding-in-types."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Target topology: one v5e pod slice of 256 chips (16x16), or two pods.

    Axes: "data" carries DP+FSDP, "model" carries TP/EP/SP; "pod" (multi-pod)
    carries pure DP across the DCN link.  Kept as a function (never a
    module-level constant) so importing this module never touches jax device
    state.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1, stage: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (tests / CPU examples).

    ``stage > 1`` prepends a pipeline "stage" axis (GPipe microbatching via
    ``distributed.pipeline``); the 2-axis shape is kept when absent so
    existing checkpoint manifests round-trip unchanged."""
    if stage > 1:
        return make_mesh((stage, data, model), ("stage", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


# --------------------------------------------------------------------------
# per-weight partition decisions
@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class WeightPlan:
    """One weight's partition decision, carried as static pytree metadata.

    ``kind`` is the tensor-parallel role of the 2-D (d_in, d_out) storage:

        column      d_out sharded over ``axis``  (wq/wk/wv/w_gate/w_up/...)
        row         d_in  sharded over ``axis``  (wo/w_down/out_proj/...)
        replicated  no TP sharding
        expert      MoE expert banks: the EXPERT dim sharded over ``axis``;
                    ``models.moe.moe_ffn`` keys its all-to-all token
                    dispatch/combine off this kind (expert parallelism)

    ``fsdp`` names the ZeRO-3 axis the complementary dim (and the ``dip_fsdp``
    backend's K split) shards over.  ``mesh`` is the mesh the decision was
    made against — hashable, so the whole object rides as jit-static aux data
    on ``DipWeight`` / ``QuantizedDipWeight`` and survives ``jit`` / ``scan``
    / ``grad``; checkpoints serialize :meth:`describe` (devices excluded) and
    restore validates it against the live mesh.
    """

    kind: str = "replicated"
    axis: Optional[str] = None
    fsdp: Optional[str] = None
    mesh: Optional[Mesh] = None

    def __post_init__(self):
        if self.kind not in ("column", "row", "replicated", "expert"):
            raise ValueError(
                f"WeightPlan.kind must be column | row | replicated | "
                f"expert, got {self.kind!r}"
            )

    def axis_size(self, name: Optional[str]) -> int:
        if name is None or self.mesh is None or name not in self.mesh.shape:
            return 1
        return int(self.mesh.shape[name])

    @property
    def tp_size(self) -> int:
        return self.axis_size(self.axis)

    @property
    def fsdp_size(self) -> int:
        return self.axis_size(self.fsdp)

    def describe(self) -> Dict[str, Any]:
        """JSON-safe manifest form (mesh reduced to its axis sizes)."""
        return {
            "kind": self.kind,
            "axis": self.axis,
            "fsdp": self.fsdp,
            "mesh_axes": (
                {str(k): int(v) for k, v in self.mesh.shape.items()}
                if self.mesh is not None else None
            ),
        }

    def __repr__(self) -> str:  # keep DipWeight reprs readable
        parts = [self.kind]
        if self.axis:
            parts.append(f"axis={self.axis}:{self.tp_size}")
        if self.fsdp:
            parts.append(f"fsdp={self.fsdp}:{self.fsdp_size}")
        return f"WeightPlan({', '.join(parts)})"


# Declarative per-layer rules: template leaf name -> partition role.  This
# table IS the old ``ShardingPolicy.param_pspec`` name ladder, lifted into
# data; :meth:`ShardingPlan.param_pspec` interprets a role against the mesh.
# ``w_gate``/``w_up``/``w_down`` with a 4-D (stacked expert-bank) shape
# resolve to "expert_bank" regardless of this table.
LAYER_RULES: Dict[str, str] = {
    # non-stacked globals
    "embed": "embed",
    "lm_head": "lm_head",
    "final_norm": "replicated",
    # column-parallel projections (d_out over TP, d_in over FSDP)
    "wq": "column", "wk": "column", "wv": "column",
    "w_gate": "column", "w_up": "column",
    "in_proj": "column", "w_dkv": "column", "w_krope": "column",
    "w_uk": "column", "w_uv": "column",
    "shared_w_gate": "column", "shared_w_up": "column",
    # row-parallel projections (d_in over TP, d_out over FSDP)
    "wo": "row", "w_down": "row",
    "out_proj": "row", "shared_w_down": "row",
    # MoE router: FSDP only (tiny, but mirrors the residual stream width)
    "router": "router",
    # biases follow their matmul's output sharding
    "bq": "bias_out", "bk": "bias_out", "bv": "bias_out",
    # SSM per-channel / per-head vectors
    "conv_w": "conv",
    "conv_b": "vector_tp", "norm": "vector_tp",
    "dt_bias": "vector_tp", "A_log": "vector_tp", "D": "vector_tp",
}

_TP_KINDS = {"column": "column", "row": "row"}


def _rule_for(name: Optional[str], shape: Tuple[int, ...]) -> str:
    if name in ("w_gate", "w_up", "w_down") and len(shape) == 4:
        return "expert_bank"
    return LAYER_RULES.get(name, "replicated")


# warn-once registry for divisibility fallbacks (satellite bugfix: the old
# policy replicated mis-sized leaves silently)
_WARNED: Set[Tuple] = set()


def _surface_fallback(leaf: str, dim: int, axis: str, size: int,
                      strict: bool) -> None:
    msg = (
        f"ShardingPlan: leaf {leaf!r} dim {dim} does not divide mesh axis "
        f"{axis!r}={size}; replicating instead of sharding"
    )
    if strict:
        raise ValueError(msg + " (strict=True)")
    key = (leaf, dim, axis, size)
    if key not in _WARNED:
        _WARNED.add(key)
        warnings.warn(msg, UserWarning, stacklevel=3)


# --------------------------------------------------------------------------
@dataclasses.dataclass
class ShardingPlan:
    """Mesh + declarative partition rules + activation constraints, unified.

    The one object the runtime layers thread: models take ``plan=`` (its
    :meth:`constrain` replaces the bare callback), trainers/servers attach it
    to parameters (:meth:`attach_params`), the dry-run lowers against its
    shardings, and checkpoints validate against it on restore.

    ``strategy`` (from ``cfg.sharding``) declares how DiP projections
    execute: ``"gspmd"`` (implicit — XLA partitions the plain dot),
    ``"tp"`` (explicit column/row shard_map kernels via the ``dip_tp``
    backend), ``"fsdp"`` (explicit K-sharded all-gather-on-load via
    ``dip_fsdp``), ``"sp"`` (sequence parallel: ``dip_sp`` ring-streamed
    column loads + reduce_scatter rows), ``"ep"`` (expert parallel: dense
    projections via ``dip_ep`` — same placement as ``dip_tp`` — and MoE
    expert banks dispatched over the model axis with paired all-to-alls,
    keyed off :attr:`expert_plan`), ``"pp"`` (pipeline stages over a
    "stage" mesh axis — GPipe microbatching through
    ``distributed.pipeline``).  ``strict=True`` turns divisibility
    fallbacks into errors.
    """

    mesh: Mesh
    cfg: Any
    mode: str                     # train | prefill | decode
    seq_parallel: bool = True     # Megatron-SP residual-stream sharding
    strict: bool = False          # raise (not warn) on divisibility fallback
    # derived axis groupings
    dp: Tuple[str, ...] = ()      # batch axes
    fsdp: Optional[str] = None    # parameter shard axis
    tp: Optional[str] = None      # tensor/expert axis
    stage: Optional[str] = None   # pipeline stage axis
    stages: int = 1               # pipeline depth (1 = no pipelining)

    def __post_init__(self):
        names = self.mesh.axis_names
        self.dp = tuple(a for a in ("pod", "data") if a in names)
        self.fsdp = "data" if "data" in names else None
        self.tp = "model" if "model" in names else None
        self.stage = "stage" if "stage" in names else None
        self.stages = int(self.mesh.shape[self.stage]) if self.stage else 1
        strategy = self.strategy
        if strategy not in STRATEGIES:
            raise ValueError(
                f"unknown sharding strategy {strategy!r} "
                f"(cfg.sharding); supported: {STRATEGIES}"
            )
        if strategy == "pp" and self.stages < 2:
            raise ValueError(
                "sharding='pp' needs a mesh with a 'stage' axis of size "
                ">= 2 (make_local_mesh(stage=...))"
            )

    # ---------------------------------------------------------- strategy ---
    @property
    def strategy(self) -> str:
        return getattr(self.cfg, "sharding", "gspmd") or "gspmd"

    @property
    def explicit_backend(self) -> Optional[str]:
        """Registered sharded backend this strategy routes DiP projections
        through (None for the implicit GSPMD path; pp stages run whatever
        backend the config names inside each stage)."""
        return {"tp": "dip_tp", "fsdp": "dip_fsdp", "sp": "dip_sp",
                "ep": "dip_ep", "pp": None, "gspmd": None}[self.strategy]

    @property
    def expert_plan(self) -> Optional[WeightPlan]:
        """The ``WeightPlan(kind="expert")`` MoE expert banks dispatch on
        under the ep strategy (expert dim over the model axis); None
        otherwise, which keeps ``moe_ffn`` on its dense-style path."""
        if self.strategy != "ep" or not self.tp:
            return None
        return WeightPlan(kind="expert", axis=self.tp, fsdp=None,
                          mesh=self.mesh)

    # ---------------------------------------------------------- helpers ----
    def _tp_if(self, n: int, leaf: Optional[str] = None) -> Optional[str]:
        return self._axis_if(self.tp, n, leaf)

    def _fsdp_if(self, n: int, leaf: Optional[str] = None) -> Optional[str]:
        return self._axis_if(self.fsdp, n, leaf)

    def _axis_if(self, axis: Optional[str], n: int,
                 leaf: Optional[str]) -> Optional[str]:
        if not axis or axis not in self.mesh.shape:
            return None
        if n % self.mesh.shape[axis] == 0:
            return axis
        # mis-sized: replicate, but SAY so for named param leaves (activation
        # / cache fallbacks are expected steady-state, e.g. ragged heads)
        if leaf is not None:
            _surface_fallback(leaf, n, axis, self.mesh.shape[axis], self.strict)
        return None

    def named(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def dp_for(self, n: int) -> Tuple[str, ...]:
        """Largest prefix of the DP axes whose product divides ``n``
        (batch=1 long-context cells replicate instead of failing)."""
        axes = []
        prod = 1
        for a in self.dp:
            if n % (prod * self.mesh.shape[a]) == 0:
                axes.append(a)
                prod *= self.mesh.shape[a]
        return tuple(axes)

    @property
    def heads_on_tp(self) -> bool:
        """Can attention shard heads over the TP axis (both q and kv)?"""
        cfg = self.cfg
        if not cfg.n_heads or not self.tp:
            return False
        tp = self.mesh.shape[self.tp]
        if self.mode == "decode":
            return cfg.n_kv_heads % tp == 0 and cfg.n_heads % tp == 0
        return cfg.n_heads % tp == 0

    # ------------------------------------------------------------ params ---
    def param_pspec(self, name: str, shape: Tuple[int, ...]) -> P:
        """PartitionSpec for a template leaf, resolved through LAYER_RULES
        (layer-stacked shapes included)."""
        rule = _rule_for(name, shape)
        stacked = rule not in ("embed", "lm_head") and name != "final_norm" \
            and len(shape) >= 1
        lead = (None,) if stacked else ()
        body = shape[1:] if stacked else shape  # strip layer axis

        if rule == "embed":
            return P(self._tp_if(shape[0], name), self._fsdp_if(shape[1], name))
        if rule == "lm_head":
            # vocab over BOTH axes: fully-sharded weight AND no contraction
            # psum (the d dim stays unsharded) — the logits come out already
            # vocab-sharded.  padded_vocab guarantees divisibility.
            combo = tuple(a for a in (self.fsdp, self.tp) if a)
            size = 1
            for a in combo:
                size *= self.mesh.shape[a]
            if combo and shape[1] % size == 0:
                return P(None, combo)
            return P(self._fsdp_if(shape[0], name), self._tp_if(shape[1], name))
        if rule == "expert_bank":   # (L, E, d, ffe) / (L, E, ffe, d)
            return P(*lead, self._tp_if(body[0], name),
                     self._fsdp_if(body[1], name), None)
        if rule == "router":
            return P(*lead, self._fsdp_if(body[0], name), None)
        if rule == "column":
            if len(body) != 2:
                return P(*lead, *([None] * len(body)))
            return P(*lead, self._fsdp_if(body[0], name),
                     self._tp_if(body[1], name))
        if rule == "row":
            if len(body) != 2:
                return P(*lead, *([None] * len(body)))
            return P(*lead, self._tp_if(body[0], name),
                     self._fsdp_if(body[1], name))
        if rule == "bias_out":
            return P(*lead, self._tp_if(body[0], name))
        if rule == "conv":
            return P(*lead, None, self._tp_if(body[1], name))
        if rule == "vector_tp":
            return P(*lead, self._tp_if(body[0], name))
        # norms and anything unknown: replicated (layer-stacked)
        return P(*lead, *([None] * len(body)))

    def weight_plan(self, name: str, storage_shape: Tuple[int, ...],
                    perm_tile: int) -> WeightPlan:
        """The :class:`WeightPlan` a DiP-stored linear should carry.

        The explicit backends shard *storage* dims (Kp / Np, padded to the
        permutation-tile grid), so the decision is checked against those:
        the sharded dim must divide the axis AND leave perm-tile-aligned
        shards (each shard must itself be valid permutated storage).  A
        mis-sized dim degrades to ``replicated`` — warned once, or raised
        under ``strict``.
        """
        rule = _rule_for(name, storage_shape)
        # lm_head is column-parallel for the explicit backends (vocab is its
        # N dim); the GSPMD pspec keeps the richer vocab-over-both-axes rule
        kind = _TP_KINDS.get(rule, "column" if rule == "lm_head" else "replicated")
        kp, np_ = int(storage_shape[-2]), int(storage_shape[-1])
        if kind != "replicated" and self.tp:
            tp = self.mesh.shape[self.tp]
            dim = np_ if kind == "column" else kp
            if dim % tp != 0 or (dim // tp) % perm_tile != 0:
                _surface_fallback(name, dim, self.tp, tp, self.strict)
                kind = "replicated"
        fsdp = self.fsdp
        if fsdp and kp % self.mesh.shape[fsdp] != 0:
            _surface_fallback(name, kp, fsdp, self.mesh.shape[fsdp], self.strict)
            fsdp = None
        return WeightPlan(
            kind=kind,
            axis=self.tp if kind != "replicated" else None,
            fsdp=fsdp,
            mesh=self.mesh,
        )

    def attach_params(self, tree: Any) -> Any:
        """Stamp every ``DipWeight`` / ``QuantizedDipWeight`` node with its
        :class:`WeightPlan` (payloads untouched — works on params, specs, or
        shardings).  Run once at init / checkpoint load; the metadata then
        rides through jit/scan/checkpoint, and ``api.matmul`` dispatches the
        explicit sharded backends off it."""
        dip_types = (DipWeight, QuantizedDipWeight)

        def walk(t, name=None):
            if isinstance(t, dict):
                return {k: walk(v, k) for k, v in t.items()}
            if isinstance(t, dip_types):
                return t.with_plan(
                    self.weight_plan(name, tuple(t.data.shape), t.perm_tile)
                )
            return t

        return walk(tree)

    def param_shardings(self, template: Dict[str, Any]) -> Dict[str, Any]:
        """NamedSharding pytree matching repro.models.transformer.param_template.

        Accepts the template (tuple leaves, DiP linears carrying a
        ``dip_meta`` 4th element), materialized params, or spec pytrees.
        ``DipWeight`` nodes come back as ``DipWeight``-wrapped shardings with
        identical metadata (the attached :class:`WeightPlan` included), so
        ``tree_map(device_put, params, shardings)`` traverses both trees in
        lockstep.  The DiP permutation is tile-local (64x64), so the storage
        dims shard exactly like natural dims.
        """

        def walk(t, name=None):
            if isinstance(t, dict):
                return {k: walk(v, k) for k, v in t.items()}
            if isinstance(t, QuantizedDipWeight):
                spec = self.param_pspec(name, tuple(t.data.shape))
                # per-output-channel scales follow the storage's N sharding;
                # the broadcast K dim (width 1) stays unsharded
                scale_spec = P(*spec[:-2], None, spec[-1])
                return t.with_data(self.named(spec), self.named(scale_spec),
                                   checksum=self._checksum_shardings(t))
            if isinstance(t, DipWeight):
                return t.with_data(
                    self.named(self.param_pspec(name, tuple(t.data.shape))),
                    checksum=self._checksum_shardings(t),
                )
            if isinstance(t, tuple):
                shape = t[0]
                dip = t[3] if len(t) > 3 else None
                ns = self.named(self.param_pspec(name, tuple(shape)))
                return DipWeight(ns, *dip) if dip is not None else ns
            return self.named(self.param_pspec(name, tuple(t.shape)))

        return walk(template)

    def _checksum_shardings(self, w):
        """Replicated shardings matching an attached ABFT checksum child (its
        vectors are O(K)+O(N) — not worth sharding) so checksum-carrying
        weights traverse ``tree_map(device_put, params, shardings)`` in
        lockstep; ``None`` stays ``None``."""
        if getattr(w, "checksum", None) is None:
            return None
        return jax.tree_util.tree_map(lambda _: self.named(P()), w.checksum)

    # ------------------------------------------------------------- batch ---
    def batch_pspec(self) -> Dict[str, P]:
        dp = P(self.dp) if self.dp else P()
        return {
            "tokens": P(self.dp, None),
            "labels": P(self.dp, None),
            "embeddings": P(self.dp, None, None),
            "_dp": dp,
        }

    # ------------------------------------------------------------- cache ---
    def cache_pspec(self, name: str, shape: Tuple[int, ...]) -> P:
        """KV/SSM cache leaves (layer-stacked: leading n_layers axis)."""
        bspec = self.dp_for(shape[1]) or None  # batch dim follows the layer axis

        if name in ("k", "v"):  # (L, B, S, KV, hd)
            if self.heads_on_tp:
                return P(None, bspec, None, self.tp, None)
            # sequence-parallel cache (flash-decode): shard the seq dim
            return P(None, bspec, self._tp_if(shape[2]), None, None)
        if name in ("c_kv", "k_rope"):  # (L, B, S, r)
            return P(None, bspec, self._tp_if(shape[2]), None)
        if name == "state":  # (L, B, H, P, N)
            return P(None, bspec, self._tp_if(shape[2]), None, None)
        if name == "conv":  # (L, B, K-1, conv_dim)
            return P(None, bspec, None, self._tp_if(shape[3]))
        return P(*([None] * len(shape)))

    def cache_shardings(self, cache_shapes: Dict[str, Any]) -> Dict[str, Any]:
        def walk(t, name=None):
            if isinstance(t, dict):
                return {k: walk(v, k) for k, v in t.items()}
            return self.named(self.cache_pspec(name, tuple(t.shape)))

        return walk(cache_shapes)

    def paged_cache_pspec(self, name: str, shape: Tuple[int, ...]) -> P:
        """Paged serving-cache leaves (``transformer.init_paged_cache``).

        Pools are (L, num_blocks, block_size, ...): the block and in-block
        token dims are *addresses*, never sharded — each device holds every
        block's rows for its head shard, so a decode step's gather is purely
        local.  KV heads shard over TP exactly like the contiguous decode
        cache when divisible; the MLA latent (no head axis) and the
        per-slot SSM pools follow their contiguous rules.
        """
        if name in ("k", "v"):            # (L, nb, bs, KV, hd)
            if self.heads_on_tp:
                return P(None, None, None, self.tp, None)
            return P(*([None] * len(shape)))
        if name in ("k_scale", "v_scale"):  # (L, nb, bs, KV)
            if self.heads_on_tp:
                return P(None, None, None, self.tp)
            return P(*([None] * len(shape)))
        if name == "state":               # (L, slots, H, P, N)
            return P(None, None, self._tp_if(shape[2]), None, None)
        if name == "conv":                # (L, slots, K-1, conv_dim)
            return P(None, None, None, self._tp_if(shape[3]))
        # c_kv / k_rope latents and their scales: replicated (rank is small
        # and the absorbed einsums want the full latent per device)
        return P(*([None] * len(shape)))

    def paged_cache_shardings(self, cache_shapes: Dict[str, Any]) -> Dict[str, Any]:
        def walk(t, name=None):
            if isinstance(t, dict):
                return {k: walk(v, k) for k, v in t.items()}
            return self.named(self.paged_cache_pspec(name, tuple(t.shape)))

        return walk(cache_shapes)

    # -------------------------------------------------------- activations --
    def constrain(self, x: jax.Array, tag: str) -> jax.Array:
        mesh = self.mesh
        if mesh.empty or not self.dp:
            return x
        tp = self.tp
        dp = self.dp_for(x.shape[0]) or None
        try:
            if tag == "act_btd":
                # Megatron-style sequence parallelism: the residual stream
                # (saved per scanned layer for backward) is sharded along seq
                # over the TP axis in train/prefill — 16x less live activation
                # memory; GSPMD inserts the all-gather at each projection.
                if self.seq_parallel and self.mode != "decode" and self._tp_if(x.shape[1]):
                    spec = P(dp, self.tp, None)
                else:
                    spec = P(dp, None, None)
            elif tag == "q_bthd":
                heads = x.shape[2]
                if heads % mesh.shape[tp] == 0:
                    spec = P(dp, None, tp, None)
                else:
                    spec = P(dp, self._tp_if(x.shape[1]), None, None)  # SP fallback
            elif tag == "kv_bthd":
                heads = x.shape[2]
                if heads % mesh.shape[tp] == 0:
                    spec = P(dp, None, tp, None)
                else:
                    # small kv tensors replicate over TP; the broadcast-to-h
                    # expansion in attention_core re-shards them on the head
                    # axis locally (no collective)
                    spec = P(dp, None, None, None)
            elif tag == "cache_bshd":
                if self.heads_on_tp:
                    spec = P(dp, None, tp, None)
                else:
                    spec = P(dp, self._tp_if(x.shape[1]), None, None)
            elif tag == "cache_bsr":
                spec = P(dp, self._tp_if(x.shape[1]), None)
            elif tag == "logits":
                # leave to propagation: the lm_head weight's vocab sharding
                # (data x model) determines the cheapest logits layout, and
                # the loss reduction is sharding-agnostic
                return x
            elif tag == "ffn_hidden":
                spec = P(dp, None, self._tp_if(x.shape[-1]))
            elif tag in ("expert_buf", "expert_hidden"):
                # (B, E, C, d/ffe): groups over DP, experts over TP
                spec = P(dp, self._tp_if(x.shape[1]), None, None)
            elif tag == "ssm_inner":
                spec = P(dp, None, self._tp_if(x.shape[-1]))
            elif tag == "scores":
                # (b, h, sq, sk): shard heads when divisible, else q-positions
                h = x.shape[1]
                if h % mesh.shape[tp] == 0:
                    spec = P(dp, tp, None, None)
                else:
                    spec = P(dp, None, self._tp_if(x.shape[2]), None)
            else:
                return x
        except (KeyError, TypeError):
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def make_plan(mesh: Mesh, cfg, mode: str, **opts) -> ShardingPlan:
    """Build the plan for one (mesh, config, phase) triple.

    ``opts``: ``seq_parallel`` / ``strict`` — see :class:`ShardingPlan`.
    """
    return ShardingPlan(mesh=mesh, cfg=cfg, mode=mode, **opts)
