"""Pipeline parallelism: GPipe-style microbatched execution over a "stage"
mesh axis using shard_map + collective_permute, with the boundary transfer
overlapped against the stage compute.

Plan-integrated: ``ShardingPlan(strategy="pp")`` carries a ``stage`` mesh
axis (``plan.stages`` devices) and :func:`pipeline_train_step_fn` builds a
drop-in replacement for ``transformer.train_step_fn`` that runs the layer
stack through :func:`pipeline_apply`.  ``runtime.Trainer`` selects it
automatically whenever ``plan.stages > 1``.

Schedule — overlapped GPipe.  The classic loop computes a microbatch and
*then* sends it, serialising the boundary transfer behind the stage compute.
Here every tick issues the ``ppermute`` of the PREVIOUS tick's output FIRST,
before the stage compute that the transfer does not depend on — so the
send/recv streams while the MXU-bound stage body runs, the same
communication-hiding argument the DiP paper makes for eliminating FIFO
stalls inside the array (docs/architecture.md).  The price is one extra
tick of latency per hop: stage ``s`` computes microbatch ``m`` at tick
``m + 2s`` (vs ``m + s`` unoverlapped), so with S stages and M microbatches
the loop runs ``M + 2(S-1)`` ticks and the bubble fraction is
``2(S-1)/(M + 2(S-1))`` — bandwidth-free ticks traded for zero exposed
transfer time on every productive tick.

The tick loop is a ``lax.scan`` (not ``fori_loop``) so the whole pipeline is
reverse-differentiable: training backprops through the scan, and each
``ppermute`` transposes to the reverse-ring ppermute, which gives the
backward pass the same overlapped boundary-transfer structure for free.

Numerics: every stage applies ``stage_fn`` exactly once per microbatch to
exactly the activation its upstream stage produced — inactive (bubble)
ticks compute on zeros and are masked out — so the output is bit-identical
to sequential application of the stages (asserted in
tests/test_multidevice.py).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.common import shard_map

__all__ = ["pipeline_apply", "pipeline_train_step_fn"]


def pipeline_apply(
    mesh: Mesh,
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,            # pytree, leaves with leading axis = n_stages
    x: jax.Array,                 # (microbatches, mb_size, ...) microbatched input
    *,
    axis: str = "stage",
) -> jax.Array:
    """Run ``x`` through ``n_stages`` sequential stages, pipelined.

    ``stage_fn(params_for_stage, activation) -> activation`` must be
    shape-preserving (standard transformer-block stack semantics).
    Returns the final activations, microbatch-major, bit-identical to
    sequential application of all stages.

    Each tick's jaxpr opens with the ``ppermute`` that forwards the previous
    tick's output — issued before the stage compute so the transfer overlaps
    it (the compute reads the *prior* tick's arrival, never this tick's).
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    perm_fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def per_stage(params, xs):
        # params: this stage's slice (leading axis 1) ; xs: (n_micro, mb, ...)
        params = jax.tree_util.tree_map(lambda t: t[0], params)
        stage_id = jax.lax.axis_index(axis)
        n_ticks = n_micro + 2 * (n_stages - 1)

        def tick(carry, t):
            recv, send, outs = carry
            # boundary transfer FIRST: forwards last tick's output, which
            # nothing below depends on — the ring hop streams under the
            # stage compute and its result is consumed only next tick
            arrived = jax.lax.ppermute(send, axis, perm_fwd)
            # stage 0 ingests microbatch t from its local input copy; every
            # other stage reads what arrived during the PREVIOUS tick
            inject = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False
            )
            cur = jnp.where(stage_id == 0, inject, recv)
            mb_idx = t - 2 * stage_id     # 2 ticks/hop: compute + in-flight
            active = (mb_idx >= 0) & (mb_idx < n_micro)
            # bubble ticks compute on zeros, not stale ring garbage: keeps
            # every masked-out value finite in forward AND backward
            cur = jnp.where(active, cur, jnp.zeros_like(cur))
            y = stage_fn(params, cur)
            y = jnp.where(active, y, cur)
            # last stage records its finished microbatch (unconditional
            # read-modify-write keeps the scan transpose simple)
            idx = jnp.clip(mb_idx, 0, n_micro - 1)
            prev = jax.lax.dynamic_index_in_dim(outs, idx, 0, keepdims=False)
            rec = jnp.where(active & (stage_id == n_stages - 1), y, prev)
            outs = jax.lax.dynamic_update_index_in_dim(outs, rec, idx, 0)
            return (arrived, y, outs), None

        zero = jnp.zeros_like(xs[0])
        (_, _, outs), _ = jax.lax.scan(
            tick,
            (zero, zero, jnp.zeros_like(xs)),
            jnp.arange(n_ticks, dtype=jnp.int32),
        )
        # broadcast the last stage's finished outputs to every stage so the
        # out_spec can be replicated over the axis (masked psum = broadcast)
        outs = jax.lax.psum(
            jnp.where(stage_id == n_stages - 1, outs, jnp.zeros_like(outs)), axis
        )
        return outs

    pspec = P(axis)
    rep = P(*([None] * x.ndim))
    fn = shard_map(
        per_stage,
        mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: pspec, stage_params), rep),
        out_specs=rep,
    )
    return fn(stage_params, x)


def pipeline_train_step_fn(cfg, optimizer, plan, *, n_micro: int,
                           guard: bool = False):
    """Pipelined ``step(state, batch) -> (state, metrics)`` — the
    ``transformer.train_step_fn`` contract with the layer stack executed
    through :func:`pipeline_apply` over ``plan``'s stage axis.

    The embedding lookup, final norm and lm_head + cross-entropy stay
    replicated outside the stage loop (they are a sliver of the FLOPs); the
    ``n_layers`` blocks are regrouped ``L -> (stages, L/stages)`` and each
    stage scans its contiguous slice.  Covers the dense attention+FFN scan
    families; MoE stacks pipeline their dense projections but dispatch
    experts with ``dip_ep`` (mixing both axes is out of scope), and SSM
    stacks carry recurrent state that would have to thread the ring.
    """
    if getattr(plan, "stages", 1) < 2 or plan.mesh is None or plan.stage is None:
        raise ValueError(
            "pipeline_train_step_fn needs a plan with a stage axis of >= 2 "
            "devices (ShardingPlan(strategy='pp', ...))"
        )
    if cfg.ssm_state or cfg.is_moe:
        raise ValueError(
            f"{cfg.name}: pipeline stages cover the dense attention+FFN scan "
            "families (SSM state / MoE dispatch do not thread the stage ring)"
        )
    n_stages, axis, mesh = plan.stages, plan.stage, plan.mesh
    if cfg.n_layers % n_stages:
        raise ValueError(
            f"n_layers={cfg.n_layers} does not divide into {n_stages} stages"
        )
    per_stage = cfg.n_layers // n_stages

    # lazy: models.transformer imports nothing from distributed.pipeline, but
    # keeping the import inside the factory makes the no-cycle claim local
    from repro.models import layers, transformer as tf_model

    cd = jnp.dtype(cfg.compute_dtype)

    def stage_fn(sp, a):
        # positions/RoPE rebuilt per stage from the (static) microbatch
        # shape: a scan constant per stage, and no closure over tracers
        # crosses the shard_map boundary
        s = a.shape[1]
        positions = jnp.arange(s, dtype=jnp.int32)
        rope_dim = cfg.qk_rope_head_dim if cfg.use_mla else cfg.resolved_head_dim
        rope = layers.rope_tables(positions, rope_dim, cfg.rope_theta)

        def blk(h, lp):
            h, _, _ = tf_model._transformer_block(
                h, lp, cfg, positions=positions, rope=rope, cache=None,
                kv_chunk=0, constrain=lambda v, _name: v, plan=None,
            )
            return h, None

        h, _ = jax.lax.scan(blk, a, sp)
        return h

    def loss(params, batch):
        tokens = batch["tokens"]
        b, s = tokens.shape
        if b % n_micro:
            raise ValueError(f"batch={b} does not divide into {n_micro} "
                             "pipeline microbatches")
        x = params["embed"].astype(cd)[tokens]
        xm = x.reshape((n_micro, b // n_micro) + x.shape[1:])
        sp = jax.tree_util.tree_map(
            lambda t: t.reshape((n_stages, per_stage) + t.shape[1:]),
            params["layers"],
        )
        h = pipeline_apply(mesh, stage_fn, sp, xm, axis=axis)
        h = h.reshape((b,) + h.shape[2:])
        h = layers.rms_norm(h, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        if cfg.tie_embeddings:
            logits = jnp.matmul(
                h, head.astype(cd), preferred_element_type=jnp.float32
            ).astype(jnp.float32)
        else:
            logits = layers.linear(
                h, head, backend=cfg.matmul_backend, compute_dtype=cd,
            ).astype(jnp.float32)
        if cfg.padded_vocab != cfg.vocab_size:
            lane = jax.lax.broadcasted_iota(
                jnp.int32, logits.shape, logits.ndim - 1
            )
            logits = jnp.where(lane < cfg.vocab_size, logits, -1e30)
        mask = batch.get("loss_mask")
        shift_mask = None if mask is None else mask[:, 1:]
        return layers.cross_entropy_loss(
            logits[:, :-1], batch["labels"][:, 1:], mask=shift_mask
        )

    def step(state, batch):
        params, opt_state, step_no = (
            state["params"], state["opt_state"], state["step"]
        )
        loss_v, grads = jax.value_and_grad(lambda p: loss(p, batch))(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(lambda p, u: p + u, params, updates)
        gnorm = optimizer.last_grad_norm(opt_state)
        new_state = {"params": params, "opt_state": opt_state,
                     "step": step_no + 1}
        return new_state, {"loss": loss_v, "grad_norm": gnorm,
                           "step": step_no + 1}

    if guard:
        from repro.reliability import guard as guard_lib  # lazy: no cycle

        return guard_lib.guarded_step_fn(step)
    return step
