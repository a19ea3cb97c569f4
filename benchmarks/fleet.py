"""Scenario fleet: every zoo config x backend x sharding, end-to-end, measured.

The paper's evaluation (Sec. V) is a workload *matrix* — DiP swept across
transformer shapes against baselines — and this driver is the repro's
equivalent regression net.  For each cell (arch, matmul backend, sharding)
it runs the three serving-stack stages at reduced dims:

* **train**   — one ``train_step_fn`` step (AdamW), loss must be finite;
* **prefill** — two chunked-prefill forward calls through ``decode_step_fn``
  against a contiguous cache (the engine's prefill path);
* **decode**  — one ``paged_decode_step_fn`` step against a ``PagedKVCache``
  with populated block tables (the engine's steady-state path);

and records, per stage, structural evidence straight from the jaxpr —
``pallas_call`` launch count, collective counts (psum / all_gather /
all_to_all / ppermute / reduce_scatter via
``kernels.dip_matmul_sharded.count_collectives``), a peak-live-bytes
estimate from a top-level liveness walk — plus wall time and pass/fail.
Explicitly sharded cells additionally run a **column probe**: one
column-parallel projection dispatch whose collective counts pin the paper's
placement contract (``dip_tp`` columns: ZERO collectives; ``dip_fsdp``:
exactly one all_gather, no psum; ``dip_sp``: the gather moved INSIDE the
kernel's load stage — ppermutes only; ``dip_ep``: collective-free, the MoE
all_to_alls live in ``models.moe`` and are counted per stage).  ``pp``
cells run the pipelined train step (prefill/decode record skipped — the
stage axis is a training schedule).

The output is schema-validated ``BENCH_fleet.json``.  The committed copy is
the baseline: :func:`validate_fleet_json` enforces the intra-document
contracts and :func:`diff_fleet_json` rejects regressions against it (launch
counts may not grow, collective counts may not grow, cells may not vanish,
previously-passing stages may not fail).  CI's ``fleet`` job re-runs the
tiny matrix and diffs; refresh the baseline with::

    PYTHONPATH=src python benchmarks/fleet.py --tiny --out BENCH_fleet.json

Quantized backends (``dip_int8w`` / ``dip_fp8``) are inference-only (the
trainer rejects them); their train stage records as skipped, never failed.
Sharded cells (``tp`` / ``fsdp`` / ``sp`` / ``ep`` / ``pp``) re-exec onto
forced host devices when the current topology is single-device, mirroring
``kernels_bench --sharded``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

FLEET_SCHEMA_VERSION = 1
DEFAULT_JSON = pathlib.Path(__file__).resolve().parent.parent / "BENCH_fleet.json"

BACKENDS = ("xla", "pallas_dip", "dip_int8w", "dip_fp8")
SHARDINGS = ("gspmd", "tp", "fsdp", "sp", "ep", "pp")
STAGES = ("train", "prefill", "decode")
COLLECTIVES = ("psum", "all_gather", "all_to_all", "ppermute",
               "reduce_scatter")

# Quantization scheme each backend-axis value implies ("none" = float).
QUANT_FOR_BACKEND = {"xla": "none", "pallas_dip": "none",
                     "dip_int8w": "int8", "dip_fp8": "fp8_e4m3"}
# DiP-layout backends swap to the explicit sharded kernels under tp/fsdp/
# sp/ep (the registry dispatches off the weight's attached plan); xla stays
# xla and lets GSPMD place the collectives; pp keeps the config's backend
# inside each stage (the stage axis is orthogonal to the matmul dispatch).
SHARDED_EFFECTIVE = {"tp": "dip_tp", "fsdp": "dip_fsdp",
                     "sp": "dip_sp", "ep": "dip_ep"}

# Reduced stage dims — one compiled shape per stage across the whole fleet.
DIMS = {
    "train_batch": 2, "train_seq": 16,
    "prefill_chunk": 8, "prefill_len": 16,
    "slots": 4, "block_size": 4, "max_seq": 16, "decode_ctx": 3,
}


# ---------------------------------------------------------------------------
# matrix definitions
def full_cells(archs: Sequence[str]) -> List[Tuple[str, str, str]]:
    return [(a, b, s) for a in archs for b in BACKENDS for s in SHARDINGS]


def tiny_cells(archs: Sequence[str]) -> List[Tuple[str, str, str]]:
    """The committed-baseline matrix: every arch covers all three stages on
    the replicated float backends, quantized and sharded columns sample the
    families whose layouts differ (dense / MLA+MoE / hybrid-SSM)."""
    cells: List[Tuple[str, str, str]] = []
    for a in archs:
        cells += [(a, "xla", "gspmd"), (a, "pallas_dip", "gspmd"),
                  (a, "dip_int8w", "gspmd")]
    for a in ("llama3_8b", "deepseek_v2_lite_16b"):
        if a in archs:
            cells.append((a, "dip_fp8", "gspmd"))
    for a in ("llama3_8b", "deepseek_v2_lite_16b", "zamba2_2_7b"):
        if a in archs:
            cells.append((a, "pallas_dip", "tp"))
    for a in ("llama3_8b", "zamba2_2_7b"):
        if a in archs:
            cells.append((a, "pallas_dip", "fsdp"))
    # sequence-parallel: the dense family plus the hybrid-SSM layout (same
    # pair as fsdp — the schedules differ, the coverage question does not)
    for a in ("llama3_8b", "zamba2_2_7b"):
        if a in archs:
            cells.append((a, "pallas_dip", "sp"))
    # expert-parallel: both MoE families (with and without shared experts)
    for a in ("qwen3_moe_235b_a22b", "deepseek_v2_lite_16b"):
        if a in archs:
            cells.append((a, "pallas_dip", "ep"))
    # pipeline stages: the dense scan family (see pipeline_train_step_fn)
    if "llama3_8b" in archs:
        cells.append(("llama3_8b", "pallas_dip", "pp"))
    if "llama3_8b" in archs:
        cells.append(("llama3_8b", "xla", "tp"))
    return cells


# ---------------------------------------------------------------------------
# peak-live-bytes: top-level jaxpr liveness walk
def estimate_peak_live_bytes(fn, *args) -> int:
    """Upper-bound live bytes from the top-level jaxpr: walk equations in
    program order, birth outvars, kill values past their last use.  Sub-jaxpr
    internals (scan carries, pallas scratch) are not expanded — their results
    surface as top-level outvars — so this is an *estimate* tracking the
    dominant residents (params, optimizer state, caches, batch activations),
    which is what the fleet baseline wants to catch drifting."""
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    jaxpr = closed.jaxpr

    def nbytes(v) -> int:
        aval = getattr(v, "aval", None)
        shape = getattr(aval, "shape", None)
        dtype = getattr(aval, "dtype", None)
        if shape is None or dtype is None:
            return 0
        try:
            itemsize = np.dtype(dtype).itemsize
        except TypeError:          # float0 tangents and friends
            return 0
        return int(np.prod(shape, dtype=np.int64)) * itemsize

    last_use: Dict[Any, int] = {}
    n = len(jaxpr.eqns)
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if hasattr(v, "aval"):
                last_use[v] = i
    for v in jaxpr.outvars:
        if hasattr(v, "aval"):
            last_use[v] = n

    live: Dict[Any, int] = {}
    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        if last_use.get(v, -1) >= 0:
            live[v] = nbytes(v)
    peak = sum(live.values())
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.outvars:
            live[v] = nbytes(v)
        peak = max(peak, sum(live.values()))
        for v in list(eqn.invars) + list(eqn.outvars):
            if v in live and last_use.get(v, -1) <= i:
                del live[v]
    return int(peak)


# ---------------------------------------------------------------------------
# cell construction
def cell_config(arch: str, backend: str, sharding: str):
    """Resolve one matrix cell to (cfg, effective_backend, quant, mesh_axes).

    ``backend`` is the matrix-axis name; the *effective* backend is what the
    registry actually dispatches (``pallas_dip`` under ``tp`` runs as
    ``dip_tp`` etc.).  Sharded cells pin float32 compute: forced host devices
    have no native bf16 and the fleet compares counts, not flops.
    """
    from repro.configs import get_config

    quant = QUANT_FOR_BACKEND[backend]
    effective = backend
    overrides: Dict[str, Any] = {"quantization": quant}
    mesh_axes: Optional[Dict[str, int]] = None
    if sharding == "gspmd":
        overrides["matmul_backend"] = backend
    else:
        if backend != "xla":
            effective = SHARDED_EFFECTIVE.get(sharding, backend)
        overrides["matmul_backend"] = effective
        overrides["compute_dtype"] = "float32"
        if sharding in ("sp", "ep", "pp"):
            # the plan's strategy drives expert_plan / stage selection; the
            # legacy tp/fsdp cells predate cfg.sharding threading and keep
            # their arch default (the registry dispatches off the backend)
            overrides["sharding"] = sharding
        if sharding == "fsdp":
            mesh_axes = {"data": 2, "model": 1}
        elif sharding == "pp":
            mesh_axes = {"stage": 2, "data": 1, "model": 1}
        else:
            mesh_axes = {"data": 1, "model": 2}
    cfg = get_config(arch).reduced(**overrides)
    return cfg, effective, quant, mesh_axes


def _make_mesh(mesh_axes: Optional[Dict[str, int]]):
    if mesh_axes is None:
        return None
    from repro.distributed.plan import make_local_mesh

    return make_local_mesh(data=mesh_axes["data"], model=mesh_axes["model"],
                           stage=mesh_axes.get("stage", 1))


def _make_params(cfg, plan):
    import jax
    from repro.models import transformer as tf_model

    params = tf_model.init_params(jax.random.PRNGKey(0), cfg)
    if plan is not None:
        # place first (the shardings tree carries plan-free nodes, so the
        # treedefs match), then stamp the WeightPlans for explicit dispatch
        pshard = plan.param_shardings(tf_model.param_template(cfg))
        params = jax.tree_util.tree_map(jax.device_put, params, pshard)
        params = plan.attach_params(params)
    return params


def _stage_record(wall_us: float, counts: Dict[str, int], peak: int) -> Dict[str, Any]:
    return {
        "status": "ok",
        "wall_us": round(float(wall_us), 1),
        "pallas_calls": int(counts.get("pallas_call", 0)),
        "collectives": {k: int(counts.get(k, 0)) for k in COLLECTIVES},
        "peak_live_bytes": int(peak),
    }


def _timed(step, *args, iters: int = 1):
    import jax

    out = step(*args)                       # compile + warm
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = step(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) * 1e6 / iters, out


# ---------------------------------------------------------------------------
# stage runners
def _run_train(cfg, params, plan, iters: int) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from repro.kernels.dip_matmul_sharded import count_collectives
    from repro.models import transformer as tf_model
    from repro.optim import AdamW

    opt = AdamW(lr=1e-3)
    if plan is not None and getattr(plan, "stages", 1) > 1:
        # stage axis in the plan: the trainer's pipelined step (GPipe
        # microbatching, boundary ppermutes overlapped with stage compute)
        from repro.distributed import pipeline as pp_lib

        step = jax.jit(pp_lib.pipeline_train_step_fn(
            cfg, opt, plan, n_micro=DIMS["train_batch"]))
    else:
        step = jax.jit(tf_model.train_step_fn(cfg, opt, plan=plan))
    state = {"params": params, "opt_state": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(
        rng.integers(1, cfg.vocab_size,
                     size=(DIMS["train_batch"], DIMS["train_seq"])), jnp.int32)
    batch = {"tokens": tokens, "labels": tokens}
    counts = count_collectives(step, state, batch)
    peak = estimate_peak_live_bytes(step, state, batch)
    wall, (_, metrics) = _timed(step, state, batch, iters=iters)
    loss = float(metrics["loss"])
    if not np.isfinite(loss):
        raise RuntimeError(f"non-finite train loss: {loss}")
    return _stage_record(wall, counts, peak)


def _run_prefill(cfg, params, plan, iters: int) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from repro.kernels.dip_matmul_sharded import count_collectives
    from repro.models import transformer as tf_model

    chunk, total = DIMS["prefill_chunk"], DIMS["prefill_len"]
    fwd = jax.jit(tf_model.decode_step_fn(cfg, plan=plan))
    cache = tf_model.init_cache(cfg, 1, total)
    rng = np.random.default_rng(1)
    toks = rng.integers(1, cfg.vocab_size, size=(total,)).astype(np.int32)
    c0 = jnp.asarray(toks[:chunk][None])
    counts = count_collectives(fwd, params, cache, c0)
    peak = estimate_peak_live_bytes(fwd, params, cache, c0)

    def both_chunks(cache):
        last = None
        for lo in range(0, total, chunk):
            piece = jnp.asarray(toks[lo:lo + chunk][None])
            last, cache = fwd(params, cache, piece)
        return last

    wall, logits = _timed(both_chunks, cache, iters=iters)
    if not np.isfinite(np.asarray(logits)).all():
        raise RuntimeError("non-finite prefill logits")
    # one chunk call is the engine's unit of work; both_chunks timed two
    return _stage_record(wall / (total // chunk), counts, peak)


def _run_decode(cfg, params, plan, iters: int) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from repro.kernels.dip_matmul_sharded import count_collectives
    from repro.models import transformer as tf_model
    from repro.serving import kv_cache as kvc

    slots, bs, max_seq = DIMS["slots"], DIMS["block_size"], DIMS["max_seq"]
    ctx = DIMS["decode_ctx"]
    kv = kvc.PagedKVCache(
        cfg, num_blocks=slots * (max_seq // bs) + 1, block_size=bs,
        slots=slots, max_seq=max_seq, kv_quant=cfg.kv_quant, plan=plan)
    if not cfg.is_ssm or cfg.is_hybrid:
        for s in range(slots):
            assert kv.ensure(s, ctx + 1)
    step = jax.jit(tf_model.paged_decode_step_fn(cfg, plan=plan))
    rng = np.random.default_rng(2)
    tokens = jnp.asarray(
        rng.integers(1, cfg.vocab_size, size=(slots, 1)), jnp.int32)
    positions = jnp.full((slots,), ctx, jnp.int32)
    tables = jnp.asarray(kv.block_tables)
    counts = count_collectives(step, params, kv.pools, tokens, positions, tables)
    peak = estimate_peak_live_bytes(
        step, params, kv.pools, tokens, positions, tables)
    wall, (logits, _) = _timed(step, params, kv.pools, tokens, positions,
                               tables, iters=iters)
    if not np.isfinite(np.asarray(logits)).all():
        raise RuntimeError("non-finite decode logits")
    return _stage_record(wall, counts, peak)


_STAGE_RUNNERS = {"train": _run_train, "prefill": _run_prefill,
                  "decode": _run_decode}


def _column_probe(cfg, plan) -> Dict[str, Any]:
    """One column-parallel projection dispatch, counted structurally.

    ``dip_tp`` columns keep the output dimension sharded and must launch
    shard-local kernels with no collective at all; ``dip_fsdp`` gathers the
    K-sharded storage exactly once and never psums.  The fleet schema turns
    these counts into hard contracts (see :func:`validate_fleet_json`).
    """
    import jax.numpy as jnp

    from repro import api
    from repro.kernels.dip_matmul_sharded import count_collectives

    d_in, d_out = cfg.d_model, 4 * api.PERM_TILE
    rng = np.random.default_rng(3)
    w = api.DipWeight.from_natural(
        jnp.asarray(rng.normal(size=(d_in, d_out)).astype(np.float32)))
    if cfg.quant_scheme is not None:
        w = api.quant.quantize(w, cfg.quant_scheme)
    w = plan.attach_params({"wq": w})["wq"]
    x = jnp.asarray(rng.normal(size=(8, d_in)).astype(np.float32))
    counts = count_collectives(
        lambda x: api.matmul(x, w, backend=cfg.matmul_backend), x)
    return {"pallas_calls": int(counts.get("pallas_call", 0)),
            "collectives": {k: int(counts.get(k, 0)) for k in COLLECTIVES}}


def _verify_probe(cfg) -> Dict[str, Any]:
    """One verified vs unverified dispatch, counted structurally from the
    jaxpr.  The ABFT audit (repro.reliability; docs/reliability.md) is jnp
    reductions over the existing output and weight checksums — the contract
    the fleet schema enforces is that ``verify=True`` adds ZERO extra
    pallas launches (the <= 1.15x wall-time bound in BENCH_reliability.json
    follows from this structure)."""
    import jax.numpy as jnp

    from repro import api
    from repro.kernels.dip_matmul_sharded import count_collectives
    from repro.reliability import attach_checksums

    d_in, d_out = cfg.d_model, 4 * api.PERM_TILE
    rng = np.random.default_rng(5)
    wn = jnp.asarray(rng.normal(size=(d_in, d_out)).astype(np.float32))
    be = api.get_backend(cfg.matmul_backend)
    if cfg.quant_scheme is not None:
        w = api.quant.quantize(wn, cfg.quant_scheme)
    elif be.layout == "dip":
        w = api.DipWeight.from_natural(wn)
    else:
        w = wn
    w = attach_checksums(w)
    x = jnp.asarray(rng.normal(size=(8, d_in)).astype(np.float32))
    plain = count_collectives(
        lambda x: api.matmul(x, w, backend=cfg.matmul_backend), x)
    def _verified(x):
        out, report = api.matmul(x, w, backend=cfg.matmul_backend,
                                 verify=True)
        # the report's "mode" is a static string — not a JAX type; keeping
        # the array scalars stops the audit from being DCE'd out of the jaxpr
        return out, {k: v for k, v in report.items() if k != "mode"}

    ver = count_collectives(_verified, x)
    pv = int(ver.get("pallas_call", 0))
    pu = int(plain.get("pallas_call", 0))
    return {"pallas_calls_unverified": pu, "pallas_calls_verified": pv,
            "extra_pallas_calls": pv - pu}


# ---------------------------------------------------------------------------
# cell driver
def run_cell(arch: str, backend: str, sharding: str, *,
             iters: int = 1) -> Dict[str, Any]:
    from repro.configs.shapes import stage_matmul_shapes
    from repro.distributed.plan import make_plan
    from repro.models import transformer as tf_model  # noqa: F401 (import check)

    cfg, effective, quant, mesh_axes = cell_config(arch, backend, sharding)
    mesh = _make_mesh(mesh_axes)
    cell: Dict[str, Any] = {
        "arch": arch, "backend": backend, "sharding": sharding,
        "effective_backend": effective, "quantization": quant,
        "stages": {}, "column_probe": None, "verify_probe": None,
        "workload_shapes": {
            k: len(v) for k, v in stage_matmul_shapes(
                cfg, train_tokens=DIMS["train_batch"] * DIMS["train_seq"],
                prefill_tokens=DIMS["prefill_chunk"],
                decode_slots=DIMS["slots"]).items()
        },
    }
    plans = {"train": None, "prefill": None, "decode": None}
    if mesh is not None:
        plans["train"] = make_plan(mesh, cfg, "train")
        decode_plan = make_plan(mesh, cfg, "decode")
        plans["prefill"] = decode_plan
        plans["decode"] = decode_plan

    for stage in STAGES:
        if stage == "train" and quant != "none":
            cell["stages"][stage] = {
                "status": "skipped",
                "reason": f"{quant} weights are inference-only "
                          "(trainer rejects quantized configs)"}
            continue
        if sharding == "pp" and stage != "train":
            cell["stages"][stage] = {
                "status": "skipped",
                "reason": "pipeline stages are a training schedule (GPipe "
                          "microbatching amortizes the bubble over a batch; "
                          "serving runs tp/sp — see docs/distributed.md)"}
            continue
        try:
            params = _make_params(cfg, plans[stage])
            cell["stages"][stage] = _STAGE_RUNNERS[stage](
                cfg, params, plans[stage], iters)
        except Exception as e:                       # noqa: BLE001 — per-cell
            cell["stages"][stage] = {
                "status": "failed",
                "reason": f"{type(e).__name__}: {e}"[:300]}
    if effective in ("dip_tp", "dip_fsdp", "dip_sp", "dip_ep"):
        cell["column_probe"] = _column_probe(cfg, plans["decode"])
    if sharding == "gspmd":
        # the verified-dispatch subset: single-device cells cover every
        # backend family without re-exec; sharded verify rides the same
        # wrapper and is structurally identical per shard
        try:
            cell["verify_probe"] = _verify_probe(cfg)
        except Exception as e:                       # noqa: BLE001 — per-cell
            cell["verify_probe"] = {
                "status": "failed",
                "reason": f"{type(e).__name__}: {e}"[:300]}
    return cell


# ---------------------------------------------------------------------------
# schema validation + baseline diff (the acceptance contracts)
def _fail(msgs: List[str]):
    raise ValueError("invalid fleet document:\n  " + "\n  ".join(msgs))


def validate_fleet_json(payload: Dict[str, Any]) -> None:
    """Structural schema plus the intra-document contracts.

    * every cell carries all three stage records; ok-stages carry positive
      wall time, non-negative launch/collective counts, positive peak bytes;
    * ``dip_tp`` cells: column probe shows ZERO collectives, and the decode
      stage issues no all_gather (columns stay sharded, rows psum);
    * ``dip_fsdp`` cells: column probe shows exactly one all_gather and no
      psum;
    * ``dip_sp`` cells: the column probe gathers INSIDE the kernel's load
      stage — at least one ppermute, and zero psum / all_gather /
      all_to_all / reduce_scatter; the forward stages (prefill/decode) may
      not all_gather at all (the sequence-parallel overlap contract: the
      pre-kernel gather never reappears; train's backward carries
      all_gathers as the AD duals of the forward reduce_scatters);
    * ``dip_ep`` cells: the column probe is collective-free (dense
      projections place like ``dip_tp``); the prefill stage shows EXACTLY
      two all_to_alls per MoE forward body (dispatch + combine — the
      2-a2a contract of ``models.moe``), decode likewise, and train at
      least two (forward + transposed backward scan bodies);
    * ``pp`` cells: prefill/decode are recorded skipped (pipeline stages
      are a training schedule), and the train stage's jaxpr carries the
      boundary ppermute;
    * for ``tiny``/``full`` matrices: every arch in the document has at
      least one cell where train, prefill AND decode all passed.
    """
    errs: List[str] = []
    if payload.get("schema_version") != FLEET_SCHEMA_VERSION:
        _fail([f"schema_version must be {FLEET_SCHEMA_VERSION}, "
               f"got {payload.get('schema_version')!r}"])
    cells = payload.get("cells")
    if not isinstance(cells, list) or not cells:
        _fail(["cells must be a non-empty list"])
    for key in ("generated_by", "matrix", "dims"):
        if key not in payload:
            errs.append(f"missing top-level key {key!r}")

    full_pass: Dict[str, bool] = {}
    seen = set()
    for i, cell in enumerate(cells):
        where = f"cells[{i}]"
        for key in ("arch", "backend", "sharding", "effective_backend",
                    "quantization", "stages"):
            if key not in cell:
                errs.append(f"{where}: missing {key!r}")
        if errs:
            continue
        arch = cell["arch"]
        if cell["backend"] not in BACKENDS:
            errs.append(f"{where}: unknown backend {cell['backend']!r}")
        if cell["sharding"] not in SHARDINGS:
            errs.append(f"{where}: unknown sharding {cell['sharding']!r}")
        ckey = (arch, cell["backend"], cell["sharding"])
        if ckey in seen:
            errs.append(f"{where}: duplicate cell {ckey}")
        seen.add(ckey)
        stages = cell["stages"]
        for st in STAGES:
            rec = stages.get(st)
            if not isinstance(rec, dict) or "status" not in rec:
                errs.append(f"{where}.stages.{st}: missing record")
                continue
            if rec["status"] == "ok":
                if not (isinstance(rec.get("wall_us"), (int, float))
                        and rec["wall_us"] > 0):
                    errs.append(f"{where}.stages.{st}: wall_us must be > 0")
                if not (isinstance(rec.get("pallas_calls"), int)
                        and rec["pallas_calls"] >= 0):
                    errs.append(f"{where}.stages.{st}: bad pallas_calls")
                coll = rec.get("collectives")
                if (not isinstance(coll, dict)
                        or set(coll) != set(COLLECTIVES)
                        or any(not isinstance(coll[k], int) or coll[k] < 0
                               for k in COLLECTIVES)):
                    errs.append(f"{where}.stages.{st}: bad collectives dict")
                if not (isinstance(rec.get("peak_live_bytes"), int)
                        and rec["peak_live_bytes"] > 0):
                    errs.append(f"{where}.stages.{st}: bad peak_live_bytes")
            elif rec["status"] in ("failed", "skipped"):
                if not rec.get("reason"):
                    errs.append(f"{where}.stages.{st}: "
                                f"{rec['status']} needs a reason")
            else:
                errs.append(f"{where}.stages.{st}: "
                            f"unknown status {rec['status']!r}")
        all_ok = all(stages.get(st, {}).get("status") == "ok" for st in STAGES)
        full_pass[arch] = full_pass.get(arch, False) or all_ok

        probe = cell.get("column_probe")
        eff = cell["effective_backend"]
        if eff in ("dip_tp", "dip_fsdp", "dip_sp", "dip_ep"):
            if not isinstance(probe, dict):
                errs.append(f"{where}: {eff} cell needs a column_probe")
            else:
                pc = probe.get("collectives", {})
                if eff in ("dip_tp", "dip_ep") and any(
                        pc.get(k, 0) for k in COLLECTIVES):
                    errs.append(
                        f"{where}: {eff} column probe must show zero "
                        f"collectives, got {pc}")
                if eff == "dip_fsdp" and (
                        pc.get("all_gather") != 1 or pc.get("psum", 0) != 0):
                    errs.append(
                        f"{where}: dip_fsdp column probe must show exactly "
                        f"one all_gather and zero psum, got {pc}")
                if eff == "dip_sp" and (
                        pc.get("ppermute", 0) < 1
                        or any(pc.get(k, 0) for k in COLLECTIVES
                               if k != "ppermute")):
                    errs.append(
                        f"{where}: dip_sp column probe must gather inside "
                        f"the kernel (ppermute >= 1, nothing else), got {pc}")
            dec = stages.get("decode", {})
            if (eff == "dip_tp" and dec.get("status") == "ok"
                    and dec.get("collectives", {}).get("all_gather", 0) > 0):
                errs.append(f"{where}: dip_tp decode must not all_gather "
                            "(columns stay sharded; rows psum)")
            if eff == "dip_sp":
                # forward stages only: the train backward contains
                # all_gathers as the AD duals of the forward
                # reduce_scatters (fwd RS <-> bwd AG is the standard
                # sequence-parallel transpose pair)
                for st in ("prefill", "decode"):
                    rec = stages.get(st, {})
                    if (rec.get("status") == "ok"
                            and rec.get("collectives", {}).get(
                                "all_gather", 0) > 0):
                        errs.append(
                            f"{where}.{st}: dip_sp forward must never "
                            "all_gather — x blocks ring through the "
                            "kernel's load stage")
            if eff == "dip_ep":
                for st, want in (("prefill", 2), ("decode", 2)):
                    rec = stages.get(st, {})
                    if rec.get("status") != "ok":
                        continue
                    a2a = rec.get("collectives", {}).get("all_to_all", 0)
                    if a2a != want:
                        errs.append(
                            f"{where}.{st}: dip_ep must show exactly {want} "
                            f"all_to_alls per MoE forward body (dispatch + "
                            f"combine), got {a2a}")
                tr = stages.get("train", {})
                if (tr.get("status") == "ok"
                        and tr.get("collectives", {}).get("all_to_all", 0) < 2):
                    errs.append(f"{where}.train: dip_ep train must carry the "
                                "dispatch/combine all_to_all pair")

        if cell["sharding"] == "pp":
            for st in ("prefill", "decode"):
                if stages.get(st, {}).get("status") != "skipped":
                    errs.append(f"{where}.{st}: pp cells record serving "
                                "stages as skipped (training schedule)")
            tr = stages.get("train", {})
            if (tr.get("status") == "ok"
                    and tr.get("collectives", {}).get("ppermute", 0) < 1):
                errs.append(f"{where}.train: pp train must carry the stage-"
                            "boundary ppermute")

        vp = cell.get("verify_probe")
        if cell["sharding"] == "gspmd":
            if not isinstance(vp, dict):
                errs.append(f"{where}: gspmd cell needs a verify_probe")
            elif vp.get("status") == "failed":
                errs.append(f"{where}: verify_probe failed "
                            f"({vp.get('reason', 'no reason')})")
            elif vp.get("extra_pallas_calls", 0) != 0:
                errs.append(
                    f"{where}: verified dispatch added "
                    f"{vp['extra_pallas_calls']} pallas launches "
                    "(contract: the ABFT audit launches zero kernels)")

    if payload.get("matrix") in ("tiny", "full"):
        for arch, ok in sorted(full_pass.items()):
            if not ok:
                errs.append(
                    f"arch {arch!r} has no cell passing all of "
                    "train+prefill+decode")
    if errs:
        _fail(errs)


def diff_fleet_json(payload: Dict[str, Any],
                    baseline: Dict[str, Any]) -> None:
    """Reject regressions of ``payload`` against the committed ``baseline``.

    Launch counts must not exceed the baseline (the fused-epilogue and
    quantized-kernel wins of PRs 3-4 stay won), collective counts must not
    exceed it (the PR-5 placement contract stays placed), baseline cells may
    not disappear, and a stage that passed before may not fail now.  Wall
    times are informational — machines differ; structure does not.
    """
    errs: List[str] = []
    new = {(c["arch"], c["backend"], c["sharding"]): c
           for c in payload.get("cells", [])}
    for cell in baseline.get("cells", []):
        key = (cell["arch"], cell["backend"], cell["sharding"])
        name = "/".join(key)
        other = new.get(key)
        if other is None:
            errs.append(f"{name}: cell present in baseline but missing now")
            continue
        for st in STAGES:
            base = cell["stages"].get(st, {})
            cur = other["stages"].get(st, {})
            if base.get("status") != "ok":
                continue
            if cur.get("status") != "ok":
                errs.append(f"{name}.{st}: was ok in baseline, now "
                            f"{cur.get('status')!r} "
                            f"({cur.get('reason', 'no reason')})")
                continue
            if cur["pallas_calls"] > base["pallas_calls"]:
                errs.append(
                    f"{name}.{st}: pallas_calls regressed "
                    f"{base['pallas_calls']} -> {cur['pallas_calls']}")
            for k in COLLECTIVES:
                if cur["collectives"][k] > base["collectives"][k]:
                    errs.append(
                        f"{name}.{st}: {k} count regressed "
                        f"{base['collectives'][k]} -> {cur['collectives'][k]}")
        bvp = cell.get("verify_probe")
        if isinstance(bvp, dict) and "extra_pallas_calls" in bvp:
            cvp = other.get("verify_probe")
            if not isinstance(cvp, dict) or "extra_pallas_calls" not in cvp:
                errs.append(f"{name}: verify_probe present in baseline "
                            "but missing/failed now")
            elif cvp["extra_pallas_calls"] > bvp["extra_pallas_calls"]:
                errs.append(
                    f"{name}: verify_probe extra_pallas_calls regressed "
                    f"{bvp['extra_pallas_calls']} -> "
                    f"{cvp['extra_pallas_calls']}")
    if errs:
        raise ValueError("fleet regression vs baseline:\n  "
                         + "\n  ".join(errs))


# ---------------------------------------------------------------------------
# drive + report
def run_matrix(cells: Sequence[Tuple[str, str, str]], *, matrix: str,
               iters: int = 1, verbose: bool = True) -> Dict[str, Any]:
    import jax

    out: List[Dict[str, Any]] = []
    for arch, backend, sharding in cells:
        t0 = time.perf_counter()
        cell = run_cell(arch, backend, sharding, iters=iters)
        took = time.perf_counter() - t0
        if verbose:
            marks = " ".join(
                f"{st}:{cell['stages'][st]['status']}" for st in STAGES)
            print(f"  {arch:24s} {backend:10s} {sharding:6s}  "
                  f"{marks}  ({took:.1f}s)")
            for st in STAGES:
                rec = cell["stages"][st]
                if rec["status"] == "failed":
                    print(f"      {st} FAILED: {rec['reason']}")
        out.append(cell)
    return {
        "schema_version": FLEET_SCHEMA_VERSION,
        "generated_by": "benchmarks/fleet.py",
        "jax_backend": jax.default_backend(),
        "matrix": matrix,
        "dims": dict(DIMS),
        "devices": jax.device_count(),
        "cells": out,
    }


def csv_rows_from(payload: Dict[str, Any]) -> List[Tuple[str, float, str]]:
    rows = []
    for cell in payload["cells"]:
        stem = f"fleet_{cell['arch']}_{cell['backend']}_{cell['sharding']}"
        for st in STAGES:
            rec = cell["stages"][st]
            if rec["status"] != "ok":
                rows.append((f"{stem}_{st}", 0.0, rec["status"]))
                continue
            coll = sum(rec["collectives"].values())
            rows.append((f"{stem}_{st}", rec["wall_us"],
                         f"launches={rec['pallas_calls']};collectives={coll};"
                         f"peak_mb={rec['peak_live_bytes'] / 1e6:.2f}"))
    return rows


# ---------------------------------------------------------------------------
# forced-device re-exec (mirrors kernels_bench --sharded)
def _held_to_cpu_without_forced_devices() -> bool:
    """Whether ``JAX_PLATFORMS=cpu`` holds this process to the CPU and no
    host device count is forced yet.  Decided from the environment alone,
    before JAX is imported: a process on an accelerator never starts a CPU
    child."""
    return (os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
            and "--xla_force_host_platform_device_count"
            not in os.environ.get("XLA_FLAGS", ""))


def _reexec_with_devices(argv: Sequence[str], devices: int) -> int:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices} "
        + env.get("XLA_FLAGS", "")).strip()
    root = pathlib.Path(__file__).resolve().parent.parent
    env["PYTHONPATH"] = os.pathsep.join(
        [str(root / "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    proc = subprocess.run(
        [sys.executable, str(pathlib.Path(__file__).resolve()), *argv],
        env=env, cwd=str(root))
    return proc.returncode


def _has_sharded(cells: Sequence[Tuple[str, str, str]]) -> bool:
    return any(s != "gspmd" for _, _, s in cells)


# ---------------------------------------------------------------------------
# entrypoints
def _select_cells(args) -> Tuple[List[Tuple[str, str, str]], str]:
    from repro.configs import ALL_ARCHS

    archs = args.archs.split(",") if args.archs else list(ALL_ARCHS)
    unknown = sorted(set(archs) - set(ALL_ARCHS))
    if unknown:
        raise SystemExit(f"unknown archs: {unknown}; have {ALL_ARCHS}")
    matrix = "full" if args.full else "tiny"
    cells = (full_cells if args.full else tiny_cells)(archs)
    if args.backends:
        keep = set(args.backends.split(","))
        cells = [c for c in cells if c[1] in keep]
        matrix = "custom"
    if args.shardings:
        keep = set(args.shardings.split(","))
        cells = [c for c in cells if c[2] in keep]
        matrix = "custom"
    if args.archs:
        matrix = "custom"
    if not cells:
        raise SystemExit("filters selected an empty matrix")
    return cells, matrix


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="committed-baseline matrix (default)")
    ap.add_argument("--full", action="store_true",
                    help="every arch x backend x sharding cell")
    ap.add_argument("--archs", default=None, help="comma list subset")
    ap.add_argument("--backends", default=None, help="comma list subset")
    ap.add_argument("--shardings", default=None, help="comma list subset")
    ap.add_argument("--iters", type=int, default=1,
                    help="timed iterations per stage")
    ap.add_argument("--out", default=None,
                    help="write BENCH_fleet.json here")
    ap.add_argument("--baseline", default=None,
                    help="diff counts against this committed baseline")
    ap.add_argument("--devices", type=int, default=2,
                    help="forced host devices for sharded cells")
    args = ap.parse_args(argv)

    cells, matrix = _select_cells(args)
    if _has_sharded(cells):
        if _held_to_cpu_without_forced_devices():
            return _reexec_with_devices(
                list(argv) if argv is not None else sys.argv[1:], args.devices)
        import jax

        if jax.device_count() < 2:
            raise SystemExit(
                f"fleet: sharded cells need >= 2 devices; "
                f"{jax.default_backend()} has {jax.device_count()} (on a CPU "
                "host, JAX_PLATFORMS=cpu forces host devices)")

    print(f"== fleet: {len(cells)} cells ({matrix} matrix) ==")
    payload = run_matrix(cells, matrix=matrix, iters=args.iters)
    validate_fleet_json(payload)
    print(f"schema: OK ({len(payload['cells'])} cells)")

    if args.baseline:
        with open(args.baseline) as f:
            diff_fleet_json(payload, json.load(f))
        print(f"baseline diff vs {args.baseline}: OK")

    if args.out:
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.out}")

    failed = [
        (c["arch"], c["backend"], c["sharding"], st)
        for c in payload["cells"] for st in STAGES
        if c["stages"][st]["status"] == "failed"]
    if failed:
        print(f"{len(failed)} failed stage(s):")
        for arch, backend, sharding, st in failed:
            print(f"  {arch}/{backend}/{sharding}/{st}")
        return 1
    return 0


def run(csv_rows) -> None:
    """benchmarks.run harness contract: tiny matrix, validated, diffed
    against the committed baseline when present, rows appended."""
    import jax

    cells = tiny_cells([a for a in _all_archs()])
    if _has_sharded(cells) and jax.device_count() < 2:
        # Under the single-process harness we cannot re-exec just this
        # module; drop the sharded cells and say so rather than fail.
        print("fleet: <2 devices and no re-exec under benchmarks.run; "
              "dropping tp/fsdp cells (run benchmarks/fleet.py directly "
              "for the sharded columns)")
        cells = [c for c in cells if c[2] == "gspmd"]
        matrix = "custom"
    else:
        matrix = "tiny"
    payload = run_matrix(cells, matrix=matrix)
    validate_fleet_json(payload)
    if DEFAULT_JSON.exists() and matrix == "tiny":
        with open(DEFAULT_JSON) as f:
            diff_fleet_json(payload, json.load(f))
        print(f"fleet: baseline diff vs {DEFAULT_JSON.name}: OK")
    csv_rows.extend(csv_rows_from(payload))


def _all_archs() -> List[str]:
    from repro.configs import ALL_ARCHS

    return list(ALL_ARCHS)


if __name__ == "__main__":
    raise SystemExit(main())
