#!/usr/bin/env python3
"""Chip smoke test: yi_9b at its published widths, served and trained on TPU.

    python chip_smoke.py              # one chip: serving, parity, training
    python chip_smoke.py --chips 4    # four chips: dip_tp vs GSPMD-xla only

One chip runs three phases in this one process, through the entry points a
user calls:

* serving: ``serving.Engine`` over the DiP ``pallas_dip`` kernels in bf16,
  chunked prefill through the flash-attention kernel, paged decode; a few
  seeded greedy requests, served twice (the first pass compiles);
* parity: one prompt's prefill logits from the served path against a plain
  ``xla``-backend float32 forward on the same weights;
* training: a few ``runtime.Trainer`` steps with ``pallas_dip``.

``--chips 4`` serves through ``dip_tp`` on a (data=1, model=4) mesh and
compares its prefill logits with GSPMD ``xla`` on the same mesh; it runs no
other phase.

Depth is cut to what the chips hold; widths, heads and vocab are the
config's own; weights are random from ``--seed``.  Any failure exits
nonzero.  Without a TPU the script exits nonzero before any phase runs.
The last line of stdout is the JSON result.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

ARCH = "yi_9b"
# One chip holds 16 GB.  Serving keeps float32 weights (4 B/param): the
# 64000-word embedding and head (0.54 B params) plus 8 of the 48 layers
# (0.17 B params each) come to 7.6 GB, leaving room for the KV pool and
# the float32 reference forward of the parity phase.
SERVE_LAYERS = 8
# Training keeps weights, gradients and two AdamW moments (16 B/param):
# embedding and head alone are 8.6 GB, so one layer (11.4 GB) is the most
# that fits next to the step's activations.
TRAIN_LAYERS = 1
N_REQUESTS, PROMPT_MIN, PROMPT_MAX, NEW_TOKENS = 8, 128, 512, 32
SLOTS, PREFILL_CHUNK = 8, 128
MAX_SEQ = 576                      # longest prompt + new tokens, 64-aligned
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 3, 4, 512
# Bound on max|served - reference| over one prefill's logits.  The served
# path rounds activations, weights and cached K/V to bf16 at every layer;
# the reference is float32 at "highest" matmul precision.  The logits of
# this random-weight model have a standard deviation near 0.9, so bf16
# rounding through 8 layers moves them by a few hundredths, while a wrong
# de-shear or a dropped epilogue (residual, SwiGLU gate) changes them by
# a multiple of their own spread.
PARITY_TOL = 0.25


def log(msg: str) -> None:
    print(msg, flush=True)


@contextlib.contextmanager
def timed(label: str, timings: dict):
    """Record the wall time of one phase under ``label``."""
    t0 = time.perf_counter()
    yield
    timings[label] = time.perf_counter() - t0


def require_tpu(chips: int):
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found {platform!r}")
    if len(devices) < chips:
        raise SystemExit(
            f"chip_smoke: --chips {chips} but JAX found {len(devices)} TPU(s)")
    return devices


def describe(cfg) -> str:
    return (f"{cfg.name}: d_model {cfg.d_model}, {cfg.n_heads} heads, "
            f"{cfg.n_kv_heads} KV heads, head_dim {cfg.resolved_head_dim}, "
            f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}; layers {cfg.n_layers} "
            f"of {get_config(ARCH).n_layers}; backend {cfg.matmul_backend}, "
            f"compute {cfg.compute_dtype}, params {cfg.param_dtype} "
            f"({cfg.param_count() / 1e9:.3f} B)")


def make_prompts(cfg, seed: int):
    rng = np.random.default_rng(seed)
    lengths = rng.integers(PROMPT_MIN, PROMPT_MAX + 1, size=N_REQUESTS)
    return [rng.integers(2, cfg.vocab_size, size=int(n)).astype(np.int32)
            for n in lengths]


# ---------------------------------------------------------------- serving --
def serve(cfg, params, prompts, timings, *, plan=None, label="serve"):
    """Serve ``prompts`` twice through one engine (the first pass compiles)
    and check both passes produced the same full-length greedy streams."""
    from repro.serving import Engine, EngineConfig, SamplingParams

    engine = Engine(
        cfg, params, plan=plan,
        engine_cfg=EngineConfig(slots=SLOTS, max_seq=MAX_SEQ,
                                prefill_chunk=PREFILL_CHUNK, eos_id=-1),
    )
    sp = SamplingParams(max_new_tokens=NEW_TOKENS)      # greedy
    passes = []
    for name in ("cold", "warm"):
        for rid, prompt in enumerate(prompts):
            engine.add_request(prompt, sp, rid=rid)
        with timed(f"{label}_{name}_s", timings):
            results = engine.run()
        passes.append(results)
        log(f"[{label}] {name} pass: {engine.last_stats}")
    for rid, prompt in enumerate(prompts):
        toks = passes[1][rid]
        st = engine.request_stats[rid]
        log(f"[{label}] request {rid}: prompt {prompt.size} tokens -> "
            f"{len(toks)} new tokens, ttft {st['ttft_s']:.4f} s, "
            f"first {toks[:6]}")
        if len(toks) != NEW_TOKENS:
            raise AssertionError(f"request {rid} got {len(toks)} tokens")
        if toks != passes[0][rid]:
            raise AssertionError(f"request {rid}: greedy streams differ "
                                 "between the cold and warm passes")
    return engine


def prefill_logits(cfg, params, prompt, *, plan=None, attn_backend=None):
    """Logits of ``prompt`` through the engine's chunked prefill step:
    ``decode_step_fn`` over fixed-size chunks into a contiguous cache."""
    import jax
    import jax.numpy as jnp

    from repro.models import transformer as tf_model

    step = jax.jit(tf_model.decode_step_fn(cfg, plan=plan,
                                           attn_backend=attn_backend))
    buf = -(-MAX_SEQ // PREFILL_CHUNK) * PREFILL_CHUNK
    cache = tf_model.init_cache(cfg, 1, buf)
    tokens = np.zeros(-(-prompt.size // PREFILL_CHUNK) * PREFILL_CHUNK, np.int32)
    tokens[:prompt.size] = prompt
    rows = []
    for start in range(0, tokens.size, PREFILL_CHUNK):
        chunk = jnp.asarray(tokens[None, start:start + PREFILL_CHUNK])
        logits, cache = step(params, cache, chunk)
        rows.append(np.asarray(logits[0, :, :cfg.vocab_size]))
    return np.concatenate(rows)[:prompt.size]


def compare(label, got, want, timings):
    delta = float(np.max(np.abs(got - want)))
    agree = float(np.mean(got.argmax(-1) == want.argmax(-1)))
    log(f"[{label}] max|delta| {delta:.6f} (tolerance {PARITY_TOL}); "
        f"reference logits std {float(want.std()):.4f}, "
        f"max|ref| {float(np.abs(want).max()):.4f}; "
        f"top-1 agreement {agree:.4f} over {got.shape[0]} positions")
    timings[f"{label}_max_abs_delta"] = delta
    if not math.isfinite(delta) or delta > PARITY_TOL:
        raise AssertionError(f"{label}: max|delta| {delta} exceeds {PARITY_TOL}")


def parity(cfg, params, prompt, timings):
    """Served prefill (flash + pallas_dip, bf16) vs a plain float32 xla
    forward over the whole prompt at the highest matmul precision."""
    import jax

    from repro.models import transformer as tf_model

    with timed("parity_s", timings):
        got = prefill_logits(cfg, params, prompt, attn_backend="flash")
        ref_cfg = dataclasses.replace(cfg, matmul_backend="xla",
                                      compute_dtype="float32")
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(
                lambda p, t: tf_model.forward(p, ref_cfg, tokens=t)[0]
            )(params, prompt[None])
        want = np.asarray(ref[0, :, :cfg.vocab_size])
    compare("parity", got, want, timings)


# --------------------------------------------------------------- training --
def train(cfg, timings, seed: int):
    from repro.runtime import Trainer, TrainerConfig

    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt_dir:
        trainer = Trainer(
            cfg,
            TrainerConfig(steps=TRAIN_STEPS, ckpt_every=TRAIN_STEPS + 1,
                          ckpt_dir=ckpt_dir, log_every=1),
            seq_len=TRAIN_SEQ, global_batch=TRAIN_BATCH,
        )
        with timed("train_s", timings):
            out = trainer.run(seed=seed)
    metrics = out["metrics"]
    losses = [m["loss"] for m in metrics]
    log(f"[train] {len(losses)} steps, batch {TRAIN_BATCH} x {TRAIN_SEQ} "
        f"tokens; losses {losses}; step times (s) "
        f"{[round(m['step_time_s'], 4) for m in metrics]} (the first compiles)")
    if len(losses) != TRAIN_STEPS or not all(math.isfinite(v) for v in losses):
        raise AssertionError(f"training losses not all finite: {losses}")


# ---------------------------------------------------------------- phases ---
def one_chip(seed: int, timings: dict) -> None:
    import jax

    from repro.models import transformer as tf_model

    yi = get_config(ARCH)
    cfg = dataclasses.replace(yi, n_layers=SERVE_LAYERS,
                              matmul_backend="pallas_dip")
    log(f"[serve] {describe(cfg)}")
    with timed("serve_init_s", timings):
        params = jax.block_until_ready(
            tf_model.init_params(jax.random.PRNGKey(seed), cfg))
    prompts = make_prompts(cfg, seed)
    engine = serve(cfg, params, prompts, timings)
    del engine
    parity(cfg, params, prompts[0], timings)
    del params

    tcfg = dataclasses.replace(yi, n_layers=TRAIN_LAYERS,
                               matmul_backend="pallas_dip")
    log(f"[train] {describe(tcfg)}")
    train(tcfg, timings, seed)


def four_chips(seed: int, timings: dict) -> None:
    """dip_tp serving and its logits against GSPMD xla, one 2x2 host."""
    import jax

    from repro import api
    from repro.distributed.plan import make_local_mesh, make_plan
    from repro.models import transformer as tf_model

    yi = get_config(ARCH)
    mesh = make_local_mesh(data=1, model=4)
    tp_cfg = dataclasses.replace(yi, n_layers=SERVE_LAYERS, sharding="tp",
                                 matmul_backend="dip_tp")
    gs_cfg = dataclasses.replace(tp_cfg, sharding="gspmd",
                                 matmul_backend="xla", dip_weights=True)
    log(f"[tp] {describe(tp_cfg)}; mesh {dict(mesh.shape)}")
    with timed("tp_init_s", timings):
        params = jax.block_until_ready(
            tf_model.init_params(jax.random.PRNGKey(seed), tp_cfg))
    tp_plan = make_plan(mesh, tp_cfg, "decode")
    gs_plan = make_plan(mesh, gs_cfg, "decode")
    gs_params = gs_plan.attach_params(params)
    gs_params = jax.tree_util.tree_map(
        jax.device_put, gs_params, gs_plan.param_shardings(gs_params))
    prompts = make_prompts(tp_cfg, seed)
    engine = serve(tp_cfg, params, prompts, timings, plan=tp_plan, label="tp")
    del params

    # the engine placed the TP weights: every split projection spans the
    # four chips, one quarter on each
    n_split = 0
    for leaf in jax.tree_util.tree_leaves(
            engine.params, is_leaf=lambda t: isinstance(t, api.DipWeight)):
        if not isinstance(leaf, api.DipWeight) or leaf.plan is None:
            continue
        if leaf.plan.kind in ("column", "row"):
            n_split += 1
            devices = {s.device for s in leaf.data.addressable_shards}
            shard = leaf.data.addressable_shards[0].data.size
            if len(devices) != 4 or shard * 4 != leaf.data.size:
                raise AssertionError(
                    f"{leaf.plan.kind} weight {leaf.data.shape} on "
                    f"{len(devices)} device(s), shard {shard} elements")
    if n_split == 0:
        raise AssertionError("no projection was split over the model axis")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0) / 2**30
              for d in jax.devices()[:4]]
    log(f"[tp] {n_split} split projections, each a quarter per chip; "
        f"GiB in use per chip {[round(g, 3) for g in in_use]}")

    with timed("tp_parity_s", timings), mesh:
        got = prefill_logits(tp_cfg, engine.params, prompts[0], plan=tp_plan)
        want = prefill_logits(gs_cfg, gs_params, prompts[0], plan=gs_plan)
    compare("tp_parity", got, want, timings)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serving, parity and training on one chip; "
                         "4: dip_tp vs GSPMD-xla serving on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = require_tpu(args.chips)
    cache_dir = enable_compile_cache()
    log(f"[chip_smoke] {len(devices)} x {devices[0].device_kind}; "
        f"compile cache {cache_dir}")
    timings: dict = {}
    t0 = time.perf_counter()
    (one_chip if args.chips == 1 else four_chips)(args.seed, timings)
    timings["total_s"] = time.perf_counter() - t0
    cache = Path(cache_dir)
    n_cached = sum(1 for p in cache.rglob("*") if p.is_file()) if cache.is_dir() else 0
    log(f"[chip_smoke] timings {json.dumps(timings)}; "
        f"{n_cached} files in {cache_dir}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
