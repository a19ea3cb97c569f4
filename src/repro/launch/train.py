"""Training driver.

    PYTHONPATH=src python -m repro.launch.train --arch mamba2-370m --reduced \
        --steps 50 --batch 8 --seq 256 --ckpt-dir /tmp/run1

On the CPU container this trains reduced configs end-to-end (the ~100M-scale
example lives in examples/train_lm.py); on a real slice the same driver jits
the full config against the production mesh (--mesh single|multi).
"""

from __future__ import annotations

import argparse

import jax

from repro.configs import get_config
from repro.distributed.plan import (
    STRATEGIES, make_local_mesh, make_plan, make_production_mesh,
)
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import AdamW, cosine_schedule
from repro.runtime import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--mesh", default="none", choices=["none", "local", "single", "multi"])
    ap.add_argument("--sharding", default=None, choices=list(STRATEGIES),
                    help="override cfg.sharding: gspmd (implicit XLA "
                         "partitioning) | tp | fsdp | sp | ep (explicit "
                         "shard_map backends) | pp (pipeline stage axis; "
                         "pair with --stages — see docs/distributed.md)")
    ap.add_argument("--stages", type=int, default=1,
                    help="pipeline stages for --sharding pp: the local mesh "
                         "gets a leading 'stage' axis of this size")
    ap.add_argument("--microbatches", type=int, default=0,
                    help="GPipe microbatches per step for --sharding pp "
                         "(0 = auto: 2x stages)")
    ap.add_argument("--strict-sharding", action="store_true",
                    help="raise (instead of warn-once + replicate) when a "
                         "param dim does not divide its mesh axis")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--autotune", action="store_true",
                    help="measure block-size candidates for this config's "
                         "projections before training (tiled backends only)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.sharding:
        import dataclasses
        cfg = dataclasses.replace(cfg, sharding=args.sharding)
        explicit = {"tp": "dip_tp", "fsdp": "dip_fsdp", "sp": "dip_sp", "ep": "dip_ep"}
        if args.sharding in explicit:
            # the explicit strategies dispatch through their sharded backend;
            # without this the flag would silently keep the implicit path.
            # pp is a stage axis, not a backend — the per-stage matmuls keep
            # the config's backend.
            cfg = dataclasses.replace(cfg, matmul_backend=explicit[args.sharding])
    if args.autotune:
        # registers measured tuning entries before train_step traces, so the
        # jitted step dispatches with them
        from repro.api import autotune
        autotune.autotune_for_config(cfg, tokens=args.batch * args.seq, verbose=True)

    mesh = plan = None
    if args.mesh == "local":
        if args.stages > 1:
            if jax.device_count() % args.stages:
                raise SystemExit(
                    f"--stages {args.stages} does not divide "
                    f"{jax.device_count()} devices"
                )
            mesh = make_local_mesh(
                data=jax.device_count() // args.stages, model=1, stage=args.stages
            )
        else:
            mesh = make_local_mesh(data=jax.device_count())
        plan = make_plan(mesh, cfg, "train", strict=args.strict_sharding)
    elif args.mesh in ("single", "multi"):
        mesh = make_production_mesh(multi_pod=args.mesh == "multi")
        plan = make_plan(mesh, cfg, "train", strict=args.strict_sharding)

    gt = None
    if args.compress_grads:
        from repro.distributed import compression
        gt = compression.compression_transform()
    opt = AdamW(lr=cosine_schedule(args.lr, 10, args.steps), grad_transform=gt)

    trainer = Trainer(
        cfg,
        TrainerConfig(steps=args.steps, ckpt_every=args.ckpt_every,
                      ckpt_dir=args.ckpt_dir,
                      pipeline_microbatches=args.microbatches),
        optimizer=opt,
        mesh=mesh,
        plan=plan,
        seq_len=args.seq,
        global_batch=args.batch,
    )
    ctx = mesh if mesh is not None else _null()
    with ctx:
        out = trainer.run()
    print(f"[train] done: {len(out['metrics'])} steps in {out['wall_s']:.1f}s, "
          f"final loss {out['metrics'][-1]['loss']:.4f}")


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()
