"""Serving driver: batched requests through the serving engine.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-9b \
        --requests 8 --max-new 32

``--reduced`` (the default) serves the config's tiny same-family variant;
``--no-reduced`` serves its published widths.  Every projection computes
in the config's ``compute_dtype``.

``--engine paged`` (default) serves through the continuous-batching engine
with the paged KV cache (``repro.serving``); ``--engine wave`` runs the
legacy static-batch wave loop for comparison.  ``--kv-quant int8`` stores
K/V at int8 (~2x sequences per byte; see docs/serving.md).
"""

from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as tf_model
from repro.runtime import Server, ServerConfig, WaveServer
from repro.runtime.server import Request


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the tiny same-family variant (default); "
                         "--no-reduced serves the published widths")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--engine", choices=("paged", "wave"), default="paged",
                    help="paged: continuous-batching engine (repro.serving); "
                         "wave: legacy static-batch loop")
    ap.add_argument("--block-size", type=int, default=None,
                    help="paged-KV block size (tokens; default "
                         "cfg.kv_block_size)")
    ap.add_argument("--kv-quant", choices=("none", "int8"), default=None,
                    help="KV-cache storage (default cfg.kv_quant); int8 "
                         "halves cache bytes per token")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="chunked-prefill granularity (tokens per chunk)")
    ap.add_argument("--dip", action="store_true",
                    help="store weights DiP-permutated + use the Pallas kernel")
    ap.add_argument("--sharded", choices=("tp", "fsdp"), default=None,
                    help="serve through the explicit multi-chip backends "
                         "(dip_tp / dip_fsdp) on a mesh over the local "
                         "devices — see docs/distributed.md")
    ap.add_argument("--quantize", choices=("int8", "fp8_e4m3"), default=None,
                    help="quantize the DiP projections and serve through the "
                         "matching quantized kernel (dip_int8w / dip_fp8)")
    ap.add_argument("--autotune", action="store_true",
                    help="measure block-size candidates for this config's "
                         "projections before serving (tiled backends only)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.dip:
        cfg = dataclasses.replace(cfg, matmul_backend="pallas_dip")
    if args.quantize:
        from repro.api import quant
        cfg = dataclasses.replace(
            cfg, quantization=args.quantize,
            matmul_backend=quant.scheme_info(args.quantize).backend,
        )
    plan = None
    if args.sharded:
        from repro.distributed.plan import make_local_mesh, make_plan
        # explicit multi-chip serving: TP over all local devices, or FSDP
        # over all local devices, dispatched per the weights' plan metadata
        n_dev = jax.device_count()
        mesh = (make_local_mesh(data=1, model=n_dev) if args.sharded == "tp"
                else make_local_mesh(data=n_dev, model=1))
        backend = {"tp": "dip_tp", "fsdp": "dip_fsdp"}[args.sharded]
        cfg = dataclasses.replace(cfg, sharding=args.sharded,
                                  matmul_backend=backend)
        plan = make_plan(mesh, cfg, "decode")
    if args.autotune:
        # registers measured tuning entries before the first forward traces,
        # so every jitted dispatch below picks them up
        from repro.api import autotune
        autotune.autotune_for_config(cfg, tokens=args.slots, verbose=True)

    params = tf_model.init_params(jax.random.PRNGKey(0), cfg)
    scfg = ServerConfig(
        batch_slots=args.slots, max_seq=args.max_seq,
        max_new_tokens=args.max_new, prefill_chunk=args.prefill_chunk,
        block_size=args.block_size, kv_quant=args.kv_quant,
    )
    cls = Server if args.engine == "paged" else WaveServer
    server = cls(cfg, scfg, params, plan=plan)
    rng = np.random.default_rng(0)
    reqs = [
        Request(rid=i, prompt=rng.integers(2, cfg.vocab_size, size=rng.integers(4, 16)))
        for i in range(args.requests)
    ]
    results = server.serve(reqs)
    for rid in sorted(results):
        print(f"req {rid}: {len(results[rid])} tokens -> {results[rid][:8]}...")
    print(f"[serve:{args.engine}] {server.last_stats}")


if __name__ == "__main__":
    main()
