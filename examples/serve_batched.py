"""End-to-end serving driver (the paper is an inference accelerator, so the
end-to-end example is serving): batched requests through the slot-pool
server, with the DiP permutated weight format + Pallas kernel as the live
matmul path.

    PYTHONPATH=src python examples/serve_batched.py [--arch yi-9b] [--dip]
"""

import argparse
import dataclasses

import jax
import numpy as np

from repro.configs import get_config
from repro.models import transformer as tf_model
from repro.runtime import Server, ServerConfig
from repro.runtime.server import Request


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-9b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--dip", action="store_true",
                    help="DiP storage + Pallas fused kernel for every matmul")
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    if args.dip:
        cfg = dataclasses.replace(cfg, matmul_backend="pallas_dip")
    print(f"serving reduced {cfg.name} ({cfg.param_count()/1e6:.1f}M params, "
          f"backend={cfg.matmul_backend}, dip_storage={cfg.uses_dip_storage})")

    params = tf_model.init_params(jax.random.PRNGKey(0), cfg)
    server = Server(
        cfg,
        ServerConfig(batch_slots=args.slots, max_seq=128, max_new_tokens=args.max_new),
        params,
    )
    rng = np.random.default_rng(0)
    reqs = [
        Request(rid=i, prompt=rng.integers(2, cfg.vocab_size, size=int(rng.integers(4, 12))))
        for i in range(args.requests)
    ]
    results = server.serve(reqs)
    for rid in sorted(results):
        print(f"  request {rid}: {len(results[rid]):>3} new tokens  {results[rid][:10]}")
    s = server.last_stats
    print(f"done: {s['decode_steps']} decode steps, {s['tok_per_s']:.1f} tok/s "
          f"on {jax.default_backend()}")


if __name__ == "__main__":
    main()
