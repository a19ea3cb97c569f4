#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell: the highest arrival rate the
engine sustains without a growing queue.  Run once, when a cell is defined;
the cell's traffic file then states its rate as a number.

    python3 bench/sweep.py --workload yi_9b.chat_poisson --rates 0.5 1 1.5 --seconds 40

One process, one set of weights; each rate gets a fresh engine, the mix's
warm period and a window of ``--seconds``.  Prints one JSON line a rate:
requests due in the window, how many still waited for a first token at the
window's open and at its close, the tails, and output tokens per second.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import program, run, serve  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=40)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    wl, conf, mix, _, _ = run.resolve(args.workload, False)
    devices = run.require_chips(wl["chips"])
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    params = None
    for rate in args.rates:
        cell = run.Cell(workload=args.workload, conf=conf, mix=dict(mix, rate_per_s=rate),
                        seed=args.seed, seconds=args.seconds, trace=False,
                        trace_dir=None, device=devices[0])
        if params is None:
            params = program.load_params(cell.cfg, cell.dims, cell.key)
        rec = serve.run(cell, time.monotonic, params=params, check=False)
        rec.update(cell=cell, setup_s=cell.setup_s,
                   host_window=(rec["t_open"], rec["t_close"]))
        t_open = rec["t_open"]
        waiting_at_open = sum(1 for s in rec["served"].values()
                              if s.due < t_open and (not s.times or s.times[0] >= t_open))
        out = {"rate_per_s": rate, "waiting_at_open": waiting_at_open}
        out.update(rec["notes"])
        for name in ("ttft_p90_s", "itl_p95_ms", "output_tok_s", "tick_ms", "mfu"):
            out[name] = run.reader(name).read(rec)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
