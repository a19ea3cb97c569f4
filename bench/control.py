#!/usr/bin/env python3
"""Readings that the serving check's limit is set from, on the chip.

    python3 bench/control.py --workload yi_9b.chat_poisson --seeds 1 2 3
    python3 bench/control.py --workload yi_9b.chat_poisson --seeds 1 2 3 --fault stale_cache

For each seed, in one process: the cell's weights and engine at the cell's
own sizes, its traffic's first ``slots`` requests served together at full
occupancy (greedy and sampled as the mix has them), and then, on the
check's sample of finished greedy requests, the check's numbers
(``serve.compare``): of the tokens and logits the program served (the
lower reading), and of the int8 control put in its place at the same
positions (the upper reading).  With ``--fault`` the program serves with
that fault (bench/faults.py) planted, and its own tokens are read.  Each
reading goes through the run's own verdict against the configuration's
limits.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import faults, gen, program, run, serve  # noqa: E402


def readings(cell, clock, fault=None) -> dict:
    """{side: the check's numbers}: "program" and "control", or the fault's
    name with a fault planted."""
    with faults.FAULTS[fault]() if fault else contextlib.nullcontext():
        params = program.load_params(cell.cfg, cell.dims, cell.key)
        engine = serve._engine(cell, params)
        logits = program.record_served_logits(engine)
        del params
        stream = gen.requests(dict(cell.mix, rate_per_s=None), cell.seed, cell.dims.vocab)
        served = []
        for _ in range(cell.mix["engine"]["slots"]):
            s = serve.Served(next(stream), due=clock())
            serve._add(engine, s, clock)
            served.append(s)
        while engine.step():
            pass
        del engine
    pick = serve.sample(cell, served)
    n = cell.mix["check"]["requests"]
    sides = {fault: "f32"} if fault else {"program": "f32", "control": "int8"}
    return {side: serve.numbers([serve.compare(cell, s.req.prompt, s.tokens,
                                               logits.get(s.req.index), mode)
                                 for s in pick], n)
            for side, mode in sides.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    args = ap.parse_args(argv)
    wl, conf, mix, _, _ = run.resolve(args.workload, False)
    devices = run.require_chips(wl["chips"])
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    for seed in args.seeds:
        cell = run.Cell(workload=args.workload, conf=conf, mix=mix, seed=seed,
                        seconds=0, trace=False, trace_dir=None, device=devices[0])
        t0 = time.monotonic()
        sides = readings(cell, time.monotonic, args.fault)
        row = {"workload": args.workload, "seed": seed}
        for side, check in sides.items():
            correct, numbers = run.verdict(conf, mix, {"check": check})
            row[side] = {"correct": correct, "gap_max": check["gap_max"],
                         "tokens_checked": check["tokens_checked"], "check": numbers}
        row["seconds"] = time.monotonic() - t0
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
