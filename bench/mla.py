#!/usr/bin/env python3
"""Serving cells of the MLA + MoE family (``mla_backlog``): DeepSeek-V2's
latent attention and routed experts through the program's ``Engine``.

The run is ``bench/serve.py``'s backlog: a queue that never empties, the
window opened once every slot has been filled.  What differs is the model:
the weights come from ``bench/weights_mla_moe.py`` and are loaded into the
program's storage here (``api.DipWeight.from_natural``, as
``bench/program.py`` does for the dense pair), and the check runs the
benchmark's own float32 reference of this family
(``bench/reference_mla_moe.py``) over the same sample of finished greedy
requests, with the same numbers (``serve.sample``, ``serve.numbers``).
The engine's MoE counters (``moe_routed``, ``moe_rows``, ``moe_dropped``)
are read at every turn of the serving loop, for the readers of the
window's routing; ``moe_dropped`` over the whole run is one of the
check's numbers, with limit 0.  The program's logit of each served token
is read in one gather per draw (``_record_served_logits``).

Readings that the check's limits are set from, on the chip:

    python3 bench/mla.py --workload deepseek_v2_lite_16b.longdoc_backlog --seeds 1 2 3

For each seed, the check's sample of the traffic's first queue (the
longest prompt among them and the rest drawn from the seed), each prompt
followed by seeded tokens as long as its output: the int8 control
(``reference_mla_moe`` mode "int8") judged against the float32 reference
at every output position.  The program's own readings are those of its
benchmark runs.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import weakref
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import gen, program, serve  # noqa: E402
from bench import reference_mla_moe as reference  # noqa: E402
from bench import weights_mla_moe as W  # noqa: E402

__all__ = ["run", "load_params", "MOE_COUNTERS"]

MOE_COUNTERS = ("moe_routed", "moe_rows", "moe_dropped")
_LOADERS: dict = {}


def _loader(cfg, dims: W.Dims):
    from repro import api
    from repro.models import transformer as tf_model

    template = tf_model.param_template(cfg)

    def store(name, nat, leaf):
        shape, dt = leaf[0], jnp.dtype(leaf[1])
        dip = leaf[3] if len(leaf) > 3 else None
        w = api.DipWeight.from_natural(nat.astype(dt), dip[2]) if dip else nat.astype(dt)
        got = w.data.shape if dip else w.shape
        if tuple(got) != tuple(shape):
            raise ValueError(f"{name}: made {got}, the program stores {shape}")
        return w

    def stacked(tree, made):
        return {nm: store(nm, jnp.stack([m[nm] for m in made]), leaf)
                for nm, leaf in tree.items()}

    def pad(a, axis, to):
        widths = [(0, 0)] * a.ndim
        widths[axis] = (0, to - a.shape[axis])
        return jnp.pad(a, widths)

    @jax.jit
    def load(key):
        kd, vp = dims.dense_layers, cfg.padded_vocab
        ffn = [W.ffn_weights(key, dims, i, i < kd) for i in range(dims.layers)]
        return {
            "embed": store("embed", pad(W.embed(key, dims), 0, vp), template["embed"]),
            "final_norm": store("final_norm", W.final_norm(key, dims), template["final_norm"]),
            "lm_head": store("lm_head", pad(W.head(key, dims), 1, vp), template["lm_head"]),
            "layers": stacked(template["layers"], [W.attention_weights(key, dims, i)
                                                   for i in range(dims.layers)]),
            "dense": stacked(template["dense"], ffn[:kd]),
            "moe": stacked(template["moe"], ffn[kd:]),
        }

    return load


def load_params(cfg, dims: W.Dims, key):
    """The program's parameter tree holding the benchmark's weights, made on
    the device in one jitted call."""
    k = (cfg, dims.frozen())
    if k not in _LOADERS:
        _LOADERS[k] = _loader(cfg, dims)
    return _LOADERS[k](key)


_take = jax.jit(lambda logits, toks: jnp.take_along_axis(logits, toks[:, None], axis=1)[:, 0])


def _record_served_logits(engine) -> dict:
    """``program.record_served_logits`` with one gather and one read per
    draw, in place of one device read per live row (about 1.5 ms each on
    the chip's host, PERF.md §7): the same logits, read in one copy."""
    served = {}
    real, owner = type(engine)._sample_rows, weakref.ref(engine)

    def sample_rows(logits, reqs):
        toks = real(owner(), logits, reqs)
        vals = np.asarray(_take(logits, jnp.asarray(toks, jnp.int32)))
        for i, r in enumerate(reqs):
            if r is not None:
                served.setdefault(r.rid, []).append(float(vals[i]))
        return toks

    engine._sample_rows = sample_rows
    return served


class _Watch:
    """The cell, as ``serve.run`` drives it, with the engine's MoE counters
    read at each turn of its loop: ``series`` holds (host time, routed,
    rows, dropped) from the window's opening to its end."""

    def __init__(self, cell, engines, clock):
        self._cell, self._engines, self._clock = cell, engines, clock
        self.series = []

    def __getattr__(self, name):
        return getattr(self._cell, name)

    def _sample(self, now):
        engine = self._engines[-1]() if self._engines else None
        if engine is not None:
            self.series.append((now,) + tuple(
                getattr(engine, "_" + c, 0) for c in MOE_COUNTERS))

    def window_opened(self, now):
        self._cell.window_opened(now)
        self._sample(now)

    def window_tick(self, now):
        self._sample(now)
        self._cell.window_tick(now)

    def window_closed(self):
        self._sample(self._clock())
        self._cell.window_closed()


def run(cell, clock) -> dict:
    """Set-up, window and check of one run; the raw record the readers and
    the result line are made from (``serve.run``'s, with ``moe``: the
    counter series)."""
    dims = W.Dims(cell.conf)
    engines = []
    real = serve._engine

    def engine(c, params):
        e = real(c, params)
        engines.append(weakref.ref(e))
        return e

    watch = _Watch(cell, engines, clock)
    record = program.record_served_logits
    serve._engine, program.record_served_logits = engine, _record_served_logits
    try:
        rec = serve.run(watch, clock, params=load_params(cell.cfg, dims, cell.key),
                        check=False)
    finally:
        serve._engine, program.record_served_logits = real, record
    rec["moe"] = watch.series
    dropped = watch.series[-1][3] if watch.series else 0
    rec["notes"].update(zip(MOE_COUNTERS, watch.series[-1][1:] if watch.series else ()))
    pick = serve.sample(cell, rec["served"].values())
    rec["check"] = serve.numbers(
        [compare(cell, dims, s.req.prompt, s.tokens, s.logits) for s in pick],
        cell.mix["check"]["requests"])
    rec["check"]["moe_dropped"] = dropped
    return rec


def compare(cell, dims, prompt, tokens, logits=None, mode: str = "f32") -> tuple:
    """``serve.compare`` against this family's reference: at each served
    position, the gap of the judged token below the reference's best logit
    and how far the judged side's logit of the served token lies from the
    reference's.  ``mode="f32"`` judges the program (its tokens, ``logits``
    its logits of them); another mode judges the reference computed in that
    precision."""
    seq = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
    rows = np.arange(prompt.size - 1, seq.size)
    at = np.arange(rows.size)
    tokens = np.asarray(tokens)
    pad = cell.mix["engine"]["max_seq"]
    ref = reference.logits_at(cell.key, dims, seq, rows, pad_to=pad)
    if mode == "f32":
        chosen, own = tokens, np.asarray(logits, np.float32)
    else:
        low = reference.logits_at(cell.key, dims, seq, rows, mode=mode, pad_to=pad)
        chosen, own = low.argmax(-1), low[at, tokens]
    return ref.max(-1) - ref[at, chosen], np.abs(own - ref[at, tokens])


def control(cell) -> dict:
    """The check's numbers of the int8 control over the check's sample of
    the traffic's first queue, each prompt followed by seeded tokens."""
    dims = W.Dims(cell.conf)
    stream = gen.requests(cell.mix, cell.seed, dims.vocab)
    rng = np.random.default_rng([int(cell.seed) % (1 << 63), 11])
    served = []
    for _ in range(cell.mix["queue"]):
        r = next(stream)
        s = serve.Served(r, due=0.0, done=True)
        s.tokens = list(rng.integers(2, dims.vocab, r.max_new))
        served.append(s)
    pick = serve.sample(cell, served)
    return serve.numbers([compare(cell, dims, s.req.prompt, s.tokens, mode="int8")
                          for s in pick], cell.mix["check"]["requests"])


def main(argv=None) -> int:
    from bench import run as bench_run

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    wl, conf, mix, _, _ = bench_run.resolve(args.workload, False)
    devices = bench_run.require_chips(wl["chips"])
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    for seed in args.seeds:
        cell = bench_run.Cell(workload=args.workload, conf=conf, mix=mix, seed=seed,
                              seconds=0, trace=False, trace_dir=None, device=devices[0])
        t0 = time.monotonic()
        check = dict(control(cell), moe_dropped=0)
        correct, numbers = bench_run.verdict(conf, mix, {"check": check})
        print(json.dumps({"workload": args.workload, "seed": seed, "control": {
            "correct": correct, "gap_max": check["gap_max"],
            "tokens_checked": check["tokens_checked"], "check": numbers},
            "seconds": time.monotonic() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
