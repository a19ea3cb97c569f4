"""Serving cells: the program's ``serving.Engine`` driven by seeded traffic.

``serve_open``: requests arrive on their schedule (an open loop), whether or
not the engine keeps up.  Each latency runs from the request's scheduled
arrival, so a stall that delays admission shows.  A warm period of the same
traffic runs before the window opens.  After the window closes the engine
keeps serving until every request due in the window has its first token
(``drain_max_s`` at most; one that never gets it has failed).

``serve_backlog``: a queue that never empties; the window opens once every
slot has been filled.

Past the close, both serve on (``drain_max_s`` at most) until the check's
sample of finished greedy requests is there to read.

Correctness: once the window has closed and the program's state is freed,
the float32 reference runs over a seeded sample of finished greedy requests,
the longest among them, and reads at every served position how far the
served token's logit lies below the reference's best (``gap_mean``: a wrong
token shows) and how far the program's own logit of that token lies from
the reference's (``logit_err_mean``: a numeric departure shows even where
it flips no token).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, NamedTuple

import numpy as np

from bench import gen, program, reference

__all__ = ["run"]


class Tick(NamedTuple):
    """One ``Engine.step()`` call: its host span, the decode steps and
    prefill chunks it ran, and the prompt position its chunk started at."""
    start: float
    end: float
    decode: int
    prefill: int
    chunk_offset: int


@dataclasses.dataclass
class Served:
    req: gen.Request
    due: float                       # absolute due time (host clock)
    added: float = 0.0
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    logits: List[float] = dataclasses.field(default_factory=list)   # the program's, of each token
    done: bool = False


def _engine(cell, params):
    from repro.serving import Engine, EngineConfig

    e = cell.mix["engine"]
    return Engine(cell.cfg, params, engine_cfg=EngineConfig(
        slots=e["slots"], max_seq=e["max_seq"], prefill_chunk=e["prefill_chunk"],
        eos_id=-1))


def _add(engine, s: Served, clock) -> None:
    from repro.serving import SamplingParams

    r = s.req

    def on_token(rid, tok, done):
        s.times.append(clock())
        s.tokens.append(int(tok))
        s.done = done

    s.added = clock()
    engine.add_request(r.prompt, SamplingParams(
        temperature=r.temperature, top_p=r.top_p, max_new_tokens=r.max_new,
        seed=r.seed), rid=r.index, on_token=on_token)


def _compile(engine, mix, clock) -> None:
    """One short request through every program the traffic uses: chunked
    prefill, the KV import and the decode step (one shape each)."""
    n = mix["engine"]["prefill_chunk"] + 1             # two prefill chunks
    s = Served(gen.Request(index=1 << 40, due_s=0.0, prompt=np.full(n, 2, np.int32),
                           max_new=3, greedy=True), due=clock())
    _add(engine, s, clock)
    while engine.step():
        pass


def run(cell, clock, params=None, check: bool = True) -> dict:
    """Set-up, window and check of one serving run; returns the raw record
    that the metric readers and the result line are made from.  ``params``
    (already loaded) and ``check=False`` serve the knee sweep."""
    mix = cell.mix
    if params is None:
        params = program.load_params(cell.cfg, cell.dims, cell.key)
    engine = _engine(cell, params)
    logits = program.record_served_logits(engine)
    del params
    _compile(engine, mix, clock)

    stream = gen.requests(mix, cell.seed, cell.dims.vocab)
    served: Dict[int, Served] = {}
    ticks: List[Tick] = []
    open_loop = mix["kind"] == "serve_open"
    base = clock()                                     # the traffic's time 0
    pending = next(stream)

    def admit(now, base):
        nonlocal pending
        while base + pending.due_s <= now:
            s = Served(pending, due=base + pending.due_s)
            _add(engine, s, clock)
            served[pending.index] = s
            pending = next(stream)

    def tick():
        c0 = program.engine_counters(engine)
        offset = program.next_chunk_offset(engine)
        t0 = clock()
        with cell.span("bench.engine_step"):
            busy = engine.step()
        t1 = clock()
        c1 = program.engine_counters(engine)
        ticks.append(Tick(t0, t1, c1["decode_steps"] - c0["decode_steps"],
                          c1["prefill_chunks"] - c0["prefill_chunks"], offset))
        return busy

    if open_loop:
        t_open = base + mix["warm_s"]
    else:
        for _ in range(mix["queue"]):                  # all due at time 0
            s = Served(pending, due=base)
            _add(engine, s, clock)
            served[pending.index] = s
            pending = next(stream)
        slots = mix["engine"]["slots"]
        while sum(1 for s in served.values() if s.times) < slots:
            tick()
        t_open = clock()
    t_close = t_open + cell.seconds
    cell.window_opened(t_open)

    drain_end = t_close + mix["drain_max_s"]
    n_check = mix["check"]["requests"] if check else 0
    while True:
        now = clock()
        cell.window_tick(now)
        if open_loop:
            admit(now, base)
        if now >= t_close:
            # past the close: serve on until every request due in the window
            # has its first token and the check has its sample to read
            due_in = [s for s in served.values() if t_open <= s.due < t_close]
            finished = sum(1 for s in served.values() if s.done and s.req.greedy)
            if ((all(s.times for s in due_in) and finished >= n_check)
                    or now >= drain_end):
                break
        if any(not s.done for s in served.values()):
            tick()
        elif open_loop:
            time.sleep(max(0.0, min(0.005, base + pending.due_s - now)))
        else:
            break
    t_end = clock()
    cell.window_closed()
    memory_peak = cell.memory_peak()
    del engine

    for s in served.values():
        s.logits = logits.get(s.req.index, [])
    rec = dict(kind=mix["kind"], t_open=t_open, t_close=t_close, t_end=t_end,
               served=served, ticks=ticks, memory_peak=memory_peak)
    due_in = [s for s in served.values() if t_open <= s.due < t_close]
    if open_loop:
        rec["attempted"] = len(due_in)
        rec["failed"] = sum(1 for s in due_in if not s.times)
    else:
        rec["attempted"] = sum(1 for s in served.values()
                               if any(t_open <= t < t_close for t in s.times))
        rec["failed"] = 0
    late = [s.added - s.due for s in served.values() if s.added]
    rec["notes"] = {"requests_due_in_window": len(due_in),
                    "generator_late_mean_s": float(np.mean(late)),
                    "generator_late_max_s": float(np.max(late)),
                    "queue_at_close": sum(1 for s in served.values()
                                          if s.due < t_close and not s.times)}
    if check:
        rec["check"] = _check(cell, served, t_close)
    return rec


def sample(cell, served) -> list:
    """The requests the check reads: finished greedy ones, the longest among
    them and the rest drawn from the seed, ``check.requests`` in all."""
    done = sorted((s for s in served
                   if s.done and s.req.greedy and len(s.tokens) == s.req.max_new),
                  key=lambda s: s.req.index)
    if not done:
        return []
    longest = max(done, key=lambda s: s.req.prompt.size + len(s.tokens))
    rest = [s for s in done if s is not longest]
    rng = np.random.default_rng([int(cell.seed) % (1 << 63), 7])
    n = cell.mix["check"]["requests"]
    return [longest] + [rest[i] for i in rng.permutation(len(rest))[: n - 1]]


def _check(cell, served: Dict[int, Served], t_close: float) -> dict:
    """The check's numbers over its sample (``compare``)."""
    pick = sample(cell, served.values())
    return numbers([compare(cell, s.req.prompt, s.tokens, s.logits) for s in pick],
                   cell.mix["check"]["requests"])


def numbers(compared: list, n: int) -> dict:
    """``gap_mean``: the mean gap of a judged token below the reference's
    best; ``logit_err_mean``: the mean distance of the judged side's logit
    of each served token from the reference's; ``unchecked``: the sample's
    shortfall.  ``gap_max`` is reported, not compared."""
    if not compared:
        return {"gap_max": 1e9, "gap_mean": 1e9, "logit_err_mean": 1e9, "unchecked": n}
    gaps = np.concatenate([g for g, _ in compared])
    errs = np.concatenate([e for _, e in compared])
    return {"gap_max": float(gaps.max()), "gap_mean": float(gaps.mean()),
            "logit_err_mean": float(errs.mean()), "unchecked": n - len(compared),
            "tokens_checked": int(gaps.size)}


def compare(cell, prompt, tokens, logits=None, mode: str = "f32") -> tuple:
    """At each served position, against the float32 reference: the gap of
    the judged token below the reference's best logit, and how far the
    judged side's logit of the served token lies from the reference's.  With
    ``mode="f32"`` the program is judged (its served tokens, ``logits``
    its logits of them); otherwise the reference computed in that
    precision stands in its place: its first choice, its logits."""
    seq = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
    rows = np.arange(prompt.size - 1, seq.size)
    at = np.arange(rows.size)
    tokens = np.asarray(tokens)
    pad = cell.mix["engine"]["max_seq"]
    ref = reference.logits_at(cell.key, cell.dims, seq, rows, pad_to=pad)
    if mode == "f32":
        chosen, own = tokens, np.asarray(logits, np.float32)
    else:
        low = reference.logits_at(cell.key, cell.dims, seq, rows, mode=mode, pad_to=pad)
        chosen, own = low.argmax(-1), low[at, tokens]
    return ref.max(-1) - ref[at, chosen], np.abs(own - ref[at, tokens])
