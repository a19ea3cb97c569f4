"""One generator for every serving mix: requests from a mix's parameters
(``bench/traffic/<mix>.json``) and the run's seed.

Plain seeded draws: exponential arrival gaps at ``rate_per_s`` (a Poisson
process; without a rate every request is due at 0, a backlog), prompt and
output lengths from their distributions clipped to their ranges, and each
request greedy with probability ``greedy_share``.  The same seed gives the
same requests.

A mix with ``block`` takes its lengths not by draws but as the ``block``
evenly spaced quantiles of each distribution, shuffled by the seed afresh
for every block: each aligned block of requests holds the same sizes
whatever the seed, so that seeds change the order and not the work (for a
backlog, whose window otherwise measures which sizes the seed drew).
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Iterator

import numpy as np

__all__ = ["Request", "requests", "draw_length", "quantile_length"]


@dataclasses.dataclass
class Request:
    index: int
    due_s: float                    # offset from the start of the traffic
    prompt: np.ndarray              # int32 token ids
    max_new: int
    greedy: bool
    temperature: float = 0.0
    top_p: float = 1.0
    seed: int = 0


def draw_length(spec: dict, rng: np.random.Generator) -> int:
    """One draw of a length distribution, clipped to its range."""
    lo, hi = spec["min"], spec["max"]
    kind = spec["dist"]
    if kind == "lognormal":
        v = spec["median"] * math.exp(spec["sigma"] * rng.standard_normal())
    elif kind == "loguniform":
        v = math.exp(rng.uniform(math.log(lo), math.log(hi)))
    elif kind == "uniform":
        v = rng.uniform(lo, hi)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return int(min(hi, max(lo, round(v))))


def quantile_length(spec: dict, q: float) -> int:
    """The ``q`` quantile of a length distribution, clipped to its range."""
    lo, hi = spec["min"], spec["max"]
    kind = spec["dist"]
    if kind == "lognormal":
        v = spec["median"] * math.exp(spec["sigma"] * NormalDist().inv_cdf(q))
    elif kind == "loguniform":
        v = math.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
    elif kind == "uniform":
        v = lo + q * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return int(min(hi, max(lo, round(v))))


def requests(mix: dict, seed: int, vocab: int) -> Iterator[Request]:
    """Endless seeded request stream of a serving mix."""
    rng = np.random.default_rng(int(seed) % (1 << 64))
    rate = mix.get("rate_per_s")
    share = mix.get("greedy_share", 1.0)
    sampled = mix.get("sampled", {})
    block = mix.get("block")
    if block:
        qs = (np.arange(block) + 0.5) / block
        sizes = [[quantile_length(mix[k], q) for q in qs]
                 for k in ("prompt_tokens", "output_tokens")]
    t, i = 0.0, 0
    while True:
        if rate:
            t += float(rng.exponential(1.0 / rate))
        if block:
            if i % block == 0:
                order = [rng.permutation(v) for v in sizes]
            n, max_new = (int(v[i % block]) for v in order)
        else:
            n = draw_length(mix["prompt_tokens"], rng)
            max_new = draw_length(mix["output_tokens"], rng)
        g = bool(rng.random() < share)
        yield Request(
            index=i, due_s=t,
            prompt=rng.integers(2, vocab, size=n).astype(np.int32),
            max_new=max_new, greedy=g,
            temperature=0.0 if g else float(sampled["temperature"]),
            top_p=1.0 if g else float(sampled.get("top_p", 1.0)),
            seed=int(rng.integers(1 << 31)))
        i += 1
