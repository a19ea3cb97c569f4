"""Faults planted under the timed path: the serving check has to find each
(bench/tests/test_faults.py on the CPU, ``bench/control.py --fault`` on the
chip)."""

from __future__ import annotations

import contextlib

import numpy as np

__all__ = ["FAULTS"]


@contextlib.contextmanager
def stale_cache():
    """The decode step hands back the KV cache it was given: the state left
    unchanged, so no generated token's keys and values are kept."""
    from repro.models import transformer as tf_model

    real = tf_model.paged_decode_step_fn

    def stale(cfg, **kw):
        step = real(cfg, **kw)
        return lambda params, cache, *a: (step(params, cache, *a)[0], cache)

    tf_model.paged_decode_step_fn = stale
    try:
        yield
    finally:
        tf_model.paged_decode_step_fn = real


@contextlib.contextmanager
def altered_token():
    """Each greedy token is altered where it is sampled."""
    from repro.serving import sampling

    real = sampling.sample_tokens

    def altered(logits, **kw):
        tok = real(logits, **kw)
        other = np.where(tok > 0, tok - 1, tok + 1)
        return np.where(kw["temperature"] <= 0, other, tok).astype(np.int32)

    sampling.sample_tokens = altered
    try:
        yield
    finally:
        sampling.sample_tokens = real


FAULTS = {"stale_cache": stale_cache, "altered_token": altered_token}
