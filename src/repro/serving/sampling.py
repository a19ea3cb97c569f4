"""Token sampling on the device (Gumbel-max): one jitted draw per row count.

The logits never leave the device: ``sample_tokens`` runs one compiled
program over the (B, V) rows and hands back only the (B,) int32 tokens.  The
Gumbel-max identity,

    argmax_i (logits_i / T + g_i),   g_i ~ Gumbel(0, 1)

draws from softmax(logits / T) exactly, so no normalized distribution is
ever sampled from.  Per-row temperature / top-k / top-p / greedy are traced
arguments, so a (rows, vocab) shape compiles once whatever the requests ask
for.  The filters and the noise sit under ``lax.cond``: a batch whose rows
are all greedy pays one argmax, and a drawing batch runs the top-k and top-p
selections only when some drawing row asks for them.

Top-k and top-p are thresholds found by a 32-step radix select over the
float32 scores' order-preserving uint32 keys (exact, no sort): top-k keeps
every score at or above the row's k-th largest, top-p every score whose
strictly larger neighbours hold less than p of the (top-k-filtered) mass.
**Tie rule:** every token tied with the boundary score is kept, so exact
ties there can keep more than k tokens or more than the shortest prefix (a
stable sort would keep the lowest ids only).

Randomness comes in per row, so callers control determinism: either a
(B, V) array of uniforms (the legacy server draws them from one host
generator), or ``RowSeeds``, from which the program makes the uniforms on
the device (``device_uniforms``): row i's stream is ``jax.random`` keyed by
its request's seed, folded with the index of the token it draws.  A
request's tokens then depend only on its seed, never on its slot, its
batch-mates or a preemption.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = ["sample_tokens", "gumbel_from_uniform", "device_uniforms", "RowSeeds",
           "row_filters"]

_EPS = np.float32(1e-20)
# largest float32 strictly below 1.0: float64's nextafter(1.0, 0.0) rounds to
# exactly 1.0 in float32, and a boundary uniform of 1.0 gives
# -log(-log(1.0)) = +inf noise that hijacks the argmax
_ONE_BELOW = np.nextafter(np.float32(1.0), np.float32(0.0))
_WORD = 0xFFFFFFFF


class RowSeeds(NamedTuple):
    """Per-row randomness made on the device: each row's request seed (any
    integer; both 32-bit words of a 64-bit seed count) and the index of the
    token it draws."""

    seeds: Any       # (B,) ints
    counters: Any    # (B,) ints


def gumbel_from_uniform(u) -> jax.Array:
    """Standard Gumbel(0,1) noise, float32, from uniforms in [0, 1)."""
    u = jnp.clip(jnp.asarray(u, jnp.float32), _EPS, _ONE_BELOW)
    return -jnp.log(-jnp.log(u))


def _seed_words(seeds) -> np.ndarray:
    """(B,) integer seeds -> (B, 2) uint32 [high, low] words, the key data
    ``jax.random.key`` makes of a 64-bit seed."""
    return np.array([((s >> 32) & _WORD, s & _WORD) for s in map(int, seeds)],
                    np.uint32).reshape(-1, 2)


def _uniforms(words, counters, vocab: int) -> jax.Array:
    def row(w, c):
        key = jax.random.wrap_key_data(w, impl="threefry2x32")
        return jax.random.uniform(jax.random.fold_in(key, c), (vocab,), jnp.float32)

    return jax.vmap(row)(words, counters.astype(jnp.uint32))


_uniforms_jit = jax.jit(_uniforms, static_argnums=2)


def device_uniforms(seeds, counters, vocab: int) -> jax.Array:
    """(B, vocab) float32 uniforms in [0, 1) on the device: row i from
    ``fold_in(jax.random.key(seeds[i]), counters[i])`` (for a seed wider
    than 32 bits, the key holds both words)."""
    return _uniforms_jit(_seed_words(seeds), jnp.asarray(counters, jnp.uint32), vocab)


def row_filters(temperature, top_k, top_p, vocab: int):
    """(draws, top-k on, top-p on) per row, for numpy or jax arrays: a row
    draws at T > 0, and only a drawing row filters."""
    draw = temperature > 0
    return draw, draw & (top_k > 0) & (top_k < vocab), draw & (top_p < 1.0)


def _keys(x):
    """float32 -> uint32 with the same order (-0.0 just below +0.0)."""
    b = lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(1 << 31))


def _select(keys, weight, need):
    """Per row, the largest threshold t with sum(weight[keys >= t]) >= need
    (0, keeping everything, where no t reaches it), set bit by bit from the
    top."""
    def bit(i, t):
        cand = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        mass = jnp.sum(jnp.where(keys >= cand[:, None], weight, 0), -1)
        return jnp.where(mass >= need, cand, t)

    return lax.fori_loop(0, 32, bit, jnp.zeros(keys.shape[0], jnp.uint32))


def _keep(scaled, temperature, top_k, top_p):
    """Mask of the tokens each drawing row may take: top-k, then top-p over
    the survivors' renormalized mass."""
    _, on_k, on_p = row_filters(temperature, top_k, top_p, scaled.shape[-1])
    keys = _keys(scaled)

    def top_k_mask():
        t = _select(keys, jnp.int32(1), top_k)
        return ~on_k[:, None] | (keys >= t[:, None])

    def top_p_mask(keep):
        masked = jnp.where(keep, scaled, -jnp.inf)
        e = jnp.exp(masked - masked.max(-1, keepdims=True))
        t = _select(keys, e / e.sum(-1, keepdims=True), top_p)
        return keep & (~on_p[:, None] | (keys >= t[:, None]))

    everything = jnp.ones(scaled.shape, bool)
    keep = lax.cond(on_k.any(), top_k_mask, lambda: everything)
    return lax.cond(on_p.any(), top_p_mask, lambda k: k, keep)


def _sample(logits, temperature, top_k, top_p, noise):
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, -1).astype(jnp.int32)
    draw = temperature > 0

    def drawn():
        scaled = logits / jnp.where(draw, temperature, 1.0)[:, None]
        keep = _keep(scaled, temperature, top_k, top_p)
        noisy = jnp.where(keep, scaled, -jnp.inf) + noise()
        return jnp.where(draw, jnp.argmax(noisy, -1).astype(jnp.int32), greedy)

    return lax.cond(draw.any(), drawn, lambda: greedy)


@jax.jit
def _sample_uniforms(logits, temperature, top_k, top_p, uniforms):
    return _sample(logits, temperature, top_k, top_p,
                   lambda: gumbel_from_uniform(uniforms))


@jax.jit
def _sample_seeded(logits, temperature, top_k, top_p, words, counters):
    vocab = logits.shape[-1]
    return _sample(logits, temperature, top_k, top_p,
                   lambda: gumbel_from_uniform(_uniforms(words, counters, vocab)))


def sample_tokens(
    logits,                      # (B, V) float, numpy or on the device
    *,
    temperature: np.ndarray,     # (B,) — rows with T <= 0 decode greedily
    top_k: np.ndarray,           # (B,) int — 0 disables
    top_p: np.ndarray,           # (B,) float — 1.0 disables
    uniforms,                    # (B, V) in [0, 1), or RowSeeds
) -> np.ndarray:
    """Draw one token per row on the device; returns (B,) int32 on the host.

    Greedy rows (temperature <= 0) take ``argmax`` of the raw logits and
    ignore top-k/top-p/noise entirely, so a greedy request is bit-stable
    regardless of the randomness supplied for its row.
    """
    params = (jnp.asarray(temperature, jnp.float32), jnp.asarray(top_k, jnp.int32),
              jnp.asarray(top_p, jnp.float32))
    if isinstance(uniforms, RowSeeds):
        tok = _sample_seeded(logits, *params, _seed_words(uniforms.seeds),
                             jnp.asarray(uniforms.counters, jnp.uint32))
    else:
        tok = _sample_uniforms(logits, *params, uniforms)
    return np.asarray(tok)
