"""Shared model layers: DiP-aware linear, RMSNorm, RoPE, SwiGLU MLP.

`linear` is the integration point of the paper's technique: every dense
projection in the zoo routes through it.  The weight is either a natural
``jax.Array`` or an ``api.DipWeight`` (permutated storage + logical-shape
metadata), and the kernel choice is a registered backend name
(``cfg.matmul_backend``) resolved by ``repro.api.matmul`` — no stringly-typed
format flags or hand-threaded ``d_out`` here.
"""

from __future__ import annotations

import math
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import api

__all__ = [
    "linear",
    "resolve_constrain",
    "rms_norm",
    "swiglu",
    "rope_frequencies",
    "rope_tables",
    "yarn_mscale",
    "attention_scale",
    "apply_rope",
    "cross_entropy_loss",
]


def resolve_constrain(plan, constrain=None):
    """The one plan -> activation-constraint resolution the model stack uses.

    Models take ``plan=`` (a ``repro.distributed.ShardingPlan``) as the
    first-class way to express distribution; the legacy bare ``constrain``
    callback is still honoured when no plan is given (identity when neither
    is).  Duck-typed (anything with ``.constrain(x, tag)`` works) so the
    model layer needs no import of the distributed package.
    """
    if plan is not None:
        return plan.constrain
    return constrain if constrain is not None else (lambda x, tag: x)

_BIAS_EPILOGUES = ("bias", "bias_gelu", "bias_silu")


def linear(
    x: jax.Array,
    w: Union[jax.Array, api.DipWeight, api.QuantizedDipWeight, tuple, list],
    b: Optional[jax.Array] = None,
    *,
    backend: Optional[str] = None,
    compute_dtype=jnp.bfloat16,
    epilogue: Optional[str] = None,
    epilogue_operands=(),
    prologue: Optional[str] = None,
    prologue_operands=(),
    prologue_eps: float = 1e-5,
) -> jax.Array:
    """``epilogue(prologue(x) @ W)`` through the registered matmul backend.

    The output width comes from the weight itself (``DipWeight.d_out`` for
    permutated storage — the padding bookkeeping lives in the type).  A
    ``QuantizedDipWeight`` keeps its reduced-precision storage + scales as-is
    (only the activations take the compute dtype); with ``backend=None`` it
    dispatches straight to its scheme's quantized kernel.

    ``epilogue`` selects a fused flush-stage epilogue (``api.EPILOGUES``):
    ``"swiglu"`` takes a ``(w_gate, w_up)`` weight pair, ``"residual"``
    takes the residual through ``epilogue_operands``.  A bias ``b`` always
    rides the epilogue path — fused into the kernel flush on backends that
    support it, applied in the same f32 epilogue arithmetic otherwise — so
    there is no per-call output-sized ``b.astype`` copy on either path.

    ``prologue="rmsnorm"`` fuses the pre-projection RMSNorm into the
    kernel's x-block load (``prologue_operands=(gain,)``, ``prologue_eps``
    the norm epsilon) — same arithmetic as ``rms_norm(x, gain) @ W``, one
    kernel launch on backends that fuse it, decomposed elsewhere.
    """
    x = x.astype(compute_dtype)

    def adapt(wi):
        return wi if isinstance(wi, api.QuantizedDipWeight) else wi.astype(compute_dtype)

    w = tuple(adapt(wi) for wi in w) if isinstance(w, (tuple, list)) else adapt(w)
    operands = tuple(epilogue_operands)
    if b is not None:
        if epilogue is None:
            epilogue = "bias"
        elif epilogue not in _BIAS_EPILOGUES:
            raise ValueError(
                f"a bias only composes with the bias epilogues "
                f"{_BIAS_EPILOGUES}, got epilogue={epilogue!r}"
            )
        operands = (b,) + operands
    return api.matmul(
        x, w, backend=backend, epilogue=epilogue, epilogue_operands=operands,
        prologue=prologue, prologue_operands=tuple(prologue_operands),
        prologue_eps=prologue_eps,
    )


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(dtype)


def swiglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    return jax.nn.silu(gate) * up


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term ``0.1 mscale ln(factor) + 1``."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def attention_scale(q_dim: int, yarn=None) -> float:
    """Softmax scale ``q_dim ** -0.5``; under YaRN times
    ``yarn_mscale(factor, mscale_all_dim) ** 2`` (DeepSeek-V2)."""
    scale = q_dim ** -0.5
    if yarn is not None:
        scale *= yarn_mscale(yarn.factor, yarn.mscale_all_dim) ** 2
    return scale


def rope_frequencies(head_dim: int, theta: float, yarn=None) -> np.ndarray:
    """Inverse frequencies for rotary embeddings (host constant).

    With ``yarn`` (``configs.base.Yarn``) the frequencies follow DeepSeek-V2's
    ``yarn_find_correction_range``: those that turn fewer than ``beta_slow``
    times over the original context are divided by ``factor``, those that
    turn more than ``beta_fast`` times are kept, with a linear ramp between.
    """
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))
    if yarn is None:
        return inv

    def bound(rotations):
        return head_dim * math.log(
            yarn.original_max_position / (rotations * 2 * math.pi)
        ) / (2 * math.log(theta))

    low = max(math.floor(bound(yarn.beta_fast)), 0)
    high = min(math.ceil(bound(yarn.beta_slow)), head_dim - 1)
    high = high + 0.001 if high == low else high
    ramp = np.clip((np.arange(head_dim // 2) - low) / (high - low), 0.0, 1.0)
    return inv / yarn.factor * ramp + inv * (1.0 - ramp)


def rope_tables(positions: jax.Array, head_dim: int, theta: float, yarn=None):
    """``(cos, sin)`` rotation tables for the given absolute positions.

    Computed ONCE per forward and threaded through every layer — the angle
    table and its cos/sin are position-only, so recomputing them per layer
    (the historical ``apply_rope`` behavior) was n_layers-1 redundant
    transcendental sweeps per step.  Shapes broadcast over heads:
    (..., seq, 1, head_dim/2), float32.  ``yarn`` scales the frequencies
    (``rope_frequencies``) and cos/sin by ``m(mscale) / m(mscale_all_dim)``.
    """
    inv_freq = jnp.asarray(rope_frequencies(head_dim, theta, yarn), dtype=jnp.float32)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # (..., seq, hd/2)
    cos, sin = jnp.cos(angles)[..., None, :], jnp.sin(angles)[..., None, :]
    if yarn is not None:
        m = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    return cos, sin


def apply_rope(
    x: jax.Array, positions: jax.Array, theta: float, *, tables=None
) -> jax.Array:
    """Rotate pairs of channels; x: (..., seq, n_heads, head_dim).

    ``tables`` takes precomputed :func:`rope_tables` (the hoisted per-forward
    path); without it the tables are derived from ``positions`` on the fly —
    the original signature, kept as a thin wrapper for direct callers.
    """
    if tables is None:
        tables = rope_tables(positions, x.shape[-1], theta)
    cos, sin = tables
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def cross_entropy_loss(
    logits: jax.Array,
    labels: jax.Array,
    *,
    z_loss: float = 1e-4,
    mask: Optional[jax.Array] = None,
    ignore_index: int = -100,
) -> jax.Array:
    """Valid-token-mean cross entropy with an optional z-loss stabilizer.

    Tokens whose label equals ``ignore_index`` (the -100 convention, used
    for padding and prompt tokens) and tokens zeroed by ``mask`` are
    excluded from both the mean and the gradient; the divisor is the count
    of valid tokens, not the batch size.  With every token valid this is
    exactly the historical unmasked mean.  ``kernels.lm_head_ce`` honours
    the same contract without materializing the logits.
    """
    logits = logits.astype(jnp.float32)
    valid = labels != ignore_index
    if mask is not None:
        valid = valid & (mask != 0)
    safe = jnp.where(valid, labels, 0)  # ignore_index would be a bad gather
    logz = jax.nn.logsumexp(logits, axis=-1)
    label_logits = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    loss = logz - label_logits
    if z_loss:
        loss = loss + z_loss * jnp.square(logz)
    loss = jnp.where(valid, loss, 0.0)
    return jnp.sum(loss) / jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
