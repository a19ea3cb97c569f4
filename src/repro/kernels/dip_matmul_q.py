"""Quantized DiP matmul kernels: reduced-precision permutated weights.

Two precisions over the same block structure as kernels/dip_matmul.py
(grid = (M/bm, N/bn, K/bk), K innermost, de-shear fused in VMEM):

``int8`` (the paper's own PE datatype, DiP Table 3; ADiP's headline regime)
    W8A8-dynamic: activations are quantized per-row to int8 on the way in
    (``ref.quantize_acts_int8`` — one cheap jnp reduction over K), weights
    arrive as per-column-scaled int8 permutated storage.  The MXU loop
    accumulates **exactly** in int32; the epilogue applies the rank-1 scale
    ``x_scale[m] * w_scale[n]`` once per output block — so the only
    approximation in the whole pipeline is the two quantization roundings.

``fp8`` (e4m3 storage)
    Weight-only: fp8 storage is upcast at block load, de-sheared, and fed to
    the MXU with f32 accumulation; the per-column scale is fused on output.
    The upcast width is gated on device support (:func:`fp8_compute_dtype`):
    bf16 on hardware with native fp8/bf16 MXU paths, f32 as the emulated
    fallback everywhere else (CPU interpret mode, older TPUs).

Scale operands ride through the grid as (M, 1) / (1, N) blocks so the
epilogue reads one sublane/lane vector — no extra VMEM pressure.

Fused epilogues (kernels/epilogue.py) compose AFTER the scale-on-output: the
flush computes ``z = acc * x_scale * w_scale`` in f32 and applies bias /
activation / residual to ``z`` before the single output cast.  ``swiglu``
streams a second quantized weight (its own per-column scales) over the same
activation block — both gate and up consume the SAME quantized-activation
block, so the int8 path quantizes x exactly once for the pair.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common
from repro.kernels import epilogue as epi
from repro.kernels import prologue as pro
from repro.kernels.ref import quantize_acts_int8

__all__ = ["dip_matmul_q_pallas", "fp8_compute_dtype", "fp8_native_supported"]


def fp8_native_supported() -> bool:
    """Whether this device has a native reduced-precision MXU path for fp8
    operands (TPU v5+ / GPU).  CPU interpret mode always emulates."""
    backend = jax.default_backend()
    if backend == "gpu":
        return True
    if backend == "tpu":
        kind = jax.devices()[0].device_kind.lower()
        return any(tag in kind for tag in ("v5", "v6", "v7"))
    return False


def fp8_compute_dtype():
    """Width fp8 storage is upcast to inside the kernel: bf16 where the MXU
    consumes it natively at reduced cost, f32 for the emulated fallback."""
    return jnp.bfloat16 if fp8_native_supported() else jnp.float32


def _kernel(x_ref, p_ref, xs_ref, ws_ref, *rest, perm_tile: int,
            upcast_dtype, epilogue: str):
    spec = epi.spec(epilogue)
    n_extra = 2 if spec.dual_weight else spec.n_operands
    extra = rest[:n_extra]
    o_ref = rest[n_extra]
    acc_refs = rest[n_extra + 1:]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        for acc in acc_refs:
            acc[...] = jnp.zeros_like(acc)

    def deshear(w):
        if upcast_dtype is not None:  # fp8 path: widen before the vector de-shear
            w = w.astype(upcast_dtype)
        return common.deshear_block(w, perm_tile)

    x = x_ref[...]
    acc_refs[0][...] += jnp.dot(
        x, deshear(p_ref[...]), preferred_element_type=acc_refs[0].dtype
    )
    if spec.dual_weight:  # up projection over the SAME (already quantized) x
        acc_refs[1][...] += jnp.dot(
            x, deshear(extra[0][...]), preferred_element_type=acc_refs[1].dtype
        )

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        xs = xs_ref[...]
        z = acc_refs[0][...].astype(jnp.float32) * xs * ws_ref[...]
        if epilogue == "none":
            o_ref[...] = z.astype(o_ref.dtype)
        else:
            if spec.dual_weight:  # extra = (q_up, ws_up)
                aux = (acc_refs[1][...].astype(jnp.float32) * xs * extra[1][...],)
            else:
                aux = tuple(op[...].astype(jnp.float32) for op in extra)
            o_ref[...] = epi.apply(epilogue, z, *aux).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "perm_tile", "interpret",
                     "out_dtype", "epilogue", "prologue", "prologue_k",
                     "prologue_eps"),
)
def dip_matmul_q_pallas(
    x: jax.Array,
    q: jax.Array,
    w_scale: jax.Array,
    *epilogue_operands: jax.Array,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 256,
    perm_tile: int = 64,
    interpret: bool = False,
    out_dtype=None,
    epilogue: str = "none",
    prologue: str = "none",
    prologue_operands=(),
    prologue_k=None,
    prologue_eps: float = pro.DEFAULT_EPS,
):
    """``epilogue(x @ dequant(unpermute_tiled(q)))`` with quantized arithmetic.

    ``x``: (M, K) float activations; ``q``: (K, N) quantized DiP-permutated
    storage (int8 or fp8 e4m3); ``w_scale``: (1, N) f32 per-output-channel
    scales.  Shapes must already be padded to block multiples (the registry
    dispatch shim handles padding).  int8 storage selects the W8A8 int32
    path; fp8 the weight-only upcast path (module doc).
    ``epilogue_operands``: ``(q_up, w_scale_up)`` for ``swiglu`` (a second
    quantized weight + its scales), ``(b,)`` (1, N) for the bias variants,
    ``(r,)`` (M, N) for ``residual``.
    """
    m, kdim = x.shape
    k2, n = q.shape
    if kdim != k2:
        raise ValueError(f"contraction mismatch {x.shape} @ {q.shape}")
    if w_scale.shape != (1, n):
        raise ValueError(
            f"w_scale must be (1, {n}) per-output-channel, got {w_scale.shape}"
        )
    if m % block_m or kdim % block_k or n % block_n:
        raise ValueError(f"unpadded shapes {x.shape} @ {q.shape} for blocks "
                         f"({block_m},{block_k},{block_n})")
    if block_k % perm_tile or block_n % perm_tile:
        raise ValueError("block_k/block_n must be multiples of the permutation tile")
    spec = epi.spec(epilogue)
    epi.validate_operands(
        epilogue, epilogue_operands, m=m, n=n, w_shape=q.shape,
        w_dtype=q.dtype, with_scales=True,
    )
    if pro.spec(prologue).normalize:
        # The quantized kernels' load stage IS the activation quantization,
        # which happens here in the wrapper (one jnp pass over x).  The
        # RMSNorm folds into that same pass — x is normalized before the
        # per-row amax/rounding so the int8/fp8 operands carry the
        # normalized values, and the dispatch stays ONE pallas launch.
        (gain,) = prologue_operands
        x = pro.apply(prologue, x, gain.reshape(-1),
                      k_true=prologue_k, eps=prologue_eps)

    int_path = jnp.issubdtype(q.dtype, jnp.integer)
    if int_path:
        if q.dtype != jnp.int8:
            raise ValueError(f"integer storage must be int8, got {q.dtype}")
        xk, x_scale = quantize_acts_int8(x)
        acc_dtype, upcast = jnp.int32, None
    else:
        upcast = fp8_compute_dtype()
        xk = x.astype(upcast)
        x_scale = jnp.ones((m, 1), jnp.float32)
        acc_dtype = jnp.float32
    out_dtype = out_dtype or (
        x.dtype if jnp.issubdtype(x.dtype, jnp.floating) else jnp.float32
    )
    w_scale = w_scale.astype(jnp.float32)
    grid = (m // block_m, n // block_n, kdim // block_k)

    extra_in = list(epilogue_operands)
    if spec.dual_weight:  # the up scales ride f32 like the gate scales
        extra_in[1] = extra_in[1].astype(jnp.float32)
    extra_specs = epi.operand_block_specs(
        epilogue, block_m=block_m, block_n=block_n, block_k=block_k,
        with_scales=True,
    )

    scratch = [pltpu.VMEM((block_m, block_n), acc_dtype)]
    if spec.dual_weight:
        scratch.append(pltpu.VMEM((block_m, block_n), acc_dtype))

    return pl.pallas_call(
        functools.partial(
            _kernel, perm_tile=perm_tile, upcast_dtype=upcast, epilogue=epilogue
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((block_m, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
            *extra_specs,
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xk, q, x_scale, w_scale, *extra_in)
