"""DiP wavefront-emulation kernel: the array's dataflow, cycle for cycle.

This kernel executes the *literal* DiP dataflow on the TPU vector unit: one
inner step per systolic wavefront.  PE row ``r`` holds permutated weight row
``p[r, :]``; the input row arrives rotated left by ``r`` (diagonal movement,
paper Fig. 2a); each step performs one rolled vector MAC:

    acc[m, i] += x[m, (i + r) % 64] * p[r, i]        r = 0..63

It is deliberately VPU-bound — it exists to demonstrate and validate the
dataflow end-to-end on real tensors (and to measure the exact vector-op cost
of diagonal movement), not to beat the MXU fast path.  Arithmetic intensity
is the same as a matmul but issued as 64 vector MACs per weight tile, so the
roofline sits at the VPU, exactly like the physical DiP array sits at its PE
throughput.

Grid: (M/bm, N/64, K/64) — one 64-wide array column-block per grid step, one
64-deep weight tile per K step (the array is 64x64; matrix tiling as in
paper Sec. IV-C).

Fused epilogues (kernels/epilogue.py) apply at the accumulator flush exactly
as in the fast-path kernel; ``swiglu`` streams the up-projection's weight
tile through a second wavefront loop over the same x block.
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
from repro.kernels.ref import acc_dtype_for

__all__ = ["dip_systolic_pallas"]


def _kernel(x_ref, p_ref, *rest, array_n: int, epilogue: str, prologue: str):
    spec = epi.spec(epilogue)
    n_pro = 2 * pro.n_operands(prologue)
    pro_refs = rest[:n_pro]
    rest = rest[n_pro:]
    extra = rest[: spec.n_operands]
    o_ref = rest[spec.n_operands]
    acc_refs = rest[spec.n_operands + 1:]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        for acc in acc_refs:
            acc[...] = jnp.zeros_like(acc)

    x = pro.kernel_load(prologue, x_ref, pro_refs)

    def sweep(p, acc0):
        def wavefront(r, acc):
            # diagonal input movement: input row rotated left by r at PE row r
            xr = common.rotate_left_dynamic(x, r, array_n)
            p_row = jax.lax.dynamic_slice_in_dim(p, r, 1, axis=0)  # stationary weights of PE row r
            return acc + xr.astype(acc.dtype) * p_row.astype(acc.dtype)

        return jax.lax.fori_loop(0, array_n, wavefront, acc0)

    acc_refs[0][...] = sweep(p_ref[...], acc_refs[0][...])
    if spec.dual_weight:  # up projection: second wavefront sweep, same x
        acc_refs[1][...] = sweep(extra[0][...], acc_refs[1][...])

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        epi.kernel_flush(epilogue, o_ref, acc_refs, extra)


@functools.partial(
    jax.jit, static_argnames=("block_m", "array_n", "interpret", "out_dtype",
                              "epilogue", "prologue", "prologue_k",
                              "prologue_eps")
)
def dip_systolic_pallas(
    x: jax.Array,
    p: jax.Array,
    *epilogue_operands: jax.Array,
    block_m: int = 128,
    array_n: int = 64,
    interpret: bool = False,
    out_dtype=None,
    epilogue: str = "none",
    prologue: str = "none",
    prologue_operands=(),
    prologue_k=None,
    prologue_eps: float = pro.DEFAULT_EPS,
):
    """``epilogue(prologue(x) @ unpermute_tiled(p))`` via explicit wavefront
    emulation.

    ``p`` is the (K, N) DiP-permutated weight with K, N multiples of
    ``array_n`` (the physical array dimension, 64 in the paper).
    ``epilogue_operands`` follow the kernels/epilogue.py contract: a second
    (K, N) weight for ``swiglu``, a (1, N) bias row, or an (M, N) residual;
    ``prologue_operands`` is the (1, K) norm gain for ``rmsnorm``.
    """
    m, kdim = x.shape
    k2, n = p.shape
    if kdim != k2:
        raise ValueError(f"contraction mismatch {x.shape} @ {p.shape}")
    if m % block_m or kdim % array_n or n % array_n:
        raise ValueError(f"unpadded shapes {x.shape} @ {p.shape}")
    spec = epi.spec(epilogue)
    epi.validate_operands(
        epilogue, epilogue_operands, m=m, n=n, w_shape=p.shape, w_dtype=p.dtype
    )
    pro_in = []
    if pro.spec(prologue).normalize:
        (gain,) = prologue_operands
        gain = gain.reshape(1, kdim)
        inv = pro.inv_rms(x, k_true=prologue_k, eps=prologue_eps)
        pro_in = [inv, gain]
        pro.validate_operands(prologue, pro_in, m=m, k=kdim)

    acc_dtype = acc_dtype_for(x, p)
    if epilogue == "none":
        out_dtype = out_dtype or (x.dtype if acc_dtype == jnp.float32 else acc_dtype)
    else:
        out_dtype = out_dtype or (
            x.dtype if jnp.issubdtype(x.dtype, jnp.floating) else jnp.float32
        )
    grid = (m // block_m, n // array_n, kdim // array_n)

    extra_in = list(epilogue_operands)
    pro_specs = pro.operand_block_specs(prologue, block_m=block_m, block_k=array_n)
    extra_specs = epi.operand_block_specs(
        epilogue, block_m=block_m, block_n=array_n, block_k=array_n
    )

    scratch = [pltpu.VMEM((block_m, array_n), acc_dtype)]
    if spec.dual_weight:
        scratch.append(pltpu.VMEM((block_m, array_n), acc_dtype))

    return pl.pallas_call(
        functools.partial(
            _kernel, array_n=array_n, epilogue=epilogue, prologue=prologue
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, array_n), lambda i, j, k: (i, k)),
            pl.BlockSpec((array_n, array_n), lambda i, j, k: (k, j)),
            *pro_specs,
            *extra_specs,
        ],
        out_specs=pl.BlockSpec((block_m, array_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, p, *pro_in, *extra_in)
