"""Shared in-kernel helpers for the DiP Pallas kernels.

TPU adaptation note (DESIGN.md §2): the DiP permutation shifts each column of
a 64x64 tile up by its column index.  A per-column variable rotate has no
single TPU vector op, but it decomposes into log2(tile) *static* sublane
rolls combined with column-mask selects — a classic barrel shifter.  Static
rolls and selects are cheap Mosaic ops, so the de-shear costs
O(log2(tile) * tile * bn) vector work per weight block, amortized against
O(bm * tile * bn) MXU work.

Mosaic rotates 32-bit vectors only, so narrower blocks (bf16, int8, fp8)
are widened to 32 bits for the shear and narrowed back afterwards — exact,
since every narrow value is representable in the wide type — and the MXU
still receives operands of the storage width.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

__all__ = ["shard_map", "deshear_block", "shear_block", "rotate_left_dynamic"]

# Every sharded module takes shard_map from here.  The kernels' bodies are
# per-shard Pallas calls whose replication JAX cannot infer, so the
# varying-manual-axes check is off at every call site.
shard_map = functools.partial(jax.shard_map, check_vma=False)


def _wide_dtype(dtype) -> jnp.dtype:
    """The 32-bit type a block is rotated in (its own type if already 32-bit)."""
    dtype = jnp.dtype(dtype)
    if dtype.itemsize == 4:
        return dtype
    return jnp.dtype(jnp.int32 if jnp.issubdtype(dtype, jnp.integer) else jnp.float32)


def _barrel_shear(block: jax.Array, tile: int, *, inverse: bool) -> jax.Array:
    """Apply the DiP (un)permutation to every ``tile x tile`` sub-block.

    ``block``: (bk, bn) with bk % tile == 0 and bn % tile == 0.
    Forward (``inverse=False``):  out[j, i] = in[(j + i%tile) % tile, i]
    Inverse (``inverse=True``):   out[j, i] = in[(j - i%tile) % tile, i]

    Implemented as log2(tile) static rolls + masked selects per 64-row group,
    in 32 bits (module doc); the result has the input's dtype.
    """
    bk, bn = block.shape
    if bk % tile or bn % tile:
        raise ValueError(f"block {block.shape} not a multiple of permutation tile {tile}")
    out_dtype = block.dtype
    block = block.astype(_wide_dtype(out_dtype))
    col = jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1) % tile
    groups = []
    for g in range(bk // tile):
        w = block[g * tile:(g + 1) * tile, :]
        bit = 1
        while bit < tile:
            # inverse: roll column i DOWN by i  -> positive (down) shifts
            # forward: roll column i UP by i    -> negative (up) shifts
            shift = bit if inverse else tile - bit
            rolled = pltpu.roll(w, shift, axis=0)
            w = jnp.where((col & bit) != 0, rolled, w)
            bit *= 2
        groups.append(w)
    out = groups[0] if len(groups) == 1 else jnp.concatenate(groups, axis=0)
    return out.astype(out_dtype)


def deshear_block(p_block: jax.Array, tile: int = 64) -> jax.Array:
    """Undo the per-tile DiP permutation inside a kernel (VMEM resident)."""
    return _barrel_shear(p_block, tile, inverse=True)


def shear_block(w_block: jax.Array, tile: int = 64) -> jax.Array:
    """Apply the per-tile DiP permutation inside a kernel."""
    return _barrel_shear(w_block, tile, inverse=False)


def rotate_left_dynamic(x: jax.Array, r: jax.Array, width: int) -> jax.Array:
    """Rotate the trailing axis left by a *traced* amount ``r`` (mod width).

    ``out[..., i] = x[..., (i + r) % width]`` — the diagonal input movement of
    the DiP array after r hops.  Uses pltpu.roll with a dynamic shift
    (tpu.DynamicRotate); the left-rotate is expressed as a down-roll by
    ``width - r``.
    """
    shift = (width - r) % width
    return pltpu.roll(x, shift, axis=x.ndim - 1)
