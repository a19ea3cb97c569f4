"""Operations and bytes of the work, counted from shapes.

The counts are of the mathematics, whatever implements it: a projection
``(M, K) @ (K, N)`` is ``2 M K N`` operations and reads its operand and
weight and writes its output once, each at the compute width (bfloat16,
2 bytes; the weights too).  Attention is causal: a query at position ``p``
meets ``p + 1`` keys.  ``model_flops`` counts the model's own sizes.  A
matmul kernel call is named by its shapes in the trace (``kernel_call_shape``),
matched to the model's projection it computes (``projection_of``), and
counted at that projection's sizes and the rows the program runs it on:
padding to the storage's tile grid is not work.
"""

from __future__ import annotations

import json
import re
from pathlib import Path

__all__ = ["peaks", "least_time", "projections", "kernel_call_shape",
           "projection_of", "matmul_work", "attention_work", "model_flops"]

WIDTH = 2                                   # bytes of a bfloat16 operand


def peaks(device_kind: str) -> dict:
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table["devices"][device_kind]


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """Seconds the chip needs at least: the larger of its compute and its
    memory bound."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])


def projections(dims) -> list:
    """(name, K, N, weights) of one layer's projections; the SwiGLU gate and
    up projections are one product with two weights and one ``ff``-wide
    output."""
    d, hd = dims.d, dims.hd
    return [("wq", d, dims.heads * hd, 1), ("wk", d, dims.kv * hd, 1),
            ("wv", d, dims.kv * hd, 1), ("wo", dims.heads * hd, d, 1),
            ("gate_up", d, dims.ff, 2), ("w_down", dims.ff, d, 1)]


_SHAPE = re.compile(r"\b(?:bf16|f16|f32|s8|u8|s32)\[([\d,]*)\]")


def kernel_call_shape(op_text: str) -> tuple:
    """(K, N, weights) of one matmul kernel call as its HLO text
    ``%name = out_type custom-call(x, w[, w2], ...)`` gives them: ``x`` is
    (M, K), each weight operand (K, N) with the output's N.  These are the
    storage's sizes, padding included."""
    body = op_text.partition(" = ")[2].split("custom_call_target")[0]
    shapes = [tuple(int(d) for d in dims.split(",") if d) for dims in _SHAPE.findall(body)]
    out, x = shapes[0], shapes[1]
    k, n = x[-1], out[-1]
    return k, n, sum(1 for sh in shapes[2:] if sh == (k, n))


def projection_of(dims, k: int, n: int, weights: int):
    """The model's projection (``projections`` or the output head) that a
    kernel call of storage sizes (K, N) with ``weights`` weight operands
    computes: the largest that fits, since storage only pads a size up.
    None where none fits."""
    fits = [p for p in projections(dims) + [("head", dims.d, dims.vocab, 1)]
            if p[3] == weights and p[1] <= k and p[2] <= n]
    return max(fits, key=lambda p: p[1] * p[2], default=None)


def matmul_work(m: int, k: int, n: int, weights: int = 1) -> tuple:
    """(flops, bytes) of ``weights`` products (M, K) @ (K, N) that share
    their operand and their output."""
    return 2 * m * k * n * weights, WIDTH * (m * k + weights * k * n + m * n)


def attention_work(dims, q_len: int, offset: int) -> tuple:
    """(flops, bytes) of one layer's causal attention for ``q_len`` queries
    that follow ``offset`` cached positions (one sequence)."""
    hd, h, kv = dims.hd, dims.heads, dims.kv
    pairs = q_len * offset + q_len * (q_len + 1) // 2
    flops = 4 * h * hd * pairs
    nbytes = WIDTH * (2 * q_len * h * hd + 2 * (offset + q_len) * kv * hd)
    return flops, nbytes


def model_flops(dims, tokens: int, context_sum: int, train: bool = False) -> float:
    """Model operations for ``tokens`` tokens whose contexts (keys each
    query meets, itself included) add up to ``context_sum``: 2 N per token
    for the N weights of the projections and the head, plus attention;
    three times that to train.  Recomputation does not count."""
    n = sum(k * n * nw for _, k, n, nw in projections(dims)) * dims.layers
    n += dims.d * dims.vocab
    f = 2 * n * tokens + 4 * dims.heads * dims.hd * context_sum * dims.layers
    return 3 * f if train else f
