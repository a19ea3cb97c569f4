"""The main path's kernels compile for a TPU v5e at yi_9b widths.

Interpret mode (every other test here) cannot see what the chip's compiler
refuses: unaligned block shapes, vector ops Mosaic lacks (it rotates 32-bit
vectors only), SMEM operands that break the tiling rule.  These tests hand
the compiler a described ``v5e:2x2`` topology instead of a chip, so nothing
runs; each test compiles one dispatch as the model issues it and checks
that the Pallas kernel is in the compiled program (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and under pytest-xdist every worker imports this file.  The persistent
compilation cache is off while these tests run (a compile for a described
chip can be written to it but not read back).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import api
from repro.api.quant import QuantizedDipWeight
from repro.configs import get_config
from repro.kernels import dip_matmul_q
from repro.kernels.lm_head_ce import fused_cross_entropy_loss

YI = get_config("yi_9b")
D, F = YI.d_model, YI.d_ff
# decode rows (8 slots) and one prefill chunk of 256 tokens
ROWS = (8, 256)
# (d_in, d_out, prologue, epilogue) of the dense block's dispatches
DISPATCHES = {
    "wq_rmsnorm": (D, YI.n_heads * YI.resolved_head_dim, "rmsnorm", "none"),
    "gate_up_swiglu": (D, F, "rmsnorm", "swiglu"),
    "down_residual": (F, D, "none", "residual"),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _assert_kernel_compiles(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _weight(sharding, d_in, d_out, storage_dtype, scheme=None):
    kp, np_ = api.DipWeight.storage_dims(d_in, d_out)
    data = _spec(sharding, (kp, np_), storage_dtype)
    if scheme is None:
        return api.DipWeight(data, d_in, d_out)
    scale = _spec(sharding, (1, np_), jnp.float32)
    return QuantizedDipWeight(data, scale, d_in, d_out, scheme=scheme)


def _dispatch_args(sharding, rows, name, act_dtype, storage_dtype, scheme):
    d_in, d_out, prologue, epilogue = DISPATCHES[name]
    x = _spec(sharding, (rows, d_in), act_dtype)
    n_w = 2 if epilogue == "swiglu" else 1
    ws = tuple(_weight(sharding, d_in, d_out, storage_dtype, scheme)
               for _ in range(n_w))
    ops = ()
    if epilogue == "residual":
        ops = (_spec(sharding, (rows, d_out), act_dtype),)
    pops = (_spec(sharding, (d_in,), jnp.float32),) if prologue == "rmsnorm" else ()
    return x, ws, ops, pops, prologue, epilogue


def _compile_dispatch(backend, x, ws, ops, pops, prologue, epilogue):
    def fn(x, ws, ops, pops):
        return api.matmul(
            x, ws if len(ws) == 2 else ws[0], backend=backend,
            epilogue=None if epilogue == "none" else epilogue,
            epilogue_operands=ops,
            prologue=None if prologue == "none" else prologue,
            prologue_operands=pops, interpret=False,
        )

    _assert_kernel_compiles(fn, x, ws, ops, pops)


@pytest.mark.parametrize("rows", ROWS)
@pytest.mark.parametrize("name", sorted(DISPATCHES))
def test_pallas_dip_bf16_compiles(one_chip, name, rows):
    """bf16 weights de-sheared in VMEM: the barrel shifter must rotate in
    32 bits and hand the MXU bf16 operands."""
    args = _dispatch_args(one_chip, rows, name, jnp.bfloat16, jnp.bfloat16, None)
    _compile_dispatch("pallas_dip", *args)


@pytest.mark.parametrize("rows", ROWS)
@pytest.mark.parametrize("name", sorted(DISPATCHES))
def test_dip_int8w_compiles(one_chip, name, rows):
    args = _dispatch_args(one_chip, rows, name, jnp.bfloat16, jnp.int8, "int8")
    _compile_dispatch("dip_int8w", *args)


@pytest.mark.parametrize("native", [False, True], ids=["f32_upcast", "bf16_upcast"])
def test_dip_fp8_compiles(one_chip, monkeypatch, native):
    """fp8 storage widens to f32 (emulated) or bf16 (a v5+ device) in VMEM;
    the width is chosen from the device, so the test picks it here."""
    monkeypatch.setattr(dip_matmul_q, "fp8_native_supported", lambda: native)
    jax.clear_caches()  # the width is read while tracing: trace afresh
    args = _dispatch_args(one_chip, 256, "gate_up_swiglu", jnp.bfloat16,
                          jnp.float8_e4m3fn, "fp8_e4m3")
    _compile_dispatch("dip_fp8", *args)


def test_flash_attention_prefill_chunk_compiles(one_chip):
    """One chunked-prefill call as the engine issues it: GQA broadcast to
    (batch*heads, chunk, head_dim), a traced per-row cache offset."""
    hd = YI.resolved_head_dim
    bh, chunk, cache_len = YI.n_heads, 128, 640
    q = _spec(one_chip, (bh, chunk, hd), jnp.bfloat16)
    kv = _spec(one_chip, (bh, cache_len, hd), jnp.bfloat16)
    q_offset = _spec(one_chip, (bh,), jnp.int32)

    def fn(q, k, v, q_offset):
        return api.attention(q, k, v, backend="flash", q_offset=q_offset,
                             kv_len=q_offset + chunk, interpret=False)

    _assert_kernel_compiles(fn, q, kv, kv, q_offset)


def test_lm_head_ce_compiles_with_grad(one_chip):
    """The fused lm_head + cross-entropy loss of a training step (2048
    tokens, the padded 65536-wide head) and its gradient."""
    t = 2048
    x = _spec(one_chip, (t, D), jnp.bfloat16)
    w = _spec(one_chip, (D, YI.padded_vocab), jnp.float32)
    labels = _spec(one_chip, (t,), jnp.int32)

    def fn(x, w, labels):
        return jax.value_and_grad(
            lambda x, w: fused_cross_entropy_loss(
                x, w, labels, vocab_size=YI.vocab_size, interpret=False),
            argnums=(0, 1),
        )(x, w)

    _assert_kernel_compiles(fn, x, w, labels)
