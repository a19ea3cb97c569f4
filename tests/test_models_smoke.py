"""Per-architecture smoke tests (assignment deliverable f).

Each assigned arch is instantiated at a REDUCED same-family config (tiny
dims, few layers/experts) and runs one forward + one train step on CPU,
asserting output shapes and finiteness.  The FULL configs are exercised only
via the dry-run (ShapeDtypeStruct, no allocation).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_ARCHS, get_config
from repro.models import transformer as tf_model
from repro.optim import AdamW


@pytest.fixture(scope="module")
def key():
    return jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_reduced_forward_and_train_step(arch, key):
    cfg = get_config(arch).reduced(compute_dtype="float32")
    params = tf_model.init_params(key, cfg)

    batch_size, seq = 2, 32
    if cfg.frontend != "none":
        batch = {
            "embeddings": jax.random.normal(key, (batch_size, seq, cfg.d_model)) * 0.02,
            "labels": jax.random.randint(key, (batch_size, seq), 0, cfg.vocab_size),
        }
        logits, _, _ = tf_model.forward(params, cfg, embeddings=batch["embeddings"])
    else:
        toks = jax.random.randint(key, (batch_size, seq), 0, cfg.vocab_size)
        batch = {"tokens": toks, "labels": toks}
        logits, _, _ = tf_model.forward(params, cfg, tokens=toks)

    assert logits.shape == (batch_size, seq, cfg.padded_vocab)
    real = logits[..., : cfg.vocab_size]
    assert bool(jnp.isfinite(real).all()), f"{arch}: non-finite logits"
    # padded lanes masked to -inf
    if cfg.padded_vocab != cfg.vocab_size:
        assert float(logits[..., cfg.vocab_size:].max()) < -1e29

    opt = AdamW(lr=1e-3)
    state = {"params": params, "opt_state": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}
    step = jax.jit(tf_model.train_step_fn(cfg, opt))
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"])), f"{arch}: non-finite loss"
    assert float(metrics["grad_norm"]) > 0
    assert int(metrics["step"]) == 1


@pytest.mark.parametrize("arch", ["llama3-8b", "deepseek-v2-lite-16b", "mamba2-370m",
                                  "zamba2-2.7b"])
def test_reduced_decode_step(arch, key):
    """serve_step: one token against a warm cache (representative families)."""
    cfg = get_config(arch).reduced(compute_dtype="float32")
    params = tf_model.init_params(key, cfg)
    toks = jax.random.randint(key, (2, 16), 0, cfg.vocab_size)
    dstep = jax.jit(tf_model.decode_step_fn(cfg))
    cache = tf_model.init_cache(cfg, batch=2, max_seq=24)
    _, cache = dstep(params, cache, toks)
    logits, cache = dstep(params, cache, toks[:, :1])
    assert logits.shape == (2, 1, cfg.padded_vocab)
    assert bool(jnp.isfinite(logits[..., : cfg.vocab_size]).all())
    assert int(cache["pos"]) == 17


def test_full_configs_match_assignment():
    """Pin the exact assigned hyper-parameters (regression guard)."""
    spec = {
        "deepseek-v2-lite-16b": dict(n_layers=27, d_model=2048, n_heads=16,
                                     vocab_size=102400, n_experts=64, moe_top_k=6,
                                     kv_lora_rank=512, d_ff_expert=1408,
                                     n_shared_experts=2),
        "qwen3-moe-235b-a22b": dict(n_layers=94, d_model=4096, n_heads=64,
                                    n_kv_heads=4, vocab_size=151936,
                                    n_experts=128, moe_top_k=8, d_ff_expert=1536),
        "mamba2-370m": dict(n_layers=48, d_model=1024, ssm_state=128,
                            vocab_size=50280),
        "llama3-8b": dict(n_layers=32, d_model=4096, n_heads=32, n_kv_heads=8,
                          d_ff=14336, vocab_size=128256),
        "codeqwen1.5-7b": dict(n_layers=32, d_model=4096, n_heads=32,
                               n_kv_heads=32, d_ff=13440, vocab_size=92416,
                               qkv_bias=True),
        "yi-9b": dict(n_layers=48, d_model=4096, n_heads=32, n_kv_heads=4,
                      d_ff=11008, vocab_size=64000),
        "qwen2-72b": dict(n_layers=80, d_model=8192, n_heads=64, n_kv_heads=8,
                          d_ff=29568, vocab_size=152064, qkv_bias=True),
        "phi-3-vision-4.2b": dict(n_layers=32, d_model=3072, n_heads=32,
                                  n_kv_heads=32, d_ff=8192, vocab_size=32064,
                                  frontend="vision_stub"),
        "musicgen-medium": dict(n_layers=48, d_model=1536, n_heads=24,
                                n_kv_heads=24, d_ff=6144, vocab_size=2048,
                                frontend="audio_stub"),
        "zamba2-2.7b": dict(n_layers=54, d_model=2560, n_heads=32,
                            n_kv_heads=32, d_ff=10240, vocab_size=32000,
                            ssm_state=64, attn_every=6),
    }
    for arch_id, fields in spec.items():
        cfg = get_config(arch_id)
        for k, v in fields.items():
            assert getattr(cfg, k) == v, f"{arch_id}.{k}: {getattr(cfg, k)} != {v}"


def test_param_counts_sane():
    expect = {
        "deepseek-v2-lite-16b": 15.7e9, "qwen3-moe-235b-a22b": 235e9,
        "mamba2-370m": 0.37e9, "llama3-8b": 8.0e9, "codeqwen1.5-7b": 8.2e9,
        "yi-9b": 8.8e9, "qwen2-72b": 72.7e9, "phi-3-vision-4.2b": 3.8e9,
        "musicgen-medium": 1.8e9, "zamba2-2.7b": 2.4e9,
    }
    for arch_id, n in expect.items():
        got = get_config(arch_id).param_count()
        assert abs(got - n) / n < 0.05, f"{arch_id}: {got/1e9:.2f}B != ~{n/1e9:.1f}B"
    # MoE active params
    assert abs(get_config("qwen3-moe-235b-a22b").active_param_count() - 22.2e9) < 1.5e9


def test_long_500k_gate():
    from repro.configs import shape_cells_for

    for arch_id in ALL_ARCHS:
        cfg = get_config(arch_id)
        names = [c.name for c in shape_cells_for(cfg)]
        if arch_id in ("mamba2_370m", "zamba2_2_7b"):
            assert "long_500k" in names
        else:
            assert "long_500k" not in names  # skip recorded in DESIGN.md §4
