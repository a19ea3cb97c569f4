"""MoE routing invariants: conservation, capacity, shared experts, aux loss."""

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.base import ArchConfig
from repro.models import moe, transformer as tf_model

KEY = jax.random.PRNGKey(3)


def _cfg(e=8, k=2, shared=0, cf=1.25):
    return ArchConfig(
        name="m", family="moe", n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
        d_ff=0, vocab_size=64, head_dim=16, n_experts=e, moe_top_k=k,
        n_shared_experts=shared, d_ff_expert=16, capacity_factor=cf,
        remat="none", compute_dtype="float32",
    )


def _params(cfg, key=KEY):
    p = tf_model.init_params(key, cfg)
    return p["layers"]


def _layer_slice(lp):
    return jax.tree_util.tree_map(lambda t: t[0], lp)


def test_moe_output_shape_and_finite():
    cfg = _cfg()
    lp = _layer_slice(_params(cfg))
    x = jax.random.normal(KEY, (2, 8, cfg.d_model))
    out, aux, dropped = moe.moe_ffn(x, lp, cfg)
    assert out.shape == x.shape
    assert bool(jnp.isfinite(out).all()) and np.isfinite(float(aux))
    assert int(dropped) == 0  # cf=1.25 leaves headroom at these shapes


def test_capacity_overflow_drops_tokens_but_stays_finite():
    """cf -> tiny forces drops; output must stay finite AND the drop
    count must surface them (the old API dropped silently)."""
    cfg = _cfg(cf=0.05)
    lp = _layer_slice(_params(cfg))
    x = jax.random.normal(KEY, (2, 32, cfg.d_model))
    out, _, dropped = moe.moe_ffn(x, lp, cfg)
    assert bool(jnp.isfinite(out).all())
    # 2 groups x 32 tokens x k=2 slots = 128 demanded, capacity 8/expert
    assert int(dropped) > 0


def test_huge_capacity_equals_explicit_dense_routing():
    """With capacity >= tokens*k no drops occur: the scatter/gather dispatch
    must equal an explicit per-token loop over its top-k experts."""
    cfg = _cfg(e=4, k=2, cf=64.0)
    lp = _layer_slice(_params(cfg))
    x = jax.random.normal(KEY, (1, 6, cfg.d_model))
    got, _, dropped = moe.moe_ffn(x, lp, cfg)
    assert int(dropped) == 0  # cf=64 is ample: parity claim requires no drops

    # reference: dense routing
    xf = np.asarray(x.reshape(6, -1), np.float64)
    logits = xf @ np.asarray(lp["router"], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    ids = np.argsort(-probs, -1)[:, :2]
    want = np.zeros_like(xf)
    wg = np.asarray(lp["w_gate"], np.float64)
    wu = np.asarray(lp["w_up"], np.float64)
    wd = np.asarray(lp["w_down"], np.float64)

    def silu(v):
        return v / (1 + np.exp(-v))

    for t in range(6):
        g = probs[t, ids[t]]
        g = g / g.sum()
        for j, e in enumerate(ids[t]):
            h = silu(xf[t] @ wg[e]) * (xf[t] @ wu[e])
            want[t] += g[j] * (h @ wd[e])
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=2e-3, rtol=1e-2)


def test_single_expert_equals_dense_ffn_with_zero_drops():
    """num_experts=1, top_k=1: routing is the identity (one expert takes
    every token at gate 1.0), so moe_ffn must equal dense_ffn over the same
    weights — and the surfaced drop count must be ZERO, which is what makes
    the equality claim sound (a silent drop would fail it confusingly)."""
    cfg = _cfg(e=1, k=1)
    lp = _layer_slice(_params(cfg))
    x = jax.random.normal(KEY, (2, 16, cfg.d_model))
    got, _, dropped = moe.moe_ffn(x, lp, cfg)
    assert int(dropped) == 0
    want = moe.dense_ffn(
        x,
        {"w_gate": lp["w_gate"][0], "w_up": lp["w_up"][0],
         "w_down": lp["w_down"][0]},
        cfg,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=1e-3)


def test_shared_experts_added():
    cfg_ns = _cfg(shared=0)
    cfg_sh = _cfg(shared=1)
    lp = _layer_slice(_params(cfg_sh))
    x = jax.random.normal(KEY, (1, 4, cfg_sh.d_model))
    out_sh, _, _ = moe.moe_ffn(x, lp, cfg_sh)
    out_ns, _, _ = moe.moe_ffn(x, {k: v for k, v in lp.items() if not k.startswith("shared")}, cfg_ns)
    shared_only = moe.dense_ffn(
        x,
        {"w_gate": lp["shared_w_gate"], "w_up": lp["shared_w_up"],
         "w_down": lp["shared_w_down"]},
        cfg_sh,
    )
    np.testing.assert_allclose(
        np.asarray(out_sh), np.asarray(out_ns + shared_only), atol=1e-4
    )


@settings(max_examples=10)
@given(tokens=st.integers(4, 64), e=st.sampled_from([4, 8]), k=st.sampled_from([1, 2]))
def test_capacity_function_bounds(tokens, e, k):
    cfg = _cfg(e=e, k=k)
    cap = moe.moe_capacity(tokens, cfg)
    assert cap >= 8 and cap % 8 == 0
    assert cap * e >= tokens * k  # with cf >= 1, total slots cover demand


def test_aux_loss_decreases_under_balanced_routing():
    """Uniform router logits => minimal load-balance loss (= cfg coefficient)."""
    cfg = _cfg(e=4, k=1)
    lp = dict(_layer_slice(_params(cfg)))
    lp["router"] = jnp.zeros_like(lp["router"])  # perfectly uniform
    x = jax.random.normal(KEY, (1, 64, cfg.d_model))
    _, aux_uniform, _ = moe.moe_ffn(x, lp, cfg)
    lp["router"] = lp["router"].at[:, 0].set(10.0)  # collapse to expert 0
    _, aux_collapsed, _ = moe.moe_ffn(x, lp, cfg)
    assert float(aux_collapsed) > float(aux_uniform)
