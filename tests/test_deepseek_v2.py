"""DeepSeek-V2-Lite through the serving engine against the plain reference.

A reduced configuration of the published model (``deepseek_v2_lite_16b``):
one dense layer and two MoE layers, YaRN with its original length cut to 64
positions so that the frequency ramp and both mscale terms act on a
200-token prompt.  The engine prefills the prompt in chunks (the expanded
MLA form through the flash kernel, interpret mode here) and then decodes
through the paged latent pool (the absorbed form); every logit row it
samples from is compared with ``models.reference.forward`` over the prompt
and the tokens served so far.

Tolerance ``TOL`` = 1e-4 on max|Δlogit| (logits up to about 3.4): the
program computes in float32 here and the reference at ``highest``
precision, so what is left is float32 summation order (flash's online
softmax, the grouped expert matmul): 7.2e-6 measured.  Each departure of
the published mathematics moves a logit by 0.7 or more, so it fails the
same comparison by four orders of magnitude.
"""

import dataclasses
import hashlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import attention, moe, reference
from repro.models import transformer as tf_model
from repro.serving import Engine, EngineConfig, SamplingParams

TOL = 1e-4
PROMPT = 200


def _cfg(**kw):
    base = get_config("deepseek_v2_lite_16b")
    return base.reduced(
        n_layers=3, compute_dtype="float32",
        rope_yarn=dataclasses.replace(base.rope_yarn, original_max_position=64),
        **kw)


def _params(cfg):
    params = tf_model.init_params(jax.random.PRNGKey(0), cfg)
    # a small residual stream, so that the norms' eps (1e-6 against 1e-5)
    # moves the logits; gains away from 1 so that a skipped norm shows
    params["embed"] = params["embed"] * 0.1
    for i, k in enumerate(("attn_norm", "ffn_norm", "kv_norm")):
        g = params["layers"][k]
        params["layers"][k] = g + 0.1 * jax.random.normal(jax.random.PRNGKey(i), g.shape)
    return params


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (PROMPT,), 2,
                                           cfg.vocab_size), np.int32)
    return cfg, _params(cfg), prompt


def _serve(cfg, params, prompt, new=6):
    """Greedy tokens, the logits row each was drawn from, and the stats."""
    eng = Engine(cfg, params, engine_cfg=EngineConfig(
        slots=2, max_seq=256, prefill_chunk=64, eos_id=-1))
    rows, real = [], type(eng)._sample_rows

    def grab(logits, reqs):
        rows.append(np.asarray(logits)[0])
        return real(eng, logits, reqs)

    eng._sample_rows = grab
    eng.add_request(prompt, SamplingParams(max_new_tokens=new))
    return eng.run()[0], np.stack(rows), eng.last_stats


def _error(setup, cfg=None, params=None):
    """max|Δlogit| of what the engine serves under (cfg, params) against
    the reference of the published configuration, at every served
    position of the engine's own token stream."""
    ref_cfg, ref_params, prompt = setup
    toks, rows, stats = _serve(cfg or ref_cfg, params or ref_params, prompt)
    seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
    want = np.asarray(reference.forward(ref_params, ref_cfg, seq))[prompt.size - 1:]
    return float(np.abs(rows[:, :ref_cfg.vocab_size] - want).max()), stats


def test_engine_prefill_and_paged_decode_match_reference(setup):
    err, stats = _error(setup)
    assert err < TOL, err
    assert stats["moe_dropped"] == 0
    # the rows the programs ran: 4 prefill chunks of 64 (the last one
    # padded) and 5 decode steps of 2 slots (one free), through 2 MoE layers
    # at top-2; the 8 experts all held, so every pair is computed once
    assert stats["moe_routed"] == stats["moe_rows"] == (4 * 64 + 5 * 2) * 2 * 2


def _no_dense_first_layer(cfg, params):
    """All layers MoE (one stack, as without leading dense layers): layer 0
    takes a copy of layer 1's experts."""
    p = {k: v for k, v in params.items() if k not in ("dense", "moe")}
    p["layers"] = dict(params["layers"], **jax.tree_util.tree_map(
        lambda t: jnp.concatenate([t[:1], t]), params["moe"]))
    return dataclasses.replace(cfg, first_k_dense=0), p


@pytest.mark.parametrize("departure", [
    "dense_first_layer", "yarn", "yarn_mscale", "latent_norm", "gate_renorm",
    "inference_drops", "norm_eps", "prefill_decode_forms"])
def test_each_departure_fails_the_reference_comparison(setup, departure, monkeypatch):
    """Each of the published model's departures in the program before this
    configuration was supported, planted alone, fails ``TOL``."""
    cfg, params, _ = setup
    c, p = cfg, params
    if departure == "dense_first_layer":
        c, p = _no_dense_first_layer(cfg, params)
    elif departure == "yarn":
        c = dataclasses.replace(cfg, rope_yarn=None)
    elif departure == "yarn_mscale":                 # the ramp kept, m() = 1
        c = dataclasses.replace(cfg, rope_yarn=dataclasses.replace(
            cfg.rope_yarn, mscale=0.0, mscale_all_dim=0.0))
    elif departure == "latent_norm":
        shim = types.SimpleNamespace(**vars(attention.layers))
        shim.rms_norm = lambda x, g, eps=1e-5: x
        monkeypatch.setattr(attention, "layers", shim)
    elif departure == "gate_renorm":
        c = dataclasses.replace(cfg, norm_topk_prob=True)
    elif departure == "inference_drops":
        real = moe.routed_ffn
        monkeypatch.setattr(moe, "routed_ffn", lambda *a, dropless=False, **k: real(
            *a, dropless=False, **k))
        c = dataclasses.replace(cfg, capacity_factor=0.5)
    elif departure == "norm_eps":
        c = dataclasses.replace(cfg, norm_eps=1e-5)
    else:                 # an absorbed decode whose softmax scale lacks mscale
        real = attention.paged_mla_attention
        monkeypatch.setattr(attention, "paged_mla_attention", lambda x, p_, cfg_, **k: real(
            x, p_, dataclasses.replace(cfg_, rope_yarn=None), **k))
    err, _ = _error(setup, c, p)
    assert err > 100 * TOL, f"{departure}: {err}"


def _moe_layer(cfg, params):
    return jax.tree_util.tree_map(lambda t: t[0], params["moe"])


def test_held_expert_shares_add_up_to_the_uncut_layer():
    """Four chips of two experts each: their routed parts, with the shared
    expert counted once, add up to the layer holding all eight experts —
    on the dropless (inference) path and on the capacity (training) path
    with ample capacity."""
    cfg = _cfg(capacity_factor=64.0)
    lp = _moe_layer(cfg, _params(cfg))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 12, cfg.d_model))
    shared = {k: v for k, v in lp.items() if k.startswith("shared")}
    only_shared = moe.dense_ffn(x, {k[7:]: v for k, v in shared.items()}, cfg)
    for dropless in (True, False):
        whole, _, stats = moe.routed_ffn(x, lp, cfg, dropless=dropless)
        parts = []
        for rank in range(4):
            c = dataclasses.replace(cfg, moe_held_experts=2, moe_first_expert=2 * rank)
            share = dict(lp, **{k: lp[k][2 * rank:2 * rank + 2]
                                for k in ("w_gate", "w_up", "w_down")})
            out, _, st = moe.routed_ffn(x, share, c, dropless=dropless)
            parts.append((out - only_shared, st))
        total = only_shared + sum(o for o, _ in parts)
        np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                                   atol=1e-5, rtol=1e-5)
        assert sum(int(st[0]) for _, st in parts) == int(stats[0]) == 2 * 12 * 2
        assert int(stats[2]) == 0


def test_skewed_router_drops_nothing_at_inference():
    """A router that sends every token to expert 0: the capacity dispatch
    drops most of them, the inference path none."""
    cfg = _cfg()
    lp = dict(_moe_layer(cfg, _params(cfg)))
    lp["router"] = lp["router"].at[:, 0].add(100.0)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 64, cfg.d_model))
    _, _, train = moe.routed_ffn(x, lp, cfg)
    _, _, infer = moe.routed_ffn(x, lp, cfg, dropless=True)
    assert int(train[2]) > 0
    assert int(infer[2]) == 0 and int(infer[0]) == int(infer[1]) == 64 * 2


def test_skewed_router_through_the_engine_drops_nothing(setup):
    cfg, params, prompt = setup
    p = dict(params, moe=dict(params["moe"], router=params["moe"]["router"].at[..., 0].add(100.0)))
    _, _, stats = _serve(cfg, p, prompt, new=3)
    assert stats["moe_dropped"] == 0 and stats["moe_routed"] > 0


def test_dense_first_layer_counts_the_published_parameters():
    cfg = get_config("deepseek_v2_lite_16b")
    assert abs(cfg.param_count() - 15.706e9) < 0.01e9
    template = tf_model.param_template(cfg)
    assert template["dense"]["w_gate"][0] == (1, 2048, 10944)
    assert template["moe"]["w_gate"][0] == (26, 64, 2048, 1408)
    held = dataclasses.replace(cfg, moe_held_experts=8)
    assert tf_model.param_template(held)["moe"]["w_gate"][0] == (26, 8, 2048, 1408)


# The engine's greedy logits rows for a reduced yi_9b (bfloat16, xla
# backend), hashed: recorded on the tree before the dense-first stack,
# YaRN and the MoE counters, which must leave a dense model's programs
# computing exactly what they did.
YI_DIGEST = "052cff6e6154364770c41aad031fcc982f1208b11c7ed3edcf1bbaa2e8e192ed"


def test_yi_9b_prefill_and_decode_logits_unchanged():
    cfg = get_config("yi_9b").reduced()
    params = tf_model.init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, engine_cfg=EngineConfig(
        slots=2, max_seq=96, prefill_chunk=32, eos_id=-1))
    rows, real = [], type(eng)._sample_rows

    def grab(logits, reqs):
        rows.append(np.asarray(logits))
        return real(eng, logits, reqs)

    eng._sample_rows = grab
    rng = np.random.default_rng(0)
    for n in (70, 40):
        eng.add_request(rng.integers(2, cfg.vocab_size, n).astype(np.int32),
                        SamplingParams(max_new_tokens=6))
    eng.run()
    h = hashlib.sha256()
    for r in rows:
        h.update(np.ascontiguousarray(r, np.float32).tobytes())
    assert len(rows) == 9 and h.hexdigest() == YI_DIGEST
