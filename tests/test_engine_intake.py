"""The serving engine's weight intake (``tf_model.serving_params``).

The engine rounds each weight the serving programs read only in the compute
dtype once, when it takes the weights, and keeps that copy alone.  Rounding
once gives the values the forward's per-call ``.astype`` gave, so the served
logits must be bit-identical to an engine that keeps float32 storage, for
every family: a dense GQA model with QKV bias, DiP storage and a d_ff off
the 256 block grid (also through ``pallas_dip``), DeepSeek-V2's shape (MLA,
a dense first layer, MoE over a held share of the experts), a hybrid
(Mamba2 + shared attention) and a pure SSM with a tied head.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import run_forced_devices as _run

from repro import api
from repro.configs import get_config
from repro.models import transformer as tf_model
from repro.reliability import attach_checksums
from repro.serving import Engine, EngineConfig, SamplingParams, telemetry

# roles read in float32 somewhere, which intake must leave at storage
F32_ROLES = {"final_norm", "attn_norm", "ffn_norm", "kv_norm", "norm_in", "norm",
             "bq", "bk", "bv", "router", "conv_w", "conv_b", "dt_bias", "A_log", "D"}

CONFIGS = {
    "dense_gqa_bias": lambda: get_config("codeqwen15_7b").reduced(
        d_ff=320, dip_weights=True),
    "dense_gqa_pallas_dip": lambda: get_config("codeqwen15_7b").reduced(
        d_ff=320, n_layers=1, matmul_backend="pallas_dip"),
    "mla_moe_held": lambda: get_config("deepseek_v2_lite_16b").reduced(
        n_layers=3, moe_held_experts=4, moe_first_expert=2),
    "hybrid": lambda: get_config("zamba2_2_7b").reduced(),
    "ssm_tied_head": lambda: get_config("mamba2_370m").reduced(),
}


def _params(cfg):
    """Initial weights with every float32-read leaf moved off the values
    bfloat16 holds exactly (gains of 1, biases of 0), so that rounding one
    of them would show in the logits."""
    params = tf_model.init_params(jax.random.PRNGKey(0), cfg)

    def nudge(path, leaf):
        if path[-1].key not in F32_ROLES:
            return leaf
        noise = jax.random.normal(jax.random.PRNGKey(len(path)), leaf.shape)
        return leaf + 0.05 * noise

    return jax.tree_util.tree_map_with_path(
        nudge, params, is_leaf=lambda x: isinstance(x, api.DipWeight))


def _weight_nodes(tree):
    """{path: leaf} with DiP weights kept whole."""
    flat, _ = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, (api.DipWeight, api.QuantizedDipWeight)))
    return {jax.tree_util.keystr(p): (p[-1].key, leaf) for p, leaf in flat}


def _storage(leaf):
    return leaf.data if isinstance(leaf, api.DipWeight) else leaf


def _serve(cfg, params, monkeypatch=None, cast=True):
    """Greedy tokens and every logits row the engine drew from, for two
    requests whose prompts span two prefill chunks and then decode in the
    paged pool together.  ``cast=False`` serves the float32 storage as it
    came (the forward casts on each call)."""
    if not cast:
        monkeypatch.setattr(tf_model, "serving_params", lambda cfg, p: p)
    eng = Engine(cfg, params, engine_cfg=EngineConfig(
        slots=2, max_seq=48, prefill_chunk=8, eos_id=-1))
    rows, real = [], type(eng)._sample_rows

    def grab(logits, reqs):
        rows.append(np.asarray(logits))
        return real(eng, logits, reqs)

    eng._sample_rows = grab
    rng = np.random.default_rng(0)
    for n in (11, 14):
        eng.add_request(rng.integers(2, cfg.vocab_size, n).astype(np.int32),
                        SamplingParams(max_new_tokens=4))
    out = eng.run()
    if not cast:
        monkeypatch.undo()
    return out, rows, eng


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_served_logits_bit_identical_to_per_call_casts(name, monkeypatch):
    cfg = CONFIGS[name]()
    assert (cfg.param_dtype, cfg.compute_dtype) == ("float32", "bfloat16")
    params = _params(cfg)
    want_tokens, want_rows, ref = _serve(cfg, params, monkeypatch, cast=False)
    got_tokens, got_rows, eng = _serve(cfg, params)
    assert ref.last_stats["intake_cast_bytes"] == 0
    assert eng.last_stats["intake_cast_bytes"] > 0
    assert got_tokens == want_tokens
    # two first tokens (prefill), then the decode steps
    assert len(got_rows) == len(want_rows) >= 5
    for got, want in zip(got_rows, want_rows):
        assert got.dtype == want.dtype == np.float32
        np.testing.assert_array_equal(got, want)


def _weight_converts(program, args, shapes):
    """The float32 -> bfloat16 converts in ``program``'s jaxpr whose operand
    has one of the weights' ``shapes``."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "convert_element_type":
                a = eqn.invars[0].aval
                if (a.dtype == jnp.float32 and eqn.params["new_dtype"] == jnp.bfloat16
                        and tuple(a.shape) in shapes):
                    found.append(tuple(a.shape))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(program)(*args).jaxpr)
    return found


@pytest.mark.parametrize("name", ["dense_gqa_bias", "mla_moe_held"])
def test_served_programs_cast_no_weight(name, monkeypatch):
    """The decode and prefill programs traced over the served weights hold
    no weight cast; over float32 storage (casting turned off) they do."""
    cfg = CONFIGS[name]()
    params = _params(cfg)
    # the shape each weight is read at: a layer's slice of a stacked one
    shapes = {tuple(_storage(leaf).shape)[0 if role in ("embed", "lm_head") else 1:]
              for role, leaf in _weight_nodes(params).values() if role not in F32_ROLES}

    def converts():
        eng = Engine(cfg, params, engine_cfg=EngineConfig(
            slots=2, max_seq=32, prefill_chunk=8))
        decode = (eng.params, eng.kv.pools, jnp.asarray(eng._cur),
                  jnp.asarray(eng._ctx), jnp.asarray(eng.kv.block_tables))
        prefill = (eng.params, tf_model.init_cache(eng.cfg, 1, eng._prefill_buf_len),
                   jnp.zeros((1, 8), jnp.int32))
        return (_weight_converts(eng._decode, decode, shapes)
                + _weight_converts(eng._prefill_fwd, prefill, shapes))

    assert converts() == []
    monkeypatch.setattr(tf_model, "serving_params", lambda cfg, p: p)
    assert set(converts()) == shapes


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_serving_params_casts_exactly_the_matmul_and_embedding_leaves(name):
    cfg = CONFIGS[name]()
    params = _params(cfg)
    served = tf_model.serving_params(cfg, params)
    before, after = _weight_nodes(params), _weight_nodes(served)
    assert before.keys() == after.keys()
    roles = set()
    for path, (role, leaf) in before.items():
        new = after[path][1]
        roles.add(role)
        if role in F32_ROLES:
            assert new is leaf, path
            assert _storage(new).dtype == jnp.float32, path
            continue
        assert _storage(new).dtype == jnp.bfloat16, path
        np.testing.assert_array_equal(
            np.asarray(_storage(new)),
            np.asarray(_storage(leaf).astype(jnp.bfloat16)), err_msg=path)
        if isinstance(leaf, api.DipWeight):
            assert (new.d_in, new.d_out, new.perm_tile, new.plan) == (
                leaf.d_in, leaf.d_out, leaf.perm_tile, leaf.plan)
    assert "embed" in roles
    assert roles - F32_ROLES <= tf_model.COMPUTE_DTYPE_ROLES


def test_the_roles_follow_the_template():
    """Every role in the template is either read only in the compute dtype
    or in ``F32_ROLES``, and the norm gains and biases (no fan-in) are among
    the latter."""
    for name, make in CONFIGS.items():
        cfg = make()
        flat, _ = jax.tree_util.tree_flatten_with_path(
            tf_model.param_template(cfg),
            is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))
        for path, leaf in flat:
            role = path[-1].key
            assert (role in F32_ROLES) != (role in tf_model.COMPUTE_DTYPE_ROLES), (name, role)
            if leaf[2] is None:
                assert role in F32_ROLES, (name, role)


@pytest.mark.parametrize("name", ["dense_gqa_bias", "mla_moe_held"])
def test_nothing_is_cast_at_the_storage_dtype(name):
    cfg = dataclasses.replace(CONFIGS[name](), compute_dtype="float32")
    params = _params(cfg)
    served = tf_model.serving_params(cfg, params)
    assert all(a is b for a, b in zip(jax.tree_util.tree_leaves(params),
                                      jax.tree_util.tree_leaves(served)))
    eng = Engine(cfg, params, engine_cfg=EngineConfig(slots=1, max_seq=16))
    stats = eng.last_stats
    assert stats["intake_cast_bytes"] == 0
    assert stats["served_weight_bytes"] == sum(
        a.nbytes for a in jax.tree_util.tree_leaves(params))


def test_quantized_weights_are_left_alone():
    cfg = get_config("codeqwen15_7b").reduced(
        matmul_backend="dip_int8w", quantization="int8")
    params = tf_model.init_params(jax.random.PRNGKey(0), cfg)
    served = tf_model.serving_params(cfg, params)
    n = 0
    for path, (role, leaf) in _weight_nodes(params).items():
        new = _weight_nodes(served)[path][1]
        if isinstance(leaf, api.QuantizedDipWeight):
            n += 1
            assert new is leaf, path
    assert n > 0
    assert served["embed"].dtype == jnp.bfloat16


def test_a_cast_drops_the_abft_checksum():
    cfg = CONFIGS["dense_gqa_bias"]()
    params = attach_checksums(_params(cfg))
    assert params["layers"]["wq"].checksum is not None
    served = tf_model.serving_params(cfg, params)
    assert served["layers"]["wq"].checksum is None
    kept = tf_model.serving_params(dataclasses.replace(cfg, compute_dtype="float32"), params)
    assert kept["layers"]["wq"] is params["layers"]["wq"]


def test_the_callers_tree_is_unchanged():
    cfg = CONFIGS["mla_moe_held"]()
    params = _params(cfg)
    leaves = jax.tree_util.tree_leaves(params)
    copies = [np.array(a) for a in leaves]
    out, _, eng = _serve(cfg, params)
    assert all(len(t) == 4 for t in out.values())
    after = jax.tree_util.tree_leaves(params)
    assert len(after) == len(leaves)
    for a, b, c in zip(leaves, after, copies):
        assert a is b and a.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(b), c)
    assert eng.params is not params


def test_intake_counters_match_a_hand_count():
    """Bytes from the template's shapes alone: every compute-dtype role's
    float32 storage is rounded, and the engine keeps it at 2 bytes."""
    cfg = CONFIGS["dense_gqa_bias"]()
    flat, _ = jax.tree_util.tree_flatten_with_path(
        tf_model.param_template(cfg),
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))
    cast = keep = 0
    for path, (shape, *_rest) in flat:
        if path[-1].key in F32_ROLES:
            keep += 4 * int(np.prod(shape))
        else:
            cast += 4 * int(np.prod(shape))
    eng = Engine(cfg, _params(cfg), engine_cfg=EngineConfig(slots=1, max_seq=16))
    assert eng.last_stats["intake_cast_bytes"] == cast
    assert eng.last_stats["served_weight_bytes"] == cast // 2 + keep
    eng.add_request(np.arange(2, 6, dtype=np.int32), SamplingParams(max_new_tokens=2))
    eng.run()
    assert eng.last_stats["intake_cast_bytes"] == cast     # run() keeps them


def test_intake_span_is_recorded_once():
    cfg = CONFIGS["dense_gqa_bias"]()
    params = _params(cfg)
    telemetry.clear()
    eng = Engine(cfg, params, engine_cfg=EngineConfig(slots=1, max_seq=16))
    eng.add_request(np.arange(2, 6, dtype=np.int32), SamplingParams(max_new_tokens=2))
    eng.run()
    spans = telemetry.spans()
    intake = [s for s in spans if s.name == "engine.intake"]
    assert len(intake) == 1
    (s,) = intake
    assert s.parent == -1 and s.end >= s.start
    steps = [t for t in spans if t.name == "engine.step"]
    assert steps and s.end <= min(t.start for t in steps)
    n_cast = sum(1 for role, _ in _weight_nodes(params).values() if role not in F32_ROLES)
    assert s.n == n_cast


def test_dip_tp_intake_keeps_the_plans_shardings():
    """The cast weights keep the placement the plan gave the float32 tree,
    and the sharded engine serves what the unsharded one does."""
    _run("""
import dataclasses
from repro import api
from repro.configs import get_config
from repro.distributed.plan import make_local_mesh, make_plan
from repro.models import transformer as tf_model
from repro.serving import Engine, EngineConfig, SamplingParams

cfg = get_config("llama3_8b").reduced()
params = tf_model.init_params(jax.random.PRNGKey(0), cfg)
tp_cfg = dataclasses.replace(cfg, sharding="tp", matmul_backend="dip_tp")
plan = make_plan(make_local_mesh(data=1, model=2), tp_cfg, "decode")
ecfg = EngineConfig(slots=2, max_seq=32, prefill_chunk=8)
eng = Engine(tp_cfg, params, engine_cfg=ecfg, plan=plan)
placed = jax.tree_util.tree_leaves(plan.param_shardings(plan.attach_params(params)))
served = jax.tree_util.tree_leaves(eng.params)
assert len(placed) == len(served)
for want, a in zip(placed, served):
    assert a.sharding.is_equivalent_to(want, a.ndim), (a.sharding, want)
wq = eng.params["layers"]["wq"]
assert wq.dtype == jnp.bfloat16
assert len(wq.sharding.device_set) == 2
assert eng.params["layers"]["attn_norm"].dtype == jnp.float32

sp = SamplingParams(max_new_tokens=4)
prompt = np.arange(2, 9, dtype=np.int32)
eng.add_request(prompt, sp, rid=0)
ref = Engine(cfg, params, engine_cfg=ecfg)
ref.add_request(prompt, sp, rid=0)
assert eng.run()[0] == ref.run()[0]
print("INTAKE_SHARDED_OK")
""", devices=2)
