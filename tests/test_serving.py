"""The serving engine's contracts (repro.serving).

Four load-bearing properties:

1. **Allocator invariants** — the free-list block allocator never hands out
   the null block, never double-allocates, is all-or-nothing, and raises on
   double-free (property-tested with hypothesis).
2. **Row independence** — a greedy request's output is bit-identical whether
   it runs alone or packed with arbitrary batch-mates, across every model
   family (GQA, MLA, pure-SSM, hybrid).  This is THE correctness property of
   continuous batching: admission order must not change anyone's tokens.
3. **int8 paged KV** — logits match the bf16 paged path within the
   quantization error bound, and a fixed byte budget holds strictly more
   int8 blocks (and concurrent sequences) than bf16.
4. **Compatibility** — the legacy ``Server`` wrapper reproduces direct
   engine results; the wave baseline still serves; ``dip_tp`` sharded
   serving works end-to-end on forced host devices.
"""

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st
from conftest import run_forced_devices as _run

import jax

from repro.configs import get_config
from repro.models import transformer as tf_model
from repro.runtime.server import Request, Server, ServerConfig, WaveServer
from repro.serving import (
    BlockAllocator, Engine, EngineConfig, PagedKVCache, SamplingParams,
    blocks_for_budget, bytes_per_block, max_concurrent,
)
from repro.serving import sampling

# Every zoo config: the four layout families (GQA, MLA+MoE, pure-SSM,
# hybrid) plus the previously-untested members — packed-vs-solo equivalence
# is the fleet's correctness floor, so the whole zoo rides through it.
from repro.configs import ALL_ARCHS as FAMILIES


def _params(cfg, seed=0):
    return tf_model.init_params(jax.random.PRNGKey(seed), cfg)


def _prompts(cfg, n, rng=None, lo=3, hi=10):
    rng = rng or np.random.default_rng(0)
    return [rng.integers(2, cfg.vocab_size, size=int(rng.integers(lo, hi)))
            .astype(np.int32) for _ in range(n)]


# ------------------------------------------------------------- allocator ----
@settings(max_examples=25)
@given(num_blocks=st.integers(min_value=2, max_value=24),
       seed=st.integers(min_value=0, max_value=10_000))
def test_allocator_invariants(num_blocks, seed):
    """Random alloc/free interleavings: no null block, no duplicates,
    all-or-nothing allocation, exact conservation of the block population."""
    rng = np.random.default_rng(seed)
    alloc = BlockAllocator(num_blocks)
    live = []
    for _ in range(40):
        if live and rng.integers(2):
            alloc.free(live.pop(int(rng.integers(len(live)))))
        else:
            n = int(rng.integers(0, num_blocks))
            free_before = alloc.num_free
            got = alloc.alloc(n)
            # all-or-nothing: refuses iff infeasible, never hands out a slice
            if got is None:
                assert n > free_before
                continue
            assert n <= free_before
            assert len(got) == n and BlockAllocator.NULL_BLOCK not in got
            live.append(got)
        flat = [b for blks in live for b in blks]
        assert len(flat) == len(set(flat)), "block double-allocated"
        assert alloc.num_free + len(flat) == num_blocks - 1, "blocks leaked"
    for blks in live:
        alloc.free(blks)
    assert alloc.num_free == num_blocks - 1


def test_allocator_double_free_raises():
    alloc = BlockAllocator(4)
    got = alloc.alloc(2)
    alloc.free(got)
    with pytest.raises(ValueError, match="not currently allocated"):
        alloc.free(got)
    with pytest.raises(ValueError, match="not currently allocated"):
        alloc.free([BlockAllocator.NULL_BLOCK])


def test_block_table_growth_and_release():
    cfg = get_config("llama3_8b").reduced()
    kv = PagedKVCache(cfg, num_blocks=9, block_size=4, slots=2, max_seq=16)
    assert kv.ensure(0, 5)                       # 2 blocks
    assert list(kv.block_tables[0][:2]) != [0, 0]
    assert kv.ensure(0, 8) and len(kv.owned[0]) == 2   # still 2 blocks
    assert kv.ensure(0, 9) and len(kv.owned[0]) == 3
    with pytest.raises(ValueError, match="blocks_per_seq"):
        kv.ensure(0, 17)                         # beyond max_seq
    assert kv.ensure(1, 16)                      # 4 more; 1 usable block left
    kv.release(0)                                # slot 0's 3 blocks return
    assert (kv.block_tables[0] == 0).all() and kv.owned[0] == []
    assert kv.allocator.num_free == 4
    assert kv.ensure(0, 16)                      # exactly refills the pool
    assert not kv.can_allocate(1)                # exhausted -> engine preempts


# --------------------------------------------------------------- sampler ----
def test_sampler_greedy_topk_topp():
    rng = np.random.default_rng(3)
    logits = rng.normal(size=(4, 64)).astype(np.float32)
    u = rng.random((4, 64))
    greedy = sampling.sample_tokens(
        logits, temperature=np.zeros(4, np.float32),
        top_k=np.zeros(4, np.int64), top_p=np.ones(4, np.float32), uniforms=u)
    assert (greedy == logits.argmax(-1)).all()
    # top-k=1 at any temperature is argmax too
    k1 = sampling.sample_tokens(
        logits, temperature=np.full(4, 1.5, np.float32),
        top_k=np.ones(4, np.int64), top_p=np.ones(4, np.float32), uniforms=u)
    assert (k1 == logits.argmax(-1)).all()
    # top-k=8: every draw lands inside each row's top-8 set
    for trial in range(20):
        u = rng.random((4, 64))
        drawn = sampling.sample_tokens(
            logits, temperature=np.full(4, 1.0, np.float32),
            top_k=np.full(4, 8, np.int64), top_p=np.ones(4, np.float32),
            uniforms=u)
        for row, tok in enumerate(drawn):
            assert tok in set(np.argsort(logits[row])[-8:])
    # tiny top-p: nucleus collapses to the argmax
    peaked = np.zeros((2, 16), np.float32)
    peaked[:, 5] = 10.0
    tp = sampling.sample_tokens(
        peaked, temperature=np.ones(2, np.float32),
        top_k=np.zeros(2, np.int64), top_p=np.full(2, 0.1, np.float32),
        uniforms=rng.random((2, 16)))
    assert (tp == 5).all()


def test_seeded_sampling_is_packing_invariant():
    """temperature>0 outputs depend only on the request's seed, not on which
    batch-mates it shares the pool with."""
    cfg = get_config("llama3_8b").reduced()
    params = _params(cfg)
    prompts = _prompts(cfg, 3)
    sp = [SamplingParams(temperature=0.9, top_k=8, max_new_tokens=5, seed=i)
          for i in range(3)]

    eng = Engine(cfg, params, engine_cfg=EngineConfig(
        slots=3, max_seq=32, prefill_chunk=8))
    for i, p in enumerate(prompts):
        eng.add_request(p, sp[i], rid=i)
    packed = eng.run()

    for i, p in enumerate(prompts):
        solo = Engine(cfg, params, engine_cfg=EngineConfig(
            slots=1, max_seq=32, prefill_chunk=8))
        solo.add_request(p, sp[i], rid=0)
        assert solo.run()[0] == packed[i], f"request {i} depends on packing"


# -------------------------------------------------- continuous batching -----
@pytest.mark.parametrize("arch", FAMILIES)
def test_continuous_greedy_matches_solo(arch):
    """Greedy decode is bit-identical packed vs alone for every family —
    including per-slot SSM/hybrid state (mamba2/zamba2)."""
    cfg = get_config(arch).reduced()
    params = _params(cfg)
    prompts = _prompts(cfg, 4)
    sp = SamplingParams(max_new_tokens=6)

    eng = Engine(cfg, params, engine_cfg=EngineConfig(
        slots=3, max_seq=32, prefill_chunk=8))   # 4 requests > 3 slots
    for i, p in enumerate(prompts):
        eng.add_request(p, sp, rid=i)
    packed = eng.run()
    assert set(packed) == set(range(4))

    for i, p in enumerate(prompts):
        solo = Engine(cfg, params, engine_cfg=EngineConfig(
            slots=1, max_seq=32, prefill_chunk=8))
        solo.add_request(p, sp, rid=0)
        assert solo.run()[0] == packed[i], f"{arch} request {i} differs packed"


def test_preemption_recovers_greedy_outputs():
    """A starved pool forces mid-decode evictions; re-prefill on re-admission
    must reproduce the unpressured outputs exactly."""
    cfg = get_config("llama3_8b").reduced()
    params = _params(cfg)
    prompts = _prompts(cfg, 3, lo=6, hi=10)
    sp = SamplingParams(max_new_tokens=8)

    roomy = Engine(cfg, params, engine_cfg=EngineConfig(
        slots=3, max_seq=32, prefill_chunk=8))
    for i, p in enumerate(prompts):
        roomy.add_request(p, sp, rid=i)
    want = roomy.run()

    evicted = []
    tight = Engine(cfg, params, engine_cfg=EngineConfig(
        slots=3, max_seq=32, prefill_chunk=8, block_size=4, num_blocks=11),
        on_preempt=lambda r: evicted.append(r.rid))
    for i, p in enumerate(prompts):
        tight.add_request(p, sp, rid=i)
    got = tight.run()
    assert tight.last_stats["preemptions"] >= 1 and evicted
    assert got == want


def test_streaming_callback_and_stats():
    cfg = get_config("llama3_8b").reduced()
    eng = Engine(cfg, _params(cfg), engine_cfg=EngineConfig(
        slots=2, max_seq=32, prefill_chunk=8))
    seen = []
    eng.add_request(np.arange(2, 7, dtype=np.int32),
                    SamplingParams(max_new_tokens=4), rid=7,
                    on_token=lambda rid, tok, done: seen.append((rid, tok, done)))
    results = eng.run()
    assert [t for _, t, _ in seen] == results[7]
    assert seen[-1][2] and not any(d for _, _, d in seen[:-1])
    st7 = eng.request_stats[7]
    assert st7["prompt_len"] == 5 and st7["new_tokens"] == len(results[7])
    assert st7["ttft_s"] is not None and st7["latency_s"] >= st7["ttft_s"]
    assert eng.last_stats["requests"] == 1


def test_add_request_validation():
    cfg = get_config("llama3_8b").reduced()
    eng = Engine(cfg, _params(cfg), engine_cfg=EngineConfig(slots=1, max_seq=16))
    with pytest.raises(ValueError, match="empty"):
        eng.add_request(np.zeros(0, np.int32))
    with pytest.raises(ValueError, match="no room"):
        eng.add_request(np.ones(16, np.int32))


# ---------------------------------------------------------------- int8 KV ---
def test_int8_paged_kv_matches_bf16_within_bound():
    """int8 K/V storage: greedy serving still completes and the per-step
    logits stay within the quantization error bound of the bf16 paged path."""
    from repro.api.quant import rows_error_bound  # noqa: F401 (the bound's source)

    cfg = get_config("llama3_8b").reduced()
    params = _params(cfg)
    prompt = _prompts(cfg, 1)[0]
    outs = {}
    for kvq in ("none", "int8"):
        eng = Engine(cfg, params, engine_cfg=EngineConfig(
            slots=1, max_seq=32, prefill_chunk=8, kv_quant=kvq))
        eng.add_request(prompt, SamplingParams(max_new_tokens=6), rid=0)
        logits_trace = []
        orig = eng._decode

        def spy(p, pools, cur, ctx, bt, _orig=orig, _trace=logits_trace):
            logits, pools = _orig(p, pools, cur, ctx, bt)
            _trace.append(np.asarray(logits[0, -1], np.float32))
            return logits, pools

        eng._decode = spy
        outs[kvq] = (eng.run()[0], logits_trace)
    # errors compound over steps only through the (identical-until-divergence)
    # token stream; compare the first decode step, which shares inputs exactly
    err = np.abs(outs["none"][1][0] - outs["int8"][1][0]).max()
    assert err < 0.25, f"int8 KV logits off by {err}"
    assert outs["int8"][0][:1] == outs["none"][0][:1], "first token flipped"


def test_int8_capacity_beats_bf16_at_fixed_bytes():
    for arch in ("llama3_8b", "deepseek_v2_lite_16b", "zamba2_2_7b"):
        cfg = get_config(arch).reduced()
        per_bf16 = bytes_per_block(cfg, 16, "none")
        per_int8 = bytes_per_block(cfg, 16, "int8")
        assert 0 < per_int8 < per_bf16, arch
        budget = 64 * per_bf16
        b16 = blocks_for_budget(cfg, budget, 16, "none")
        i8 = blocks_for_budget(cfg, budget, 16, "int8")
        assert i8 > b16, f"{arch}: int8 fits {i8} <= bf16 {b16}"
        assert (max_concurrent(cfg, i8, 64, 16)
                > max_concurrent(cfg, b16, 64, 16)), arch


def test_pure_ssm_has_no_paged_bytes():
    cfg = get_config("mamba2_370m").reduced()
    assert bytes_per_block(cfg, 16, "none") == 0
    with pytest.raises(ValueError, match="no paged KV bytes"):
        blocks_for_budget(cfg, 1 << 20, 16, "none")


# ----------------------------------------------------------- compat layer ---
def test_server_wrapper_matches_engine():
    cfg = get_config("llama3_8b").reduced()
    params = _params(cfg)
    prompts = _prompts(cfg, 3)
    scfg = ServerConfig(batch_slots=2, max_seq=32, max_new_tokens=5,
                        temperature=0.0, top_k=0, prefill_chunk=8)
    srv = Server(cfg, scfg, params)
    reqs = [Request(rid=i, prompt=p) for i, p in enumerate(prompts)]
    via_server = srv.serve(reqs)
    assert all(r.done and r.out_tokens == via_server[r.rid] for r in reqs)
    assert srv.last_stats["requests"] == 3

    eng = Engine(cfg, params, engine_cfg=EngineConfig(
        slots=2, max_seq=32, prefill_chunk=8))
    for i, p in enumerate(prompts):
        eng.add_request(p, SamplingParams(max_new_tokens=5, seed=i), rid=i)
    assert eng.run() == via_server


def test_wave_server_still_serves_with_per_request_caps():
    cfg = get_config("llama3_8b").reduced()
    scfg = ServerConfig(batch_slots=2, max_seq=32, max_new_tokens=8,
                        temperature=0.0, top_k=0)
    ws = WaveServer(cfg, scfg, _params(cfg))
    reqs = [Request(rid=0, prompt=np.arange(2, 6, dtype=np.int32), max_new=3),
            Request(rid=1, prompt=np.arange(2, 9, dtype=np.int32))]
    results = ws.serve(reqs)
    assert len(results[0]) == 3                  # per-request cap honored
    assert len(results[1]) <= 8
    assert ws.last_stats["decode_steps"] > 0


def test_engine_sharded_backend_requires_plan():
    import dataclasses
    cfg = dataclasses.replace(get_config("llama3_8b").reduced(),
                              matmul_backend="dip_tp")
    with pytest.raises(ValueError, match="ShardingPlan"):
        Engine(cfg, engine_cfg=EngineConfig(slots=1, max_seq=16))


def test_dip_tp_sharded_serving_smoke():
    """End-to-end paged serving over a 2-way model mesh: KV-head pools shard
    over 'model', block tables stay host-side, outputs match unsharded."""
    _run("""
import dataclasses
from repro.configs import get_config
from repro.distributed.plan import make_local_mesh, make_plan
from repro.models import transformer as tf_model
from repro.serving import Engine, EngineConfig, SamplingParams

cfg = get_config("llama3_8b").reduced()
params = tf_model.init_params(jax.random.PRNGKey(0), cfg)
prompt = np.arange(2, 9, dtype=np.int32)
sp = SamplingParams(max_new_tokens=4)

ref = Engine(cfg, params, engine_cfg=EngineConfig(slots=2, max_seq=32,
                                                  prefill_chunk=8))
ref.add_request(prompt, sp, rid=0)
want = ref.run()[0]

tp_cfg = dataclasses.replace(cfg, sharding="tp", matmul_backend="dip_tp")
mesh = make_local_mesh(data=1, model=2)
plan = make_plan(mesh, tp_cfg, "decode")
eng = Engine(tp_cfg, params, engine_cfg=EngineConfig(slots=2, max_seq=32,
                                                     prefill_chunk=8),
             plan=plan)
eng.add_request(prompt, sp, rid=0)
got = eng.run()[0]
assert len(got) == len(want) == 4, (got, want)
assert got == want, f"sharded serving diverged: {got} vs {want}"
print("SHARDED_SERVE_OK")
""", devices=2)


def test_gumbel_boundary_uniform_stays_finite():
    """Regression (pre-PR bug): the upper clip was ``1.0 - 1e-20``, which IS
    1.0 in float64 — a boundary uniform of exactly 1.0 produced +inf Gumbel
    noise that hijacked the argmax (and turned a top-k-masked lane into
    inf + -inf = nan).  The clip must land strictly below 1.0."""
    g = sampling.gumbel_from_uniform(np.array([0.0, 0.5, 1.0, np.nextafter(1.0, 2.0)]))
    assert np.isfinite(g).all(), g

    # end-to-end: one row fed u==1.0 everywhere must still draw from its
    # top-k set, never a masked lane, never token 0 by nan-argmax accident
    logits = np.zeros((1, 16), np.float32)
    logits[0, :4] = 10.0  # only tokens 0-3 are plausible
    tok = sampling.sample_tokens(
        logits, temperature=np.ones(1, np.float32),
        top_k=np.full(1, 4, np.int64), top_p=np.ones(1, np.float32),
        uniforms=np.ones((1, 16)))
    assert int(tok[0]) in range(4)
