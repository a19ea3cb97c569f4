"""The serving engine's host spans (repro.serving.telemetry).

A tiny engine serves three requests (one sampled, two whose prompts span
two prefill chunks, one that waits for a slot); the spans it leaves must
nest under their tick, match the engine's own counters, and carry the
request and row counts the metrics read.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as tf_model
from repro.serving import Engine, EngineConfig, SamplingParams, telemetry

CHUNK = 8
PROMPTS = (12, 14, 5)            # tokens; the first two take two chunks
WORK = ("engine.admit", "engine.prefill", "engine.import", "engine.decode",
        "engine.fetch", "engine.sample")


@pytest.fixture(scope="module")
def served():
    cfg = get_config("llama3_8b").reduced()
    params = tf_model.init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, engine_cfg=EngineConfig(
        slots=2, max_seq=48, prefill_chunk=CHUNK))
    rng = np.random.default_rng(0)
    for rid, n in enumerate(PROMPTS):
        sp = SamplingParams(temperature=0.9 if rid == 1 else 0.0,
                            max_new_tokens=4, seed=rid)
        eng.add_request(rng.integers(2, cfg.vocab_size, n).astype(np.int32), sp,
                        rid=rid)
    telemetry.clear()
    eng.run()
    spans = telemetry.spans()
    return eng, spans, {s.id: s for s in spans}


def _tick(s, by_id):
    while s is not None and s.name != "engine.step":
        s = by_id.get(s.parent)
    return s


@pytest.mark.parametrize("name", WORK)
def test_every_span_is_inside_a_tick(served, name):
    _, spans, by_id = served
    mine = [s for s in spans if s.name == name]
    assert mine
    for s in mine:
        tick = _tick(s, by_id)
        assert tick is not None and tick.start <= s.start <= s.end <= tick.end


@pytest.mark.parametrize("name,counter", [("engine.prefill", "_prefill_chunks"),
                                          ("engine.decode", "_decode_steps")])
def test_span_count_matches_engine_counter(served, name, counter):
    eng, spans, _ = served
    assert sum(s.name == name for s in spans) == getattr(eng, counter) > 0


def test_ticks_are_numbered_roots(served):
    eng, spans, _ = served
    steps = [s for s in spans if s.name == "engine.step"]
    assert [s.n for s in steps] == list(range(1, eng._tick + 1))
    assert all(s.parent == -1 for s in steps)


def test_each_decode_is_followed_by_one_fetch_and_sample_of_its_rows(served):
    _, spans, by_id = served
    by_tick = {}
    for s in spans:
        if s.name != "engine.step":
            by_tick.setdefault(_tick(s, by_id).id, []).append(s)
    decodes = 0
    for inside in by_tick.values():
        dec = [s for s in inside if s.name == "engine.decode"]
        first = [s for s in inside if s.rid >= 0 and s.name in ("engine.fetch", "engine.sample")]
        rows = [s for s in inside if s.rid < 0 and s.name in ("engine.fetch", "engine.sample")]
        imports = sum(s.name == "engine.import" for s in inside)
        # a tick that finishes a prefill draws that request's first token
        assert sorted(s.name for s in first) == ["engine.fetch", "engine.sample"] * imports
        assert all(s.n == 1 for s in first)
        if not dec:
            assert not rows
            continue
        decodes += 1
        (d,) = dec
        fetch, sample = sorted(rows, key=lambda s: s.start)
        assert (fetch.name, sample.name) == ("engine.fetch", "engine.sample")
        assert d.end <= fetch.start and fetch.end <= sample.start
        assert d.n == fetch.n == sample.n > 0
    assert decodes > 0


@pytest.mark.parametrize("rid", range(len(PROMPTS)))
def test_a_requests_spans_share_its_rid(served, rid):
    _, spans, _ = served
    mine = sorted((s for s in spans if s.rid == rid), key=lambda s: s.start)
    names = [s.name for s in mine]
    chunks = -(-PROMPTS[rid] // CHUNK)
    assert names == (["engine.admit"] + ["engine.prefill"] * chunks
                     + ["engine.import", "engine.fetch", "engine.sample"])
    admit, *prefill, imp, fetch, sample = mine
    assert admit.n == imp.n == PROMPTS[rid] == sum(s.n for s in prefill)
    assert fetch.n == sample.n == 1


def test_queue_wait_is_recorded(served):
    eng, _, _ = served
    st = eng.request_stats
    for rid in range(len(PROMPTS)):
        assert 0.0 <= st[rid]["queue_s"] <= st[rid]["ttft_s"] <= st[rid]["latency_s"]
    # two slots: the third request waits until one of the first two finishes
    assert st[2]["queue_s"] > max(st[0]["ttft_s"], st[1]["ttft_s"])


@pytest.mark.parametrize("attr,name", [("_decode", "jit_engine_decode"),
                                       ("_prefill_fwd", "jit_engine_prefill_chunk")])
def test_programs_carry_the_engines_names(served, attr, name):
    eng = served[0]
    if attr == "_decode":
        args = (eng.params, eng.kv.pools, jnp.asarray(eng._cur), jnp.asarray(eng._ctx),
                jnp.asarray(eng.kv.block_tables))
    else:
        args = (eng.params, tf_model.init_cache(eng.cfg, 1, eng._prefill_buf_len),
                jnp.zeros((1, CHUNK), jnp.int32))
    assert f"module @{name} " in getattr(eng, attr).lower(*args).as_text()


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


def test_spans_nest_and_record_what_the_handle_holds():
    r = telemetry.Recorder(clock=FakeClock())
    with r.span("outer", n=7) as outer:
        with r.span("inner", rid=3) as inner:
            inner.n = 5
    with pytest.raises(ValueError):
        with r.span("failed"):
            raise ValueError
    got = {s.name: s for s in r.spans()}
    assert got["outer"] == telemetry.Span(outer.id, "outer", 1.0, 4.0, -1, -1, 7)
    assert got["inner"] == telemetry.Span(inner.id, "inner", 2.0, 3.0, outer.id, 3, 5)
    assert got["failed"].parent == -1 and got["failed"].end > got["failed"].start
    r.clear()
    assert r.spans() == []


def test_ring_drops_the_oldest_at_capacity():
    r = telemetry.Recorder(capacity=4, clock=FakeClock())
    for i in range(6):
        with r.span("s", n=i):
            pass
    assert [s.n for s in r.spans()] == [2, 3, 4, 5]
    assert telemetry.CAPACITY == 65536
