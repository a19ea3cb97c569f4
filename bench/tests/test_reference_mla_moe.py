"""The MLA + MoE family's benchmark code on the CPU at tiny sizes: its
reference against the program's own (``repro.models.reference``) on the
benchmark's weights, a whole ``mla_backlog`` run through the engine, and
its readers on synthetic records, with counts worked by hand at the
published widths."""

import dataclasses
import json
import time
import types

import jax
import numpy as np
import pytest

from bench import mla, program, run, work
from bench import reference_mla_moe as reference
from bench import weights_mla_moe as W
from bench import work_mla_moe
from bench.tests.conftest import ROOT


def conf_file():
    return json.loads((ROOT / "bench" / "configs" / "deepseek_v2_lite_16b.json").read_text())


def tiny_conf(backend="xla", compute="float32"):
    """The configuration file at tiny widths: 1 dense and 2 MoE layers, 4 of
    8 experts held from expert 2, YaRN over 64 original positions."""
    from repro.configs.base import Yarn

    conf = conf_file()
    conf.update(hidden_size=128, num_attention_heads=4, num_key_value_heads=4,
                qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32, kv_lora_rank=64,
                intermediate_size=256, moe_intermediate_size=64, num_hidden_layers=3,
                vocab_size=512, n_routed_experts=4, first_held_expert=2,
                num_experts_per_tok=2, published={"n_routed_experts": 8})
    conf["rope_scaling"] = dict(conf["rope_scaling"], original_max_position_embeddings=64)
    y = conf["rope_scaling"]
    conf["run"] = dict(
        matmul_backend=backend, compute_dtype=compute, param_dtype="float32",
        qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32, kv_lora_rank=64,
        n_experts=8, moe_top_k=2, d_ff_expert=64, moe_held_experts=4, moe_first_expert=2,
        rope_yarn=Yarn(factor=y["factor"], original_max_position=64, beta_fast=y["beta_fast"],
                       beta_slow=y["beta_slow"], mscale=y["mscale"],
                       mscale_all_dim=y["mscale_all_dim"]))
    return conf


def test_reference_matches_the_programs_reference():
    from repro.models import reference as program_reference

    conf = tiny_conf()
    cfg, dims = program.arch_config(conf), W.Dims(conf)
    key = W.seed_key(2**33 + 21)
    params = mla.load_params(cfg, dims, key)
    toks = np.random.default_rng(0).integers(0, dims.vocab, 150).astype(np.int32)
    want = np.asarray(program_reference.forward(params, cfg, toks))
    got = reference.logits_at(key, dims, toks, np.arange(toks.size))
    assert np.abs(got - want).max() < 1e-4 * max(1.0, np.abs(want).max())
    low = reference.logits_at(key, dims, toks, np.arange(toks.size), mode="int8")
    assert np.abs(low - want).max() > 100 * np.abs(got - want).max()


def test_weights_of_a_share_are_the_uncut_experts():
    conf = tiny_conf()
    whole = W.Dims(dict(conf, n_routed_experts=8, first_held_expert=0))
    share = W.Dims(conf)
    key = W.seed_key(3)
    a = W.ffn_weights(key, whole, 1, False)["w_gate"]
    b = W.ffn_weights(key, share, 1, False)["w_gate"]
    np.testing.assert_array_equal(np.asarray(a[2:6]), np.asarray(b))


def tiny_cell(seed=2**31 + 77):
    mix = json.loads((ROOT / "bench" / "traffic" / "longdoc_backlog.json").read_text())
    mix.update(queue=10, drain_max_s=30, engine={"slots": 4, "max_seq": 256,
                                                 "prefill_chunk": 64})
    mix["prompt_tokens"].update(min=40, max=150)
    mix["output_tokens"].update(min=4, max=12)
    return run.Cell(workload="tiny", conf=tiny_conf(), mix=mix, seed=seed, seconds=2.0,
                    trace=False, trace_dir=None, device=jax.devices()[0])


def test_a_run_through_the_engine_is_correct():
    cell = tiny_cell()
    rec = mla.run(cell, time.monotonic)
    rec.update(cell=cell, host_window=(cell.t_open, rec["t_close"]))
    check = rec["check"]
    assert check["unchecked"] == 0 and check["tokens_checked"] > 0
    assert check["moe_dropped"] == 0
    # float32 compute: the served logits are the reference's
    assert check["logit_err_mean"] < 1e-4 and check["gap_mean"] < 1e-4
    series = rec["moe"]
    assert series and all(b[1] >= a[1] for a, b in zip(series, series[1:]))
    assert run.reader("moe_row_use.longdoc").read(rec) == pytest.approx(100.0)
    assert rec["notes"]["moe_routed"] == series[-1][1] > 0


def _synthetic_cell():
    conf = conf_file()
    mix = json.loads((ROOT / "bench" / "traffic" / "longdoc_backlog.json").read_text())
    return types.SimpleNamespace(conf=conf, mix=mix, trace_window=(10.0, 20.0),
                                 device=types.SimpleNamespace(device_kind="TPU v5 lite"))


def test_published_counts():
    dims = W.Dims(conf_file())
    attn = 2048 * 3072 + 2048 * 512 + 2048 * 64 + 512 * 16 * 256 + 16 * 128 * 2048
    assert attn == 13_762_560                          # 13.76 M a layer
    assert work_mla_moe.shared_weights(dims) == (
        9 * attn + 3 * 2048 * 10944 + 8 * (2048 * 64 + 3 * 2048 * 2816) + 2048 * 102400)
    assert work_mla_moe.expert_flops(dims) == 2 * 3 * 2048 * 1408
    pairs = 2048 * 16384 + 2048 * 2049 // 2
    flops, nbytes = work_mla_moe.attention_work(dims, 2048, 16384)
    assert flops == 2 * 16 * (192 + 128) * pairs
    assert nbytes == 2 * 16 * 320 * (2048 + 18432)
    assert work_mla_moe.model_flops(dims, 3, 10, 7) == (
        2 * 3 * work_mla_moe.shared_weights(dims) + 7 * 17_301_504 + 2 * 16 * 320 * 10 * 9)


def test_readers_on_a_synthetic_record():
    cell = _synthetic_cell()
    dims = W.Dims(cell.conf)
    tick = dataclasses.make_dataclass("Tick", ["start", "end", "decode", "prefill",
                                               "chunk_offset"])
    ticks = [tick(11.0, 11.2, 1, 1, 4096), tick(11.2, 11.3, 1, 0, 0),
             tick(25.0, 25.2, 1, 1, 0)]            # outside the traced slice
    least = work.least_time(*work_mla_moe.attention_work(dims, 2048, 4096),
                            work.peaks("TPU v5 lite"))
    rec = {"cell": cell, "ticks": ticks,
           "trace": {"kernels": {"flash_attention": {"count": 9, "seconds": 0.05}}}}
    got = run.reader("flash_attn_roofline.longdoc").read(rec)
    assert got == pytest.approx(100.0 * 9 * least / 0.05)

    srv = types.SimpleNamespace(req=types.SimpleNamespace(prompt=np.zeros(100)),
                                times=[21.0, 22.0, 23.0, 40.0])
    series = [(19.0, 0, 0, 0), (20.5, 10, 10, 0), (24.0, 130, 130, 0), (31.0, 500, 500, 0)]
    rec = {"cell": cell, "served": {0: srv}, "moe": series, "host_window": (20.0, 30.0)}
    flops = work_mla_moe.model_flops(dims, 100 + 2, 100 * 101 // 2 + 101 + 102, 120)
    assert run.reader("mfu.longdoc").read(rec) == pytest.approx(100.0 * flops / 10.0 / 197e12)
    assert run.reader("moe_row_use.longdoc").read(
        dict(rec, moe=[(20.5, 10, 40, 0), (24.0, 30, 120, 0)])) == pytest.approx(25.0)
    assert run.reader("moe_row_use.longdoc").read(dict(rec, moe=[])) is None
    assert work_mla_moe.counts_between(series, 20.0, 30.0) == (120, 120, 0)
