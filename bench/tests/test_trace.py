"""Trace reduction: busy union, idle gaps and their host spans, and the
per-program and per-kernel device times."""

import json
from pathlib import Path

import pytest

from bench import trace

HERE = Path(__file__).parent


def test_union_and_gaps():
    busy = trace.union([(0, 10), (5, 20), (30, 40), (40, 45)])
    assert busy == [(0, 20), (30, 45)]
    assert trace.gaps(busy, -5, 50) == [(-5, 0), (20, 30), (45, 50)]


def synthetic():
    ms = 1_000_000
    dev = [
        # a prefill program: a dip kernel, flash, a dip kernel
        ("XLA Modules", "jit_step", 0, 10 * ms),
        ("XLA Ops", "%dip_matmul_pallas.1 = bf16[256,4096] custom-call()", 0, 3 * ms),
        ("XLA Ops", "%flash_attention_pallas.2 = bf16[32,256,128] custom-call()", 3 * ms, 4 * ms),
        ("XLA Ops", "%dip_matmul_pallas.3 = bf16[256,4096] custom-call()", 7 * ms, 3 * ms),
        # a decode program: dip and a plain fusion
        ("XLA Modules", "jit_step", 20 * ms, 5 * ms),
        ("XLA Ops", "%dip_matmul_pallas.1 = bf16[32,4096] custom-call()", 20 * ms, 4 * ms),
        ("XLA Ops", "%fusion.7 = f32[32] fusion()", 24 * ms, 1 * ms),
        # an import program, no kernel
        ("XLA Modules", "jit_imp(42)", 40 * ms, 2 * ms),
        ("XLA Ops", "%scatter.2 = bf16[9] scatter()", 40 * ms, 2 * ms),
    ]
    host = [("python", "bench.engine_step", 0, 26 * ms),
            ("python", "bench.wait", 26 * ms, 14 * ms)]
    return {"device": dev, "host": host}


def test_summarize_synthetic():
    s = trace.summarize(synthetic(), window_s=0.042)
    assert s["busy_s"] == pytest.approx(0.017)
    assert s["programs"]["prefill"] == {"count": 1, "seconds": pytest.approx(0.010)}
    assert s["programs"]["decode"] == {"count": 1, "seconds": pytest.approx(0.005)}
    assert s["programs"]["import"]["count"] == 1
    assert s["kernels"]["pallas_dip"] == {"count": 3, "seconds": pytest.approx(0.010)}
    assert s["kernels"]["flash_attention"]["seconds"] == pytest.approx(0.004)
    assert s["breakdown"]["idle_gaps"][0] == ["bench.wait", pytest.approx(0.015)]
    assert s["breakdown"]["idle_gaps"][1] == ["bench.engine_step", pytest.approx(0.010)]
    assert s["breakdown"]["device_ops"][0] == ["pallas_dip", pytest.approx(0.010)]


def test_summarize_recorded_tpu_trace():
    """A slice recorded on a TPU v5e: one prefill chunk, the KV import and
    one decode step of the engine."""
    rec = json.loads((HERE / "data" / "trace_tpu_v5e.json").read_text())
    events = {side: [tuple(e) for e in rec[side]] for side in ("device", "host")}
    ops = [e for e in events["device"] if e[0] == "XLA Ops"]
    s = trace.summarize(events, window_s=0.05)
    assert {k: v["count"] for k, v in s["programs"].items()
            if k != "other"} == {"prefill": 1, "import": 1, "decode": 1}
    dip = [e for e in ops if e[1].startswith("%dip_matmul_pallas.")]
    flash = [e for e in ops if e[1].startswith("%flash_attention_pallas.")]
    assert dip and flash
    assert s["kernels"]["pallas_dip"]["count"] == len(dip)
    assert s["kernels"]["pallas_dip"]["seconds"] == pytest.approx(sum(e[3] for e in dip) / 1e9)
    assert s["kernels"]["flash_attention"]["count"] == len(flash)
    assert [n for n, _, _ in s["kernel_events"]["pallas_dip"]] == [e[1] for e in dip]
    # each call is tagged with the program it ran in: in each, six
    # projections a layer over two layers, and the head
    progs = [p for _, _, p in s["kernel_events"]["pallas_dip"]]
    assert progs.count("prefill") == progs.count("decode") == len(dip) // 2
    assert 0 < s["busy_s"] <= sum(e[3] for e in ops) / 1e9
    # the host sorts the logits for sampling while the device waits
    assert any("argsort" in label for label, _ in s["breakdown"]["idle_gaps"])
