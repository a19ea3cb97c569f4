"""Operation and byte counts against hand-computed ones at the published
widths of both configurations."""

import json

import pytest

from bench import weights as W
from bench import work
from bench.tests.conftest import ROOT


def dims(name):
    conf = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    return W.Dims({k: v for k, v in conf.items() if isinstance(v, (int, float, bool))})


def test_yi_model_flops():
    # per layer: q, o 4096x4096; k, v 4096x512; gate+up 2x4096x11008; down 11008x4096
    d = dims("yi_9b")
    weights = sum(k * n * nw for _, k, n, nw in work.projections(d))
    assert weights == 2 * 4096 * 4096 + 2 * 4096 * 512 + 3 * 4096 * 11008 == 173_015_040
    n = 8 * weights + 4096 * 64000
    assert n == 1_646_264_320            # with the embedding: 1.908 B params
    assert work.model_flops(d, 32, 32 * 700) == 2 * 32 * n + 4 * 32 * 128 * 32 * 700 * 8


def test_codeqwen_model_flops():
    # GQA, 4 KV heads: q, o 4096x4096; k, v 4096x512; 3 x 4096x13440
    d = dims("codeqwen15_7b")
    layer = 2 * 4096 * 4096 + 2 * 4096 * 512 + 3 * 4096 * 13440
    assert layer == 202_899_456
    n = 4 * layer + 4096 * 92416
    assert n == 1_190_133_760
    # one token at context 5000: 2N + 4 * heads * head_dim * context * layers
    assert work.model_flops(d, 1, 5000) == 2 * n + 4 * 32 * 128 * 5000 * 4
    assert work.model_flops(d, 1, 5000, train=True) == 3 * work.model_flops(d, 1, 5000)


@pytest.mark.parametrize("name,kv", [("yi_9b", 4), ("codeqwen15_7b", 4)])
def test_causal_chunk_attention(name, kv):
    # a 256-query chunk after 512 cached positions meets 256*512 + 256*257/2 keys
    flops, nbytes = work.attention_work(dims(name), q_len=256, offset=512)
    assert flops == 4 * 32 * 128 * 163_968
    assert nbytes == 2 * (2 * 256 * 32 * 128 + 2 * 768 * kv * 128)


def test_least_time_takes_the_larger_bound():
    peak = work.peaks("TPU v5 lite")
    assert work.least_time(197e12, 0, peak) == pytest.approx(1.0)
    assert work.least_time(0, 819e9, peak) == pytest.approx(1.0)
    with pytest.raises(KeyError):
        work.peaks("no such chip")


@pytest.mark.parametrize("name,text,rows,proj", [
    # a prefill chunk's q projection with the RMSNorm prologue's operands
    ("yi_9b", "%dip_matmul_pallas.50 = bf16[256,4096]{1,0:T(8,128)(2,1)S(1)} custom-call("
     "bf16[256,4096]{1,0} %bitcast.123, bf16[4096,4096]{1,0} %fusion.33, "
     "f32[256,1]{1,0} %copy.64, f32[1,4096]{1,0} %c.8), custom_call_target=\"tpu_custom_call\"",
     256, (4096, 4096, 1)),
    # the fused SwiGLU pair of a decode step: two weights, one ff-wide
    # output; 4 slots run on rows padded to 8
    ("yi_9b", "%dip_matmul_pallas.7 = bf16[8,11008]{1,0} custom-call(bf16[8,4096]{1,0} %a, "
     "bf16[4096,11008]{1,0} %b, f32[8,1]{1,0} %c, f32[1,4096]{1,0} %d, "
     "bf16[4096,11008]{1,0} %e), custom_call_target=\"tpu_custom_call\"",
     4, (4096, 11008, 2)),
    # the down projection with its fused residual, K padded to the tile grid
    ("yi_9b", "%dip_matmul_pallas.9 = bf16[8,4096]{1,0} custom-call(bf16[8,11264]{1,0} %a, "
     "bf16[11264,4096]{1,0} %b, bf16[8,4096]{1,0} %r), custom_call_target=\"tpu_custom_call\"",
     4, (11008, 4096, 1)),
    # the head, its vocabulary padded to 65536 columns
    ("yi_9b", "%dip_matmul_pallas.49 = bf16[256,65536]{1,0} custom-call(bf16[256,4096]{1,0} %a, "
     "bf16[4096,65536]{1,0} %b), custom_call_target=\"tpu_custom_call\"",
     256, (4096, 64000, 1)),
    # codeqwen's k projection (4 KV heads) beside its bias
    ("codeqwen15_7b", "%dip_matmul_pallas.3 = bf16[8,512]{1,0} custom-call(bf16[8,4096]{1,0} %a, "
     "bf16[4096,512]{1,0} %b, f32[1,512]{1,0} %bias), custom_call_target=\"tpu_custom_call\"",
     8, (4096, 512, 1)),
])
def test_kernel_call_work_from_its_shapes(name, text, rows, proj):
    """A call is counted at the model's projection it computes, not at the
    storage's padded sizes."""
    got = work.projection_of(dims(name), *work.kernel_call_shape(text))
    assert got[1:] == proj
    k, n, w = proj
    assert work.matmul_work(rows, k, n, w) == (2 * rows * k * n * w,
                                               2 * (rows * k + w * k * n + rows * n))


def test_call_no_projection_fits():
    assert work.projection_of(dims("yi_9b"), 4096, 256, 1) is None
