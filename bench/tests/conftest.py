"""Tiny configurations for CPU tests of the benchmark's own code."""

import json
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = dict(program_arch="yi_9b", hidden_size=128, intermediate_size=256,
            num_attention_heads=4, num_key_value_heads=2, head_dim=32,
            num_hidden_layers=2, vocab_size=500, rope_theta=10000.0,
            rms_norm_eps=1e-5, attention_bias=False)


def tiny_mix(name: str, lengths: str = "tiny") -> dict:
    """A mix on 4 slots for the CPU: with ``lengths="cell"`` at the cell's
    own prompt and output lengths, else at short ones."""
    mix = json.loads((ROOT / "bench" / "traffic" / f"{name}.json").read_text())
    if lengths == "cell":
        mix["engine"]["slots"] = 4
    else:
        mix["engine"] = {"slots": 4, "max_seq": 256, "prefill_chunk": 32}
        for key, lo, hi, med in (("prompt_tokens", 8, 160, 40), ("output_tokens", 4, 40, 12)):
            mix[key].update(min=lo, max=hi)
            if "median" in mix[key]:
                mix[key]["median"] = med
    if "rate_per_s" in mix:
        mix.update(rate_per_s=4.0 if lengths == "tiny" else 1.0, warm_s=1.0, drain_max_s=30)
    if "queue" in mix:
        mix["queue"] = 12
    mix["check"]["requests"] = 2
    return mix


def tiny_cell(mix_name: str, *, seed: int = 2**31 + 5, seconds: float = 3.0,
              backend: str = "xla", bias: bool = False, lengths: str = "tiny",
              layers: int = 2):
    from bench import run

    conf = dict(TINY, attention_bias=bias, num_hidden_layers=layers,
                run={"matmul_backend": backend})
    conf["program_arch"] = "codeqwen15_7b" if bias else "yi_9b"
    return run.Cell(workload="tiny", conf=conf, mix=tiny_mix(mix_name, lengths), seed=seed,
                    seconds=seconds, trace=False, trace_dir=None,
                    device=jax.devices()[0])


@pytest.fixture
def cell_factory():
    return tiny_cell
