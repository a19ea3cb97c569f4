"""A serving run with its timed path broken underneath must come out not
correct: the harness's run and check on the CPU, with the chip look
skipped, against the limits of the served configurations."""

import json
import time

import pytest

from bench import faults, run, serve
from bench.tests.conftest import ROOT, tiny_cell

CONFIGS = ["yi_9b", "codeqwen15_7b"]


def verdicts(cell):
    rec = serve.run(cell, time.monotonic)
    out = {}
    for name in CONFIGS:
        conf = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
        out[name] = run.verdict(conf, cell.mix, rec)
    return rec, out


@pytest.mark.parametrize("mix", ["chat_poisson", "repo_backlog"])
def test_sound_run_is_correct(mix):
    rec, out = verdicts(tiny_cell(mix, backend="pallas_dip"))
    assert rec["check"]["unchecked"] == 0
    assert all(ok for ok, _ in out.values()), out


def test_altered_token_is_caught():
    with faults.altered_token():
        rec, out = verdicts(tiny_cell("repo_backlog"))
    assert not any(ok for ok, _ in out.values()), out


def test_decode_that_keeps_its_cache_is_caught():
    # the chat cell's own prompt and output lengths, at d_model 128
    cell = tiny_cell("chat_poisson", lengths="cell")
    with faults.stale_cache():
        rec, out = verdicts(cell)
    assert not any(ok for ok, _ in out.values()), out
