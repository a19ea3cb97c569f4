"""A later cell brings only new files: a configuration, a traffic mix and a
metric reader, found by the names in BENCHMARK.json, with no file of the
harness edited."""

import hashlib
import itertools
import json
import shutil

import numpy as np

from bench import gen, run
from bench.tests.conftest import ROOT


def digest(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*")) if p.is_file()}


def test_new_cell_found_by_name(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = digest(tmp_path)

    conf = json.loads((ROOT / "bench" / "configs" / "yi_9b.json").read_text())
    conf["num_hidden_layers"] = 2
    (tmp_path / "bench" / "configs" / "dummy_model.json").write_text(json.dumps(conf))
    mix = json.loads((ROOT / "bench" / "traffic" / "chat_poisson.json").read_text())
    mix.update(rate_per_s=3.0, prompt_tokens=dict(dist="uniform", min=10, max=20))
    (tmp_path / "bench" / "traffic" / "dummy_mix.json").write_text(json.dumps(mix))
    (tmp_path / "bench" / "metrics" / "dummy_count.py").write_text(
        "def read(rec):\n    return float(len(rec['served']))\n")
    bench["configs"].append({"name": "dummy_model", "source": "x",
                             "file": "bench/configs/dummy_model.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "dummy_model.dummy_mix", "config": "dummy_model",
                               "traffic": "dummy_mix", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "dummy_count.x", "unit": "1", "better": "higher",
                               "source": "program_counter", "layer": "serving.engine",
                               "moves": "setup_s", "workloads": ["dummy_model.dummy_mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    wl, got_conf, got_mix, metrics, readers = run.resolve(
        "dummy_model.dummy_mix", True, root=tmp_path)
    assert got_conf["num_hidden_layers"] == 2 and got_mix["rate_per_s"] == 3.0
    assert [m["name"] for m in metrics] == ["dummy_count.x"]
    assert readers["dummy_count.x"].read({"served": {1: None, 2: None}}) == 2.0
    _, _, _, e2e, _ = run.resolve("dummy_model.dummy_mix", False, root=tmp_path)
    assert [m["name"] for m in e2e] == ["setup_s"]
    reqs = list(itertools.islice(gen.requests(got_mix, 5, 100), 32))
    assert all(10 <= r.prompt.size <= 20 for r in reqs)
    after = digest(tmp_path)
    assert all(after[p] == h for p, h in before.items())


def test_every_declared_metric_has_a_reader():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert hasattr(run.reader(m["name"]), "read"), m["name"]


def test_seed_fixes_the_requests():
    mix = json.loads((ROOT / "bench" / "traffic" / "chat_poisson.json").read_text())
    big = 2**31 + 9
    a = list(itertools.islice(gen.requests(mix, big, 64000), 400))
    again = list(itertools.islice(gen.requests(mix, big, 64000), 400))
    other = list(itertools.islice(gen.requests(mix, 1, 64000), 400))
    assert all(x.due_s == y.due_s and x.max_new == y.max_new and x.seed == y.seed
               and np.array_equal(x.prompt, y.prompt) for x, y in zip(a, again))
    assert [r.prompt.size for r in a] != [r.prompt.size for r in other]
    # plain draws: exponential gaps at the rate, lengths in range, half greedy
    gaps = np.diff([0.0] + [r.due_s for r in a])
    assert abs(gaps.mean() * mix["rate_per_s"] - 1) < 0.15
    assert all(64 <= r.prompt.size <= 2048 and 16 <= r.max_new <= 512 for r in a)
    assert 320 < np.median([r.prompt.size for r in a]) < 460
    assert 160 < sum(r.greedy for r in a) < 240


def test_block_gives_every_seed_the_same_sizes():
    mix = json.loads((ROOT / "bench" / "traffic" / "repo_backlog.json").read_text())
    block = mix["block"]
    a = list(itertools.islice(gen.requests(mix, 1, 92416), 3 * block))
    b = list(itertools.islice(gen.requests(mix, 2**31 + 9, 92416), 3 * block))
    assert [r.prompt.size for r in a] != [r.prompt.size for r in b]
    for start in range(0, 3 * block, block):
        for got in (a, b):
            part = got[start:start + block]
            assert sorted(r.prompt.size for r in part) == sorted(
                gen.quantile_length(mix["prompt_tokens"], (j + 0.5) / block) for j in range(block))
            assert sorted(r.max_new for r in part) == sorted(r.max_new for r in a[:block])
    assert all(r.due_s == 0 and r.greedy for r in a)
