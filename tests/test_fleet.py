"""The fleet driver's schema IS the acceptance contract (benchmarks/fleet.py).

Property-tested (hypothesis): synthetic fleet documents round-trip
through ``validate_fleet_json`` and JSON serialization; the baseline differ
rejects launch-count regressions, collective-count regressions, vanished
cells, and newly-failing stages no matter where in the matrix they occur.
Plus deterministic unit coverage of the cell-config mapping (the
matrix axis -> effective backend/quantization resolution), the tiny-matrix
coverage guarantees, and the peak-live-bytes estimator.
"""

import copy
import json

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

fleet = pytest.importorskip("benchmarks.fleet")

ARCHS = ["llama3_8b", "deepseek_v2_lite_16b", "zamba2_2_7b"]


# ---------------------------------------------------------- doc synthesis ----
def _stage(pallas=4, psum=0, ag=0, wall=123.4, status="ok", ppermute=0,
           a2a=0, rs=0):
    if status != "ok":
        return {"status": status, "reason": "synthetic"}
    return {
        "status": "ok", "wall_us": wall, "pallas_calls": pallas,
        "collectives": {"psum": psum, "all_gather": ag,
                        "all_to_all": a2a, "ppermute": ppermute,
                        "reduce_scatter": rs},
        "peak_live_bytes": 1 << 20,
    }


def _cell(arch="llama3_8b", backend="pallas_dip", sharding="gspmd",
          pallas=4, psum=0, ag=0):
    effective = backend
    if sharding not in ("gspmd", "pp") and backend != "xla":
        effective = fleet.SHARDED_EFFECTIVE[sharding]
    quant = fleet.QUANT_FOR_BACKEND[backend]
    probe = None
    if effective in ("dip_tp", "dip_ep"):
        probe = {"pallas_calls": 1, "collectives": dict.fromkeys(
            fleet.COLLECTIVES, 0)}
    elif effective == "dip_fsdp":
        probe = {"pallas_calls": 1, "collectives": dict(
            dict.fromkeys(fleet.COLLECTIVES, 0), all_gather=1)}
    elif effective == "dip_sp":
        probe = {"pallas_calls": 2, "collectives": dict(
            dict.fromkeys(fleet.COLLECTIVES, 0), ppermute=1)}
    vprobe = None
    if sharding == "gspmd":
        vprobe = {"pallas_calls_unverified": pallas,
                  "pallas_calls_verified": pallas,
                  "extra_pallas_calls": 0}
    # keep the synthetic cell legal under the per-strategy contracts:
    # dip_sp never all_gathers, dip_ep carries the 2-a2a pair, pp records
    # serving stages skipped and ppermutes in train
    a2a = 2 if effective == "dip_ep" else 0
    if effective == "dip_sp":
        ag = 0
    stages = {
        "train": _stage(pallas, psum, ag, a2a=a2a,
                        ppermute=1 if sharding == "pp" else 0,
                        status="skipped" if quant != "none" else "ok"),
        "prefill": _stage(pallas, psum, ag, a2a=a2a),
        # dip_tp decode must not all_gather — keep the synthetic legal
        "decode": _stage(pallas, psum,
                         0 if effective == "dip_tp" else ag, a2a=a2a),
    }
    if sharding == "pp":
        stages["prefill"] = _stage(status="skipped")
        stages["decode"] = _stage(status="skipped")
    return {
        "arch": arch, "backend": backend, "sharding": sharding,
        "effective_backend": effective, "quantization": quant,
        "column_probe": probe, "verify_probe": vprobe,
        "stages": stages,
    }


def _doc(cells, matrix="custom"):
    return {
        "schema_version": fleet.FLEET_SCHEMA_VERSION,
        "generated_by": "benchmarks/fleet.py", "jax_backend": "cpu",
        "matrix": matrix, "dims": dict(fleet.DIMS), "devices": 1,
        "cells": cells,
    }


# ------------------------------------------------------- validator props ----
@settings(max_examples=25)
@given(pallas=st.integers(min_value=0, max_value=40),
       psum=st.integers(min_value=0, max_value=8),
       arch=st.sampled_from(ARCHS),
       backend=st.sampled_from(list(fleet.BACKENDS)),
       sharding=st.sampled_from(list(fleet.SHARDINGS)))
def test_validator_roundtrips_valid_documents(pallas, psum, arch, backend,
                                              sharding):
    doc = _doc([_cell(arch, backend, sharding, pallas=pallas, psum=psum)])
    fleet.validate_fleet_json(doc)                       # direct
    fleet.validate_fleet_json(json.loads(json.dumps(doc)))   # JSON round-trip
    fleet.diff_fleet_json(doc, copy.deepcopy(doc))       # self-diff is clean


@settings(max_examples=25)
@given(base=st.integers(min_value=0, max_value=30),
       bump=st.integers(min_value=1, max_value=5),
       stage=st.sampled_from(["prefill", "decode"]))
def test_differ_rejects_launch_count_regression(base, bump, stage):
    doc = _doc([_cell(pallas=base)])
    worse = copy.deepcopy(doc)
    worse["cells"][0]["stages"][stage]["pallas_calls"] = base + bump
    with pytest.raises(ValueError, match="pallas_calls regressed"):
        fleet.diff_fleet_json(worse, doc)
    fleet.diff_fleet_json(doc, worse)    # fewer launches than baseline: fine


@settings(max_examples=25)
@given(bump=st.integers(min_value=1, max_value=5),
       kind=st.sampled_from(list(fleet.COLLECTIVES)))
def test_differ_rejects_collective_count_regression(bump, kind):
    doc = _doc([_cell(backend="xla", sharding="tp", psum=1)])
    worse = copy.deepcopy(doc)
    coll = worse["cells"][0]["stages"]["decode"]["collectives"]
    coll[kind] = coll[kind] + bump
    with pytest.raises(ValueError, match=f"{kind} count regressed"):
        fleet.diff_fleet_json(worse, doc)


@settings(max_examples=10)
@given(drop=st.integers(min_value=0, max_value=2))
def test_differ_rejects_missing_cells_and_new_failures(drop):
    cells = [_cell(a) for a in ARCHS]
    doc = _doc(cells)
    shrunk = _doc([c for i, c in enumerate(cells) if i != drop])
    with pytest.raises(ValueError, match="missing now"):
        fleet.diff_fleet_json(shrunk, doc)
    broken = copy.deepcopy(doc)
    broken["cells"][drop]["stages"]["decode"] = _stage(status="failed")
    with pytest.raises(ValueError, match="was ok in baseline"):
        fleet.diff_fleet_json(broken, doc)


def test_validator_rejects_structural_violations():
    with pytest.raises(ValueError, match="schema_version"):
        fleet.validate_fleet_json({"schema_version": 999})
    with pytest.raises(ValueError, match="non-empty"):
        fleet.validate_fleet_json(
            {"schema_version": fleet.FLEET_SCHEMA_VERSION, "cells": []})
    doc = _doc([_cell()])
    del doc["cells"][0]["stages"]["decode"]
    with pytest.raises(ValueError, match="missing record"):
        fleet.validate_fleet_json(doc)
    dup = _doc([_cell(), _cell()])
    with pytest.raises(ValueError, match="duplicate cell"):
        fleet.validate_fleet_json(dup)
    bad = _doc([_cell()])
    bad["cells"][0]["stages"]["prefill"]["wall_us"] = 0
    with pytest.raises(ValueError, match="wall_us"):
        fleet.validate_fleet_json(bad)
    # the ABFT verify contract is schema, not just a test: a gspmd cell
    # must carry a probe, and the audit must add ZERO pallas launches
    noprobe = _doc([_cell()])
    noprobe["cells"][0]["verify_probe"] = None
    with pytest.raises(ValueError, match="needs a verify_probe"):
        fleet.validate_fleet_json(noprobe)
    leaky = _doc([_cell()])
    leaky["cells"][0]["verify_probe"]["extra_pallas_calls"] = 1
    with pytest.raises(ValueError, match="zero kernels"):
        fleet.validate_fleet_json(leaky)


def test_validator_enforces_placement_contracts():
    """dip_tp columns: ZERO collectives; dip_fsdp: one all_gather, no psum;
    dip_tp decode never all_gathers.  These are the PR-5 placement wins as
    schema rules."""
    tp = _doc([_cell(sharding="tp")])
    tp["cells"][0]["column_probe"]["collectives"]["psum"] = 1
    with pytest.raises(ValueError, match="zero"):
        fleet.validate_fleet_json(tp)

    fsdp = _doc([_cell(sharding="fsdp")])
    fsdp["cells"][0]["column_probe"]["collectives"]["all_gather"] = 2
    with pytest.raises(ValueError, match="exactly"):
        fleet.validate_fleet_json(fsdp)

    noprobe = _doc([_cell(sharding="tp")])
    noprobe["cells"][0]["column_probe"] = None
    with pytest.raises(ValueError, match="column_probe"):
        fleet.validate_fleet_json(noprobe)

    leak = _doc([_cell(sharding="tp")])
    leak["cells"][0]["stages"]["decode"]["collectives"]["all_gather"] = 1
    with pytest.raises(ValueError, match="must not all_gather"):
        fleet.validate_fleet_json(leak)


def test_validator_enforces_overlap_contracts():
    """The PR-10 communication-hiding wins as schema rules: dip_sp gathers
    inside the kernel (ppermute-only probe, no all_gather anywhere), dip_ep
    carries exactly the dispatch/combine all_to_all pair, pp trains with the
    boundary ppermute and records serving stages skipped."""
    sp = _doc([_cell(sharding="sp")])
    sp["cells"][0]["column_probe"]["collectives"]["all_gather"] = 1
    with pytest.raises(ValueError, match="inside"):
        fleet.validate_fleet_json(sp)
    sp = _doc([_cell(sharding="sp")])
    sp["cells"][0]["column_probe"]["collectives"]["ppermute"] = 0
    with pytest.raises(ValueError, match="ppermute >= 1"):
        fleet.validate_fleet_json(sp)
    sp = _doc([_cell(sharding="sp")])
    sp["cells"][0]["stages"]["prefill"]["collectives"]["all_gather"] = 1
    with pytest.raises(ValueError, match="never all_gather"):
        fleet.validate_fleet_json(sp)

    ep = _doc([_cell(sharding="ep")])
    ep["cells"][0]["stages"]["prefill"]["collectives"]["all_to_all"] = 3
    with pytest.raises(ValueError, match="exactly 2 all_to_alls"):
        fleet.validate_fleet_json(ep)
    ep = _doc([_cell(sharding="ep")])
    ep["cells"][0]["column_probe"]["collectives"]["psum"] = 1
    with pytest.raises(ValueError, match="zero"):
        fleet.validate_fleet_json(ep)
    ep = _doc([_cell(sharding="ep")])
    ep["cells"][0]["stages"]["train"]["collectives"]["all_to_all"] = 0
    with pytest.raises(ValueError, match="dispatch/combine"):
        fleet.validate_fleet_json(ep)

    pp = _doc([_cell(sharding="pp")])
    pp["cells"][0]["stages"]["decode"] = _stage()
    with pytest.raises(ValueError, match="skipped"):
        fleet.validate_fleet_json(pp)
    pp = _doc([_cell(sharding="pp")])
    pp["cells"][0]["stages"]["train"]["collectives"]["ppermute"] = 0
    with pytest.raises(ValueError, match="boundary ppermute"):
        fleet.validate_fleet_json(pp)


def test_validator_tiny_matrix_requires_full_arch_coverage():
    """In a tiny/full document every arch must pass all three stages in at
    least one cell — the acceptance headline of the fleet baseline."""
    broken = _doc([_cell("llama3_8b"), _cell("zamba2_2_7b")], matrix="tiny")
    broken["cells"][1]["stages"]["train"] = _stage(status="failed")
    with pytest.raises(ValueError, match="zamba2_2_7b.*no cell passing"):
        fleet.validate_fleet_json(broken)
    # same document as a custom (filtered) matrix is fine
    broken["matrix"] = "custom"
    fleet.validate_fleet_json(broken)


# ------------------------------------------------------------ cell config ----
def test_cell_config_effective_backend_and_quant_mapping():
    cfg, eff, quant, mesh = fleet.cell_config("llama3_8b", "pallas_dip", "gspmd")
    assert (eff, quant, mesh) == ("pallas_dip", "none", None)
    assert cfg.matmul_backend == "pallas_dip"

    cfg, eff, quant, mesh = fleet.cell_config("llama3_8b", "pallas_dip", "tp")
    assert eff == "dip_tp" and cfg.matmul_backend == "dip_tp"
    assert mesh == {"data": 1, "model": 2}
    assert cfg.compute_dtype == "float32"     # forced-host-device precision

    cfg, eff, quant, mesh = fleet.cell_config("yi_9b", "dip_int8w", "fsdp")
    assert eff == "dip_fsdp" and quant == "int8"
    assert cfg.quantization == "int8" and mesh == {"data": 2, "model": 1}

    cfg, eff, quant, mesh = fleet.cell_config("llama3_8b", "xla", "tp")
    assert eff == "xla"                       # GSPMD places the collectives
    assert cfg.matmul_backend == "xla" and mesh == {"data": 1, "model": 2}

    cfg, eff, quant, _ = fleet.cell_config("musicgen_medium", "dip_fp8", "gspmd")
    assert quant == "fp8_e4m3" and cfg.quantization == "fp8_e4m3"

    cfg, eff, quant, mesh = fleet.cell_config("llama3_8b", "pallas_dip", "sp")
    assert eff == "dip_sp" and cfg.matmul_backend == "dip_sp"
    assert cfg.sharding == "sp" and mesh == {"data": 1, "model": 2}

    cfg, eff, quant, mesh = fleet.cell_config(
        "qwen3_moe_235b_a22b", "pallas_dip", "ep")
    assert eff == "dip_ep" and cfg.sharding == "ep"
    assert mesh == {"data": 1, "model": 2}

    cfg, eff, quant, mesh = fleet.cell_config("llama3_8b", "pallas_dip", "pp")
    assert eff == "pallas_dip"        # stages run the config's own backend
    assert cfg.sharding == "pp" and mesh == {"stage": 2, "data": 1, "model": 1}


def test_tiny_matrix_covers_every_arch_with_full_stage_cells():
    from repro.configs import ALL_ARCHS

    cells = fleet.tiny_cells(ALL_ARCHS)
    assert len(cells) == len(set(cells)), "duplicate cells in tiny matrix"
    for arch in ALL_ARCHS:
        # at least one float replicated cell -> all three stages can pass
        assert any(c == (arch, "xla", "gspmd") for c in cells)
        assert any(c == (arch, "pallas_dip", "gspmd") for c in cells)
        assert any(c == (arch, "dip_int8w", "gspmd") for c in cells)
    assert ("llama3_8b", "pallas_dip", "tp") in cells
    assert ("llama3_8b", "pallas_dip", "fsdp") in cells
    assert ("llama3_8b", "pallas_dip", "sp") in cells
    assert ("zamba2_2_7b", "pallas_dip", "sp") in cells
    assert ("qwen3_moe_235b_a22b", "pallas_dip", "ep") in cells
    assert ("deepseek_v2_lite_16b", "pallas_dip", "ep") in cells
    assert ("llama3_8b", "pallas_dip", "pp") in cells
    # arch filters subset consistently
    sub = fleet.tiny_cells(["llama3_8b"])
    assert set(sub) <= set(cells) and all(a == "llama3_8b" for a, _, _ in sub)


def test_full_matrix_is_cartesian():
    cells = fleet.full_cells(["a", "b"])
    assert len(cells) == 2 * len(fleet.BACKENDS) * len(fleet.SHARDINGS)
    assert len(set(cells)) == len(cells)


# ----------------------------------------------------- peak-bytes + CSV ----
def test_estimate_peak_live_bytes_tracks_dominant_intermediate():
    import jax.numpy as jnp

    def small(x):
        return (x @ x).sum()

    def big(x):
        y = jnp.concatenate([x] * 8, axis=0)     # 8x intermediate
        return (y @ x).sum()

    x = np.zeros((32, 32), np.float32)
    lo = fleet.estimate_peak_live_bytes(small, x)
    hi = fleet.estimate_peak_live_bytes(big, x)
    assert lo >= x.nbytes                        # inputs are resident
    assert hi >= lo + 7 * x.nbytes               # the blow-up is visible


def test_csv_rows_follow_harness_contract():
    doc = _doc([_cell("llama3_8b"), _cell("zamba2_2_7b", backend="dip_int8w")])
    rows = fleet.csv_rows_from(doc)
    assert len(rows) == 2 * len(fleet.STAGES)
    names = [r[0] for r in rows]
    assert "fleet_llama3_8b_pallas_dip_gspmd_decode" in names
    for name, us, derived in rows:
        assert isinstance(us, float)
        if derived not in ("failed", "skipped"):
            assert "launches=" in derived and "peak_mb=" in derived
