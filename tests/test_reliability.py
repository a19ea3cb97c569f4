"""The reliability layer's contracts (repro.reliability; docs/reliability.md).

Five load-bearing properties:

1. **No observer effect** — ``api.matmul(..., verify=True)`` returns output
   *bit-identical* to the unverified call across backend x epilogue x dtype
   (verification is a post-hoc audit, never a different computation), and a
   clean dispatch never false-positives.
2. **Detection** — a seeded bit flip / planted NaN in weight storage trips
   the probe (float) or the integer-exact storage compare (quantized);
   injection itself is deterministic (same seed => same corruption).
3. **Fail-safe training** — a corrupted ``DipWeight`` mid-run is detected by
   the fingerprint guard, the poisoned update is skipped, counters
   increment, and the trainer restores the latest checkpoint.
4. **Fail-safe serving** — a poisoned KV block surfaces as a nonfinite
   logits row; the request is retried (re-prefill on clean blocks) or
   degraded to the ``xla`` decode path while batch-mates keep streaming.
5. **Integrity under crashes** — the block allocator holds its invariants
   when alloc/free raise mid-operation (fail-points), and a checkpoint save
   crashed mid-write never corrupts the latest restorable step; storage rot
   is caught by per-leaf CRCs that name the corrupt leaf.
"""

import os

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

import jax
import jax.numpy as jnp

from repro import api
from repro import reliability as rel
from repro.checkpoint.manager import (
    CheckpointManager, restore_pytree, save_pytree,
)
from repro.configs import get_config
from repro.configs.base import ArchConfig
from repro.models import transformer as tf_model
from repro.reliability.inject import InjectedFault, failpoint
from repro.runtime.trainer import Trainer, TrainerConfig
from repro.serving import BlockAllocator, Engine, EngineConfig, SamplingParams


def _weight_for(backend, w):
    be = api.get_backend(backend)
    if be.layout == "dip_q":
        return api.quant.quantize(jnp.asarray(w, jnp.float32), be.scheme)
    if be.layout == "dip":
        return api.DipWeight.from_natural(jnp.asarray(w))
    return jnp.asarray(w)


# ----------------------------------------------------------- no observer ----
VERIFY_MATRIX = [
    # backend, epilogue, dtype — one cell per backend family x epilogue
    # class x coarse dtype; bit-identity is the acceptance criterion
    ("xla", "none", "float32"),
    ("xla", "bias", "float32"),
    ("ws", "none", "bfloat16"),
    ("ws", "swiglu", "float32"),
    ("pallas_dip", "none", "float32"),
    ("pallas_dip", "bias", "bfloat16"),
    ("pallas_systolic", "residual", "float32"),
    ("dip_int8w", "none", "float32"),
    ("dip_int8w", "bias_gelu", "bfloat16"),
    ("dip_fp8", "none", "float32"),
]


def _inputs(backend, epilogue, dtype, m=16, k=64, n=64, seed=0):
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(0, 1, (m, k)).astype(np.float32)).astype(dtype)
    wg = jnp.asarray(r.normal(0, 1, (k, n)).astype(np.float32))
    wu = jnp.asarray(r.normal(0, 1, (k, n)).astype(np.float32))
    if epilogue == "swiglu":
        wobj = (_weight_for(backend, wg), _weight_for(backend, wu))
        ops = ()
    elif epilogue.startswith("bias"):
        wobj = _weight_for(backend, wg)
        ops = (jnp.asarray(r.normal(0, 1, (n,)).astype(np.float32)),)
    elif epilogue == "residual":
        wobj = _weight_for(backend, wg)
        ops = (jnp.asarray(r.normal(0, 1, (m, n)).astype(np.float32)),)
    else:
        wobj = _weight_for(backend, wg)
        ops = ()
    wobj = rel.attach_checksums(wobj)
    return x, wobj, ops


@pytest.mark.parametrize("backend,epilogue,dtype", VERIFY_MATRIX)
def test_verified_is_bit_identical_and_clean(backend, epilogue, dtype):
    """With injection disabled: verify=True output == unverified output
    bit-for-bit, and the audit reports ok on every rung it picked."""
    x, wobj, ops = _inputs(backend, epilogue, dtype)
    plain = api.matmul(x, wobj, backend=backend, epilogue=epilogue,
                       epilogue_operands=ops)
    out, report = api.matmul(x, wobj, backend=backend, epilogue=epilogue,
                             epilogue_operands=ops, verify=True)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(out))
    assert bool(report["ok"]), (backend, epilogue, dtype, report)
    assert report["mode"] in ("probe", "storage")
    rel.raise_on_fault(report)  # must not raise on a clean report


def test_probe_mode_selection():
    """auto => probe exactly where the row-sum identity holds; an explicit
    probe request elsewhere is a caller error."""
    x, wobj, _ = _inputs("pallas_dip", "none", "float32")
    _, rep = api.matmul(x, wobj, backend="pallas_dip", verify=True)
    assert rep["mode"] == "probe"
    xs, wsw, _ = _inputs("pallas_dip", "swiglu", "float32")
    _, rep = api.matmul(xs, wsw, backend="pallas_dip", epilogue="swiglu",
                        verify=True)
    assert rep["mode"] == "storage"  # nonlinear epilogue: probe invalid
    with pytest.raises(ValueError, match="probe verification is invalid"):
        api.matmul(xs, wsw, backend="pallas_dip", epilogue="swiglu",
                   verify="probe")


# -------------------------------------------------------------- detection ---
def test_probe_detects_weight_bitflip():
    x, dw, _ = _inputs("pallas_systolic", "none", "float32")
    bad = rel.bitflip(dw.data, seed=3, bit=30)     # exponent bit: loud
    dwc = dw.with_data(bad, checksum=dw.checksum)  # stale checksum = reference
    out, rep = api.matmul(x, dwc, backend="pallas_systolic", verify=True)
    assert not bool(rep["ok"])
    assert int(rep["rows_flagged"]) > 0
    with pytest.raises(rel.ReliabilityError, match="ABFT verification failed"):
        rel.raise_on_fault(rep)


def test_storage_compare_detects_quant_code_flip():
    """A single int8 code flip is far below the analog probe tolerance —
    the integer-exact storage compare is what catches it."""
    x, qw, _ = _inputs("dip_int8w", "none", "float32")
    bad = rel.bitflip(qw.data, seed=5, bit=6)
    qc = qw.with_data(bad, qw.scale, checksum=qw.checksum)
    _, rep = api.matmul(x, qc, backend="dip_int8w", verify="storage")
    assert not bool(rep["ok"])
    _, rep_auto = api.matmul(x, qc, backend="dip_int8w", verify=True)
    assert not bool(rep_auto["ok"])  # probe folds the storage compare in


def test_planted_nan_output_flagged():
    x, w, _ = _inputs("xla", "none", "float32")
    xn = rel.plant_nan(x, seed=0)
    out, rep = api.matmul(xn, w, backend="xla", verify=True)
    assert not bool(rep["finite"]) and not bool(rep["ok"])


@settings(max_examples=10)
@given(seed=st.integers(0, 2**16), bit=st.integers(0, 31))
def test_injection_is_deterministic(seed, bit):
    arr = jnp.asarray(np.random.default_rng(0).normal(size=(32, 16)),
                      jnp.float32)
    a = np.asarray(rel.bitflip(arr, seed=seed, bit=bit))
    b = np.asarray(rel.bitflip(arr, seed=seed, bit=bit))
    np.testing.assert_array_equal(a, b)
    assert (a != np.asarray(arr)).sum() == 1  # exactly one element touched
    n1 = np.asarray(rel.plant_nan(arr, seed=seed))
    n2 = np.asarray(rel.plant_nan(arr, seed=seed))
    np.testing.assert_array_equal(n1, n2)
    assert np.isnan(n1).sum() == 1


def test_corrupt_pytree_targets_by_path():
    tree = {"layers": {"q": jnp.ones((4, 4)), "k": jnp.ones((4, 4))}}
    new, hit = rel.corrupt_pytree(tree, "k", seed=0, mode="nan")
    assert "k" in hit and np.isnan(np.asarray(new["layers"]["k"])).any()
    np.testing.assert_array_equal(np.asarray(new["layers"]["q"]),
                                  np.asarray(tree["layers"]["q"]))
    with pytest.raises(KeyError):
        rel.corrupt_pytree(tree, "nonexistent", seed=0)


# ---------------------------------------------------------- fail-points -----
@settings(max_examples=15)
@given(num_blocks=st.integers(4, 24), seed=st.integers(0, 10_000),
       fail_at=st.integers(1, 6))
def test_allocator_invariants_under_injected_failures(num_blocks, seed, fail_at):
    """Random alloc/free interleavings with alloc/free raising at an
    injected point: the free/allocated partition of blocks 1..nb-1 must
    survive every crash (no leak, no double-ownership)."""
    rng = np.random.default_rng(seed)
    alloc = BlockAllocator(num_blocks)
    held = []

    def check():
        free = set(alloc._free)
        used = set(alloc._allocated)
        assert not (free & used)
        assert free | used == set(range(1, num_blocks))
        assert BlockAllocator.NULL_BLOCK not in free | used
        held_flat = {b for blocks in held for b in blocks}
        assert held_flat == used

    name = "kv.alloc" if rng.integers(2) else "kv.free"
    with failpoint(name, exc=InjectedFault("chaos"), count=int(fail_at)):
        for _ in range(30):
            try:
                if rng.integers(2) and alloc.num_free:
                    got = alloc.alloc(int(rng.integers(1, alloc.num_free + 1)))
                    if got is not None:
                        held.append(got)
                elif held:
                    i = int(rng.integers(len(held)))
                    alloc.free(held[i])  # atomic: raises => still ours
                    held.pop(i)
            except InjectedFault:
                pass
            check()


def test_checkpoint_crc_names_corrupt_leaf(tmp_path):
    tree = {"a": jnp.arange(16, dtype=jnp.float32),
            "b": jnp.ones((4, 4), jnp.bfloat16)}
    path = str(tmp_path / "ck")
    save_pytree(path, tree)
    # rot one byte of leaf b's payload on disk
    victim = None
    import json
    with open(os.path.join(path, "manifest.json")) as f:
        for e in json.load(f)["leaves"]:
            if "b" in e["path"]:
                victim = os.path.join(path, e["file"])
    blob = bytearray(open(victim, "rb").read())
    blob[-1] ^= 0xFF
    open(victim, "wb").write(bytes(blob))
    with pytest.raises(ValueError, match="integrity failure at leaf .*b"):
        restore_pytree(path, jax.eval_shape(lambda: tree))
    # the untouched checkpoint still restores
    save_pytree(str(tmp_path / "ck2"), tree)
    restore_pytree(str(tmp_path / "ck2"), jax.eval_shape(lambda: tree))


def test_checkpoint_mid_save_crash_is_atomic(tmp_path):
    """A save killed between leaf writes (or before the rename) leaves the
    previous step fully restorable and only a GC-able tmp orphan behind."""
    tree = {"w": jnp.arange(8, dtype=jnp.float32),
            "b": jnp.zeros((3,), jnp.float32)}
    mgr = CheckpointManager(str(tmp_path), keep=5)
    mgr.save(1, tree, blocking=True)

    for name in ("checkpoint.save.mid_write", "checkpoint.save.pre_rename"):
        with failpoint(name, exc=InjectedFault(name)):
            with pytest.raises(InjectedFault):
                save_pytree(mgr._step_path(2), tree)
        assert mgr.latest_step() == 1, name
    restored, _ = mgr.restore(jax.eval_shape(lambda: tree))
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(tree["w"]))
    # a fresh manager garbage-collects the orphaned tmp dirs
    CheckpointManager(str(tmp_path), keep=5)
    assert not [n for n in os.listdir(tmp_path) if ".tmp-" in n]


# ------------------------------------------------------- fail-safe train ----
def _tiny_cfg():
    return ArchConfig(
        name="tiny", family="dense", n_layers=2, d_model=32, n_heads=2,
        n_kv_heads=2, d_ff=64, vocab_size=128, head_dim=16, remat="none",
        compute_dtype="float32",
    )


def test_train_guard_detects_flip_skips_and_recovers(tmp_path):
    """Acceptance chaos test (a): a seeded bit flip planted in a parameter
    mid-run is detected, the poisoned step is skipped, counters increment,
    and training recovers from the latest checkpoint and completes."""
    fault_step = 5

    def hook(step_no, state):
        if step_no == fault_step:
            params, hit = rel.corrupt_pytree(
                state["params"], "layers", seed=7, mode="nan"
            )
            state = dict(state, params=params)
            hook.hit = hit
        return state

    tr = Trainer(
        _tiny_cfg(),
        TrainerConfig(steps=8, ckpt_every=2, ckpt_dir=str(tmp_path),
                      keep=5, async_ckpt=False, log_every=100, guard=True),
        seq_len=32, global_batch=4, step_hook=hook,
    )
    out = tr.run()
    assert out["weight_faults"] >= 1
    assert out["skipped"] >= 1
    assert out["recoveries"] >= 1
    assert int(out["state"]["step"]) == 8
    # post-recovery params are finite — the NaN never entered committed state
    for leaf in jax.tree_util.tree_leaves(out["state"]["params"]):
        assert np.isfinite(np.asarray(leaf, np.float32)).all()
    skipped_metrics = [m for m in out["metrics"] if m.get("skipped")]
    assert skipped_metrics and skipped_metrics[0]["weight_fault"] == 1.0


def test_train_guard_without_checkpoint_raises(tmp_path):
    """recover_on_fault with no checkpoint on disk: the guard refuses to
    continue on corrupt weights and names the fault."""
    def hook(step_no, state):
        if step_no == 1:
            params, _ = rel.corrupt_pytree(state["params"], "layers",
                                           seed=1, mode="nan")
            state = dict(state, params=params)
        return state

    tr = Trainer(
        _tiny_cfg(),
        TrainerConfig(steps=4, ckpt_every=100, ckpt_dir=str(tmp_path),
                      async_ckpt=False, log_every=100, guard=True),
        seq_len=32, global_batch=4, step_hook=hook,
    )
    with pytest.raises(rel.ReliabilityError, match="weight corruption"):
        tr.run()


def test_guard_clean_run_matches_unguarded(tmp_path):
    """With no fault injected the guard must not change training: losses of
    guarded and unguarded runs are identical step for step."""
    def train(guard, sub):
        return Trainer(
            _tiny_cfg(),
            TrainerConfig(steps=4, ckpt_every=100,
                          ckpt_dir=str(tmp_path / sub), async_ckpt=False,
                          log_every=100, guard=guard),
            seq_len=32, global_batch=4,
        ).run()

    a, b = train(False, "a"), train(True, "b")
    for ma, mb in zip(a["metrics"], b["metrics"]):
        assert ma["loss"] == mb["loss"]
    assert b["skipped"] == 0 and b["weight_faults"] == 0


# ------------------------------------------------------- fail-safe serve ----
def _engine(verify=True, max_retries=1, slots=2, **kw):
    cfg = get_config("llama3_8b").reduced()
    params = tf_model.init_params(jax.random.PRNGKey(0), cfg)
    ecfg = EngineConfig(slots=slots, max_seq=96, prefill_chunk=32,
                        verify=verify, max_retries=max_retries, **kw)
    return Engine(cfg, params, engine_cfg=ecfg, seed=0), cfg


def _run_with_kv_fault(eng, r_victim, ticks=4):
    for _ in range(ticks):
        eng.step()
    req = next(r for r in eng._slots if r is not None and r.rid == r_victim)
    blk = eng.kv.owned[req.slot][0]
    rel.corrupt_kv_block(eng.kv, blk, mode="nan")
    return eng.run()


def test_serve_kv_corruption_retried_peers_served():
    """Acceptance chaos test (b): NaN-poisoned KV block mid-decode is
    detected, the victim is retried on clean blocks and completes, the
    batch-mate streams through untouched."""
    eng, _ = _engine(verify=True, max_retries=1)
    r0 = eng.add_request(np.arange(2, 20, dtype=np.int32),
                         SamplingParams(max_new_tokens=8))
    r1 = eng.add_request(np.arange(5, 30, dtype=np.int32),
                         SamplingParams(max_new_tokens=8))
    out = _run_with_kv_fault(eng, r0)
    assert len(out[r0]) == 8 and len(out[r1]) == 8
    assert eng.last_stats["faults_detected"] == 1
    assert eng.last_stats["retries"] == 1
    assert eng.request_stats[r0]["retries"] == 1
    assert not eng.request_stats[r0]["degraded"]
    assert eng.request_stats[r1]["retries"] == 0

    # peer's tokens are bit-identical to a clean solo run (greedy)
    solo, _ = _engine(verify=True)
    rs = solo.add_request(np.arange(5, 30, dtype=np.int32),
                          SamplingParams(max_new_tokens=8))
    assert solo.run()[rs] == out[r1]


def test_serve_exhausted_retries_degrade_to_xla():
    """max_retries=0: the first fault degrades the request to the xla
    decode path; it still completes, flagged degraded, engine healthy."""
    eng, _ = _engine(verify=True, max_retries=0)
    r0 = eng.add_request(np.arange(2, 20, dtype=np.int32),
                         SamplingParams(max_new_tokens=6))
    r1 = eng.add_request(np.arange(5, 30, dtype=np.int32),
                         SamplingParams(max_new_tokens=6))
    out = _run_with_kv_fault(eng, r0, ticks=2)
    assert len(out[r0]) == 6 and len(out[r1]) == 6
    assert eng.last_stats["degraded_requests"] == 1
    assert eng.request_stats[r0]["degraded"]
    assert eng._decode_xla is not None  # the fallback path was compiled


def test_serve_verify_off_is_undisturbed():
    """verify=False: zero reliability overhead paths run; stats stay 0."""
    eng, _ = _engine(verify=False)
    r0 = eng.add_request(np.arange(2, 20, dtype=np.int32),
                         SamplingParams(max_new_tokens=4))
    out = eng.run()
    assert len(out[r0]) == 4
    assert eng.last_stats["faults_detected"] == 0
    assert eng._decode_xla is None


def test_deadline_ttl_sweeps_waiting_request():
    eng, _ = _engine(verify=False, slots=1)
    r0 = eng.add_request(np.arange(2, 20, dtype=np.int32),
                         SamplingParams(max_new_tokens=6))
    # r1 can never be admitted before its deadline (one slot, ttl ~ 0)
    r1 = eng.add_request(np.arange(5, 30, dtype=np.int32),
                         SamplingParams(max_new_tokens=6), ttl_s=0.0)
    out = eng.run()
    assert len(out[r0]) == 6
    assert out[r1] == []
    assert eng.last_stats["deadline_evictions"] == 1
    assert eng.request_stats[r1]["deadline_expired"]
    assert not eng.request_stats[r0]["deadline_expired"]


def test_admission_capacity_fail_fast():
    """Regression: a prompt whose KV need exceeds the whole pool used to
    sit at the queue head forever and spin run(); now it fails at intake."""
    cfg = get_config("llama3_8b").reduced()
    params = tf_model.init_params(jax.random.PRNGKey(0), cfg)
    ecfg = EngineConfig(slots=2, max_seq=96, prefill_chunk=32, num_blocks=3)
    eng = Engine(cfg, params, engine_cfg=ecfg, seed=0)
    with pytest.raises(ValueError, match="can never be admitted"):
        eng.add_request(np.arange(2, 90, dtype=np.int32),
                        SamplingParams(max_new_tokens=4))
    # a prompt that fits is unaffected
    rid = eng.add_request(np.arange(2, 20, dtype=np.int32),
                          SamplingParams(max_new_tokens=2))
    assert len(eng.run()[rid]) == 2


# ----------------------------------------------------------- guard unit -----
def test_guarded_step_fn_skip_semantics():
    """Unit-level: nonfinite loss => params/opt unchanged, step advances,
    counters increment; healthy step commits normally."""
    def fake_step(state, batch):
        new = {
            "params": jax.tree_util.tree_map(lambda p: p + 1.0, state["params"]),
            "opt_state": state["opt_state"],
            "step": state["step"] + 1,
        }
        return new, {"loss": batch["loss"], "grad_norm": jnp.float32(1.0),
                     "step": new["step"]}

    g = rel.guarded_step_fn(fake_step)
    state = rel.init_guard_state({
        "params": {"w": jnp.zeros((2,))},
        "opt_state": {"m": jnp.zeros((2,))},
        "step": jnp.zeros((), jnp.int32),
    })
    state, m = g(state, {"loss": jnp.float32(1.0)})
    assert float(state["params"]["w"][0]) == 1.0 and int(state["step"]) == 1
    state, m = g(state, {"loss": jnp.float32(np.nan)})
    assert float(state["params"]["w"][0]) == 1.0   # poisoned update dropped
    assert int(state["step"]) == 2                 # step always advances
    assert int(state["skipped"]) == 1 and float(m["skipped"]) == 1.0
    state, m = g(state, {"loss": jnp.float32(0.5)})
    assert float(state["params"]["w"][0]) == 2.0
    assert int(state["skipped"]) == 1
