"""Conformance for the explicit multi-chip backends (`dip_tp` / `dip_fsdp` /
`dip_sp` / `dip_ep`) and the ShardingPlan metadata they dispatch on.

Two layers of coverage:

* **Multi-device conformance** (subprocess, 8 forced host devices — shared
  helper in conftest): every epilogue x dtype for column-parallel,
  row-parallel, and fsdp dispatch against the single-device ``api.matmul``
  reference, with jaxpr-asserted collective counts (zero for column, exactly
  ONE psum for row — including the dual-weight swiglu pair — one all_gather
  per weight for fsdp), quantized weights included (bit-exact for int8 on
  the full-K paths, per the documented tolerance on the K-split path), and a
  reduced end-to-end model forward through ``dip_tp``.  ``dip_sp`` adds the
  sequence-parallel contract: NO pre-kernel all_gather — the x blocks ring
  through the kernel's load stage via ppermute issued before each fused
  launch (column) or a single reduce_scatter (row).  ``dip_ep`` adds the
  MoE expert-parallel contract: exactly TWO all_to_alls per ``moe_ffn``
  call (dispatch + combine), with the dispatch issued before the
  shared-expert compute it hides behind.
* **Plan metadata invariants** (in-process, device-count independent): the
  ``WeightPlan`` carried on a weight survives jit / scan / grad /
  checkpoint-save/restore; restore validates plans against the live mesh;
  plan-free weights decompose to GSPMD; registration rules for sharded
  layouts hold.

Tolerances (documented in docs/distributed.md): column/fsdp run the SAME
f32-accumulated kernel over the full contraction, so they match the
single-device dispatch to launch-order noise (bit-exact for int8 — identical
activation quantization and int32 accumulation); row-parallel splits K, so
float results differ by f32 reduction reordering (<= the generic f32
tolerance) and int8 results re-quantize activations per shard (compared
against the float reference within the documented int8 bound instead).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from conftest import run_forced_devices as _run
from repro.distributed.plan import make_mesh

from repro import api
from repro.checkpoint import restore_pytree, save_pytree
from repro.distributed.plan import ShardingPlan, WeightPlan, make_local_mesh, make_plan


# ===========================================================================
# multi-device conformance (subprocess; 8 forced host devices)
# ===========================================================================
def test_sharded_backends_match_single_device_every_epilogue():
    """The acceptance matrix: dip_tp(column) / dip_tp(row) / dip_fsdp vs the
    single-device pallas_dip dispatch for every epilogue x float dtype, plus
    jaxpr collective counts."""
    out = _run("""
from repro import api
from repro.distributed.plan import WeightPlan, make_local_mesh
from repro.kernels.dip_matmul_sharded import count_collectives

mesh = make_local_mesh(data=2, model=4)
col = WeightPlan("column", axis="model", fsdp="data", mesh=mesh)
row = WeightPlan("row", axis="model", fsdp="data", mesh=mesh)

m, k, n = 8, 256, 256
r = np.random.default_rng(0)
TOL = {"float32": dict(atol=2e-3, rtol=2e-3),
       "bfloat16": dict(atol=0.5, rtol=0.05)}

def inputs(epilogue, dtype):
    x = jnp.asarray(r.normal(0, 1, (m, k)).astype(np.float32)).astype(dtype)
    wg = jnp.asarray(r.normal(0, 1, (k, n)).astype(np.float32)).astype(dtype)
    wu = jnp.asarray(r.normal(0, 1, (k, n)).astype(np.float32)).astype(dtype)
    b = jnp.asarray(r.normal(0, 1, (n,)).astype(np.float32))
    resid = jnp.asarray(r.normal(0, 1, (m, n)).astype(np.float32)).astype(dtype)
    if epilogue == "swiglu":
        return x, (wg, wu), ()
    if epilogue.startswith("bias"):
        return x, wg, (b,)
    if epilogue == "residual":
        return x, wg, (resid,)
    return x, wg, ()

def wrap(w, plan):
    if isinstance(w, tuple):
        return tuple(api.DipWeight.from_natural(wi, plan=plan) for wi in w)
    return api.DipWeight.from_natural(w, plan=plan)

cases = [("dip_tp", col, "column"), ("dip_tp", row, "row"),
         ("dip_fsdp", col, "fsdp")]
for epilogue in api.EPILOGUES:
    for dtype in ("float32", "bfloat16"):
        x, w, ops = inputs(epilogue, dtype)
        want = api.matmul(x, wrap(w, None), backend="pallas_dip",
                          epilogue=epilogue, epilogue_operands=ops)
        for backend, plan, label in cases:
            got = api.matmul(x, wrap(w, plan), backend=backend,
                             epilogue=epilogue, epilogue_operands=ops)
            assert got.shape == want.shape, (label, epilogue, dtype)
            np.testing.assert_allclose(
                np.asarray(got, np.float32), np.asarray(want, np.float32),
                **TOL[dtype], err_msg=f"{label}/{epilogue}/{dtype}")
print("PARITY_OK")

# ---- fused rmsnorm prologue across sharded dispatch ----------------------
# column/fsdp keep the full K local, so the norm fuses into the per-shard
# kernel; row-parallel splits K and must DECOMPOSE (a shard cannot see the
# whole row to reduce it) — both must match the single-device fused result.
g = jnp.asarray(r.normal(1, 0.1, (k,)).astype(np.float32))
for dtype in ("float32", "bfloat16"):
    x, wg, _ = inputs("none", dtype)
    want = api.matmul(x, wrap(wg, None), backend="pallas_dip",
                      prologue="rmsnorm", prologue_operands=(g,))
    for backend, plan, label in cases:
        got = api.matmul(x, wrap(wg, plan), backend=backend,
                         prologue="rmsnorm", prologue_operands=(g,))
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            **TOL[dtype], err_msg=f"prologue/{label}/{dtype}")
print("PROLOGUE_OK")

# ---- jaxpr-asserted collective placement ---------------------------------
x, wg, _ = inputs("none", "float32")
_, pair, _ = inputs("swiglu", "float32")
def counts(backend, w, epilogue="none", ops=()):
    return count_collectives(
        lambda xx: api.matmul(xx, w, backend=backend, epilogue=epilogue,
                              epilogue_operands=ops), x)

c = counts("dip_tp", wrap(wg, col))
assert c["psum"] == 0 and c["all_gather"] == 0 and c["pallas_call"] == 1, c
c = counts("dip_tp", wrap(pair, col), "swiglu")
assert c["psum"] == 0 and c["pallas_call"] == 1, c   # ONE fused launch/shard
c = counts("dip_tp", wrap(wg, row))
assert c["psum"] == 1 and c["all_gather"] == 0 and c["pallas_call"] == 1, c
c = counts("dip_tp", wrap(pair, row), "swiglu")
assert c["psum"] == 1 and c["pallas_call"] == 2, c   # ONE psum for the pair
bias = jnp.zeros((n,), jnp.float32)
c = counts("dip_tp", wrap(wg, row), "bias_silu", (bias,))
assert c["psum"] == 1, c                             # epilogue past the psum
c = counts("dip_fsdp", wrap(wg, col))
assert c["all_gather"] == 1 and c["psum"] == 0 and c["pallas_call"] == 1, c
c = counts("dip_fsdp", wrap(pair, col), "swiglu")
assert c["all_gather"] == 2 and c["psum"] == 0 and c["pallas_call"] == 1, c
print("COLLECTIVES_OK")
""", devices=8, timeout=900)
    assert "PARITY_OK" in out and "COLLECTIVES_OK" in out
    assert "PROLOGUE_OK" in out


def test_sharded_backends_quantized_exact_for_int8():
    """Quantized dispatch through the sharded backends: the scales shard
    with N on the column path, and the full-K paths (column / fsdp) are
    BIT-EXACT vs the single-device int8 kernel (same per-row activation
    quantization, same int32 accumulation); the K-split row path re-scales
    activations per shard and is held to the documented int8-vs-float
    bound instead."""
    out = _run("""
from repro import api
from repro.distributed.plan import WeightPlan, make_local_mesh
from repro.kernels import ref

mesh = make_local_mesh(data=2, model=4)
col = WeightPlan("column", axis="model", fsdp="data", mesh=mesh)
row = WeightPlan("row", axis="model", fsdp="data", mesh=mesh)

m, k, n = 8, 256, 256
r = np.random.default_rng(1)
x = jnp.asarray(r.normal(0, 1, (m, k)).astype(np.float32))
w = jnp.asarray(r.normal(0, 1, (k, n)).astype(np.float32))
b = jnp.asarray(r.normal(0, 1, (n,)).astype(np.float32))

for scheme in sorted(api.quant.SCHEMES):
    qw = api.quant.quantize(w, scheme)
    for epilogue, ops in (("none", ()), ("bias_silu", (b,))):
        want = api.matmul(x, qw, epilogue=epilogue, epilogue_operands=ops)
        got_col = api.matmul(x, qw.with_plan(col), backend="dip_tp",
                             epilogue=epilogue, epilogue_operands=ops)
        got_fsdp = api.matmul(x, qw.with_plan(col), backend="dip_fsdp",
                              epilogue=epilogue, epilogue_operands=ops)
        if scheme == "int8":
            np.testing.assert_array_equal(np.asarray(got_col), np.asarray(want))
            np.testing.assert_array_equal(np.asarray(got_fsdp), np.asarray(want))
        else:
            np.testing.assert_allclose(np.asarray(got_col), np.asarray(want),
                                       atol=1e-5, rtol=1e-5)
            np.testing.assert_allclose(np.asarray(got_fsdp), np.asarray(want),
                                       atol=1e-5, rtol=1e-5)

# K-split row path: per-shard activation re-quantization -> compare against
# the FLOAT reference within the documented int8 bound (docs/quantization.md)
qw = api.quant.quantize(w, "int8")
got_row = api.matmul(x, qw.with_plan(row), backend="dip_tp")
want_f = np.asarray(ref.ws_matmul_ref(x, w))
dev = np.abs(np.asarray(got_row) - want_f).max() / np.abs(want_f).max()
assert dev < 0.02, f"row-parallel int8 deviation {dev}"
print("QUANT_OK")
""", devices=8, timeout=600)
    assert "QUANT_OK" in out


def test_dip_sp_parity_counts_and_schedule():
    """Sequence-parallel dispatch: parity vs the single-device kernel for a
    representative epilogue/prologue slice, then the overlap contract in the
    jaxpr — the column path issues NO all_gather (each shard's x block is
    gathered inside the kernel's load stage: tp-1 ppermutes, each issued
    BEFORE the fused launch it overlaps), the row path ends in ONE
    reduce_scatter, and int8 stays bit-exact on the full-K column path."""
    out = _run("""
from repro import api
from repro.distributed.plan import WeightPlan, make_local_mesh
from repro.kernels import ref
from repro.kernels.dip_matmul_sharded import collective_schedule, count_collectives

mesh = make_local_mesh(data=2, model=4)
col = WeightPlan("column", axis="model", fsdp="data", mesh=mesh)
row = WeightPlan("row", axis="model", fsdp="data", mesh=mesh)
m, k, n = 8, 256, 256
r = np.random.default_rng(0)
x = jnp.asarray(r.normal(0, 1, (m, k)).astype(np.float32))
wg = jnp.asarray(r.normal(0, 1, (k, n)).astype(np.float32))
wu = jnp.asarray(r.normal(0, 1, (k, n)).astype(np.float32))
b = jnp.asarray(r.normal(0, 1, (n,)).astype(np.float32))
resid = jnp.asarray(r.normal(0, 1, (m, n)).astype(np.float32))

def wrap(w, plan):
    if isinstance(w, tuple):
        return tuple(api.DipWeight.from_natural(wi, plan=plan) for wi in w)
    return api.DipWeight.from_natural(w, plan=plan)

for epi, w, ops in [("none", wg, ()), ("bias_gelu", wg, (b,)),
                    ("residual", wg, (resid,)), ("swiglu", (wg, wu), ())]:
    want = api.matmul(x, wrap(w, None), backend="pallas_dip",
                      epilogue=epi, epilogue_operands=ops)
    for plan, lbl in [(col, "col"), (row, "row")]:
        got = api.matmul(x, wrap(w, plan), backend="dip_sp",
                         epilogue=epi, epilogue_operands=ops)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-3, rtol=2e-3, err_msg=f"{lbl}/{epi}")
g = jnp.asarray(r.normal(1, 0.1, (k,)).astype(np.float32))
want = api.matmul(x, wrap(wg, None), backend="pallas_dip",
                  prologue="rmsnorm", prologue_operands=(g,))
for plan, lbl in [(col, "col"), (row, "row")]:
    got = api.matmul(x, wrap(wg, plan), backend="dip_sp",
                     prologue="rmsnorm", prologue_operands=(g,))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-3, rtol=2e-3, err_msg=lbl)
print("SP_PARITY_OK")

# ---- the overlap contract, jaxpr-asserted --------------------------------
def sp(w, **kw):
    return lambda xx: api.matmul(xx, w, backend="dip_sp", **kw)

c = count_collectives(sp(wrap(wg, col)), x)
assert c["all_gather"] == 0 and c["psum"] == 0, c      # NO pre-kernel gather
assert c["ppermute"] == 3 and c["pallas_call"] == 4, c # tp-1 hops, tp launches
sched = collective_schedule(sp(wrap(wg, col)), x)
assert sched[0] == "ppermute", sched  # hop issued BEFORE the launch it hides
assert sched[:4] == ["ppermute", "pallas_call"] * 2, sched
c = count_collectives(sp(wrap((wg, wu), col), epilogue="swiglu"), x)
assert c["pallas_call"] == 4 and c["psum"] == 0, c     # ONE fused launch/step
c = count_collectives(sp(wrap(wg, row)), x)
assert c["reduce_scatter"] == 1 and c["psum"] == 0, c  # row: scatter, not psum
assert c["pallas_call"] == 1 and c["all_gather"] == 0, c
print("SP_COLLECTIVES_OK")

# ---- quantized -----------------------------------------------------------
qw = api.quant.quantize(wg, "int8")
got = api.matmul(x, qw.with_plan(col), backend="dip_sp")
np.testing.assert_array_equal(np.asarray(got), np.asarray(api.matmul(x, qw)))
got_row = api.matmul(x, qw.with_plan(row), backend="dip_sp")
want_f = np.asarray(ref.ws_matmul_ref(x, wg))
dev = np.abs(np.asarray(got_row) - want_f).max() / np.abs(want_f).max()
assert dev < 0.02, dev
xb, wb = x.astype(jnp.bfloat16), wg.astype(jnp.bfloat16)
want = api.matmul(xb, wrap(wb, None), backend="pallas_dip")
got = api.matmul(xb, wrap(wb, row), backend="dip_sp")
np.testing.assert_allclose(np.asarray(got, np.float32),
                           np.asarray(want, np.float32), atol=0.5, rtol=0.05)
print("SP_QUANT_OK")
""", devices=8, timeout=900)
    assert "SP_PARITY_OK" in out and "SP_COLLECTIVES_OK" in out
    assert "SP_QUANT_OK" in out


def test_dip_ep_moe_collective_contract():
    """Expert-parallel MoE: moe_ffn under an 'ep' plan must equal the
    global-dispatch path under zero drops, and its jaxpr must show exactly
    TWO all_to_alls (dispatch + combine) with the dispatch issued BEFORE the
    shared-expert launches it overlaps, plus ONE psum (aux/drop stats)."""
    out = _run("""
from repro.configs.base import ArchConfig
from repro.distributed.plan import make_local_mesh, make_plan
from repro.models import moe, transformer as tf_model
from repro.kernels.dip_matmul_sharded import collective_schedule, count_collectives

cfg = ArchConfig(name="m", family="moe", n_layers=1, d_model=64, n_heads=2,
                 n_kv_heads=2, d_ff=0, vocab_size=64, head_dim=32, n_experts=8,
                 moe_top_k=2, n_shared_experts=1, d_ff_expert=32,
                 capacity_factor=2.0, remat="none", compute_dtype="float32",
                 matmul_backend="dip_ep", sharding="ep")
key = jax.random.PRNGKey(0)
lp = jax.tree_util.tree_map(lambda t: t[0], tf_model.init_params(key, cfg)["layers"])
mesh = make_local_mesh(data=2, model=4)
plan = make_plan(mesh, cfg, "train")
assert plan.expert_plan is not None and plan.explicit_backend == "dip_ep"
lp = plan.attach_params(lp)
x = jax.random.normal(key, (4, 16, cfg.d_model))

ref_out, ref_aux, ref_drop = moe.moe_ffn(x, lp, cfg)        # global dispatch
with mesh:
    out, aux, drop = moe.moe_ffn(x, lp, cfg, plan=plan)     # expert-parallel
assert int(drop) == 0 and int(ref_drop) == 0
np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                           atol=2e-3, rtol=2e-3)
assert abs(float(ref_aux) - float(aux)) < 1e-3  # per-shard stats, averaged
print("EP_PARITY_OK")

c = count_collectives(lambda xx: moe.moe_ffn(xx, lp, cfg, plan=plan)[0], x)
assert c["all_to_all"] == 2 and c["psum"] == 1 and c["all_gather"] == 0, c
sched = collective_schedule(lambda xx: moe.moe_ffn(xx, lp, cfg, plan=plan)[0], x)
# dispatch a2a BEFORE the shared-expert launches it hides behind
assert sched.index("all_to_all") < sched.index("pallas_call"), sched
print("EP_COLLECTIVES_OK")

# seq-split fallback (batch not divisible by the axis) keeps parity
x2 = jax.random.normal(key, (2, 16, cfg.d_model))
ref2, _, _ = moe.moe_ffn(x2, lp, cfg)
with mesh:
    out2, _, d2 = moe.moe_ffn(x2, lp, cfg, plan=plan)
assert int(d2) == 0
np.testing.assert_allclose(np.asarray(out2), np.asarray(ref2),
                           atol=2e-3, rtol=2e-3)
print("EP_SEQ_SPLIT_OK")
""", devices=8, timeout=900)
    assert "EP_PARITY_OK" in out and "EP_COLLECTIVES_OK" in out
    assert "EP_SEQ_SPLIT_OK" in out


def test_model_forward_through_dip_tp_matches_gspmd():
    """End to end: a reduced transformer with cfg.sharding='tp' and
    matmul_backend='dip_tp', plans attached by the ShardingPlan, forward
    under jit+scan on an 8-device mesh — logits match the implicit
    GSPMD-on-xla path."""
    out = _run("""
import dataclasses
from repro.configs.base import ArchConfig
from repro.distributed.plan import make_plan
from repro.models import transformer as tf_model

cfg = ArchConfig(name="t", family="dense", n_layers=2, d_model=256, n_heads=4,
                 n_kv_heads=4, d_ff=256, vocab_size=512, head_dim=64,
                 remat="none", compute_dtype="float32", param_dtype="float32",
                 matmul_backend="dip_tp", sharding="tp")
assert cfg.uses_dip_storage
key = jax.random.PRNGKey(0)
params = tf_model.init_params(key, cfg)
toks = jax.random.randint(key, (2, 8), 0, 512)

# implicit reference: same DiP-stored params through GSPMD-on-xla
ref_cfg = dataclasses.replace(cfg, matmul_backend="xla", sharding="gspmd")
ref_logits, _, _ = tf_model.forward(params, ref_cfg, tokens=toks)

mesh = make_mesh((2, 4), ("data", "model"))
plan = make_plan(mesh, cfg, "train")
params_tp = plan.attach_params(params)
# every 2-D projection in this config divides the mesh: all plans explicit
lyr = params_tp["layers"]
assert lyr["wq"].plan.kind == "column" and lyr["wo"].plan.kind == "row"
assert lyr["w_gate"].plan.kind == "column" and lyr["w_down"].plan.kind == "row"
shards = plan.param_shardings(params_tp)
with mesh:
    params_tp = jax.tree_util.tree_map(jax.device_put, params_tp, shards)
    fwd = jax.jit(lambda p, t: tf_model.forward(p, cfg, tokens=t, plan=plan)[0])
    logits = fwd(params_tp, toks)
np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                           atol=5e-2, rtol=5e-3)
print("MODEL_TP_OK")
""", devices=8, timeout=900)
    assert "MODEL_TP_OK" in out


# ===========================================================================
# plan metadata invariants (in-process; device-count independent)
# ===========================================================================
def _mesh11():
    return make_local_mesh(data=1, model=1)


def _plan_col(mesh=None):
    return WeightPlan("column", axis="model", fsdp="data", mesh=mesh or _mesh11())


def test_weight_plan_survives_jit_scan_grad():
    mesh = _mesh11()
    plan = _plan_col(mesh)
    r = np.random.default_rng(3)
    stacked = api.DipWeight.from_natural(
        jnp.asarray(r.normal(0, 1, (3, 100, 130)).astype(np.float32)), plan=plan
    )
    x = jnp.asarray(r.normal(0, 1, (4, 100)).astype(np.float32))

    @jax.jit
    def ident(w):
        return w

    back = ident(stacked)
    assert isinstance(back, api.DipWeight) and back.plan == plan

    def body(carry, lw):
        assert lw.plan == plan  # plan rides into the scan body (static aux)
        return carry, api.matmul(x, lw)

    _, ys = jax.lax.scan(body, 0, stacked)
    assert ys.shape == (3, 4, 130)

    g = jax.grad(
        lambda w: jnp.sum(api.matmul(x, w, backend="pallas_dip"))
    )(jax.tree_util.tree_map(lambda t: t[0], stacked))
    assert isinstance(g, api.DipWeight) and g.plan == plan

    spec = jax.eval_shape(lambda t: t, stacked)
    assert spec.plan == plan


def test_weight_plan_survives_checkpoint_and_validates_on_restore(tmp_path):
    mesh = _mesh11()
    plan = _plan_col(mesh)
    r = np.random.default_rng(5)
    w = jnp.asarray(r.normal(0, 1, (100, 130)).astype(np.float32))
    tree = {
        "wq": api.DipWeight.from_natural(w, plan=plan),
        "q": api.quant.quantize(w, "int8").with_plan(plan),
    }
    path = str(tmp_path / "ck")
    save_pytree(path, tree)

    # the manifest records the JSON plan (mesh reduced to axis sizes)
    import json, os
    manifest = json.load(open(os.path.join(path, "manifest.json")))
    entry = manifest["dip_weights"]["['wq']"]  # tree_flatten_with_path key
    assert entry["plan"]["kind"] == "column"
    assert entry["plan"]["axis"] == "model"
    assert entry["plan"]["mesh_axes"] == {"data": 1, "model": 1}

    got = restore_pytree(path, jax.eval_shape(lambda: tree))
    assert got["wq"].plan == plan and got["q"].plan == plan
    np.testing.assert_array_equal(np.asarray(got["wq"].data),
                                  np.asarray(tree["wq"].data))

    # plan KIND mismatch on restore is detected
    bad_plan = WeightPlan("row", axis="model", fsdp="data", mesh=mesh)
    bad = jax.eval_shape(lambda: {
        "wq": tree["wq"].with_plan(bad_plan), "q": tree["q"].with_plan(bad_plan)
    })
    with pytest.raises(ValueError, match="ShardingPlan mismatch"):
        restore_pytree(path, bad)

    # a live mesh that lost the saved plan's axis is detected
    mesh1 = make_mesh((1,), ("stage",))
    lost = WeightPlan("column", axis="stage", fsdp=None, mesh=mesh1)
    # rewrite the manifest as if saved from a {model}-axis mesh restoring
    # onto a {stage}-only mesh: axis names must survive re-mesh
    bad2 = jax.eval_shape(lambda: {
        "wq": tree["wq"].with_plan(lost), "q": tree["q"].with_plan(lost)
    })
    with pytest.raises(ValueError, match="ShardingPlan mismatch"):
        restore_pytree(path, bad2)

    # restoring into a plan-FREE target still works (plans are optional)
    plain = jax.eval_shape(lambda: {
        "wq": tree["wq"].with_plan(None), "q": tree["q"].with_plan(None)
    })
    got2 = restore_pytree(path, plain)
    assert got2["wq"].plan is None


def test_attach_params_stamps_declarative_roles():
    from repro.configs.base import ArchConfig
    from repro.models import transformer as tf_model

    cfg = ArchConfig(name="t", family="dense", n_layers=2, d_model=128,
                     n_heads=2, n_kv_heads=2, d_ff=128, vocab_size=512,
                     head_dim=64, matmul_backend="pallas_dip", sharding="tp",
                     remat="none")
    mesh = make_local_mesh(data=1, model=1)
    plan = make_plan(mesh, cfg, "train")
    specs = plan.attach_params(tf_model.param_specs(cfg))
    lyr = specs["layers"]
    assert lyr["wq"].plan.kind == "column"
    assert lyr["wo"].plan.kind == "row"
    assert lyr["w_gate"].plan.kind == "column"
    assert lyr["w_down"].plan.kind == "row"
    assert specs["lm_head"].plan.kind == "column"
    # shardings mirror the attached plans, so device_put zips in lockstep
    shards = plan.param_shardings(specs)
    assert shards["layers"]["wq"].plan == lyr["wq"].plan
    jax.tree_util.tree_structure(specs) == jax.tree_util.tree_structure(shards)


def test_plan_free_weight_decomposes_to_gspmd():
    r = np.random.default_rng(7)
    x = jnp.asarray(r.normal(0, 1, (4, 100)).astype(np.float32))
    w = jnp.asarray(r.normal(0, 1, (100, 130)).astype(np.float32))
    dw = api.DipWeight.from_natural(w)  # no plan
    for backend in ("dip_tp", "dip_fsdp", "dip_sp", "dip_ep"):
        got = api.matmul(x, dw, backend=backend)
        np.testing.assert_allclose(np.asarray(got), np.asarray(x @ w),
                                   atol=2e-3, rtol=2e-3, err_msg=backend)
    # quantized plan-free weights keep their scheme kernel on decomposition
    qw = api.quant.quantize(w, "int8")
    got_q = api.matmul(x, qw, backend="dip_tp")
    np.testing.assert_array_equal(np.asarray(got_q), np.asarray(api.matmul(x, qw)))
    # a replicated plan decomposes too (nothing to shard over)
    rep = api.DipWeight.from_natural(w, plan=WeightPlan("replicated"))
    got_r = api.matmul(x, rep, backend="dip_tp")
    np.testing.assert_allclose(np.asarray(got_r), np.asarray(x @ w),
                               atol=2e-3, rtol=2e-3)


def test_sharded_registration_rules():
    for name in ("dip_tp", "dip_fsdp", "dip_sp", "dip_ep"):
        assert api.backend_layout(name) == "sharded", name
        # sharded backends declare the full fused-epilogue AND -prologue sets
        assert set(api.backend_epilogues(name)) == set(api.EPILOGUES), name
        assert set(api.backend_prologues(name)) == set(api.PROLOGUES), name
    with pytest.raises(ValueError, match="tiled=False"):
        api.register_backend("bad_sharded", lambda *a, **k: None,
                             layout="sharded", tiled=True)


def test_weight_plan_validation_and_describe():
    with pytest.raises(ValueError, match="column | row | replicated"):
        WeightPlan("diagonal")
    mesh = make_local_mesh(data=1, model=1)
    p = WeightPlan("row", axis="model", fsdp="data", mesh=mesh)
    d = p.describe()
    assert d == {"kind": "row", "axis": "model", "fsdp": "data",
                 "mesh_axes": {"data": 1, "model": 1}}
    assert p.fsdp_size == 1 and p.tp_size == 1
    assert WeightPlan("row", axis="ghost", mesh=mesh).tp_size == 1  # absent axis
    assert WeightPlan("replicated").describe()["mesh_axes"] is None
    # value equality + hashability (jit static aux requirements)
    assert p == WeightPlan("row", axis="model", fsdp="data", mesh=mesh)
    assert hash(p) == hash(WeightPlan("row", axis="model", fsdp="data", mesh=mesh))


def test_sharded_dispatch_validates_inputs():
    mesh = _mesh11()
    col = _plan_col(mesh)
    w = jnp.ones((100, 130), jnp.float32)
    dw = api.DipWeight.from_natural(w, plan=col)
    with pytest.raises(ValueError, match="contraction"):
        api.matmul(jnp.ones((4, 96), jnp.float32), dw, backend="dip_tp")
    with pytest.raises(ValueError, match="2-D"):
        api.matmul(
            jnp.ones((4, 100), jnp.float32),
            api.DipWeight.from_natural(jnp.ones((2, 100, 130)), plan=col),
            backend="dip_tp",
        )
    # mixed plans on a swiglu pair are rejected
    other = WeightPlan("column", axis="model", fsdp=None, mesh=mesh)
    with pytest.raises(ValueError, match="share one WeightPlan"):
        api.matmul(
            jnp.ones((4, 100), jnp.float32),
            (dw, api.DipWeight.from_natural(w, plan=other)),
            backend="dip_tp", epilogue="swiglu",
        )
