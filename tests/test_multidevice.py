"""Multi-device behaviours, each in a subprocess with forced host devices
(the shared helper lives in conftest — the pytest process must NOT set
XLA_FLAGS so smoke tests see the real topology).

Covers: pipeline-parallel equivalence (bit-match + overlap schedule),
pipelined train step vs flat, compressed psum, sharded train step on
a small (2,2) mesh, plan PartitionSpec validity for every arch, divisibility
fallback surfacing (warn-once / strict), and a reduced-config
production-mesh dry-run (the CI-sized version of deliverable e).
"""

import pytest

from conftest import run_forced_devices as _run


def test_pipeline_parallel_equals_sequential():
    """Bit-match, not allclose: every stage applies stage_fn exactly once
    per microbatch to exactly the upstream activation, so the overlapped
    schedule must be numerically invisible.  The tick body's jaxpr must
    open with the ppermute (transfer issued BEFORE the stage compute —
    the overlap contract)."""
    out = _run("""
from repro.distributed.pipeline import pipeline_apply
from repro.kernels.dip_matmul_sharded import collective_schedule, count_collectives
mesh = make_mesh((4,), ("stage",))
n_stages, n_micro, mb, d = 4, 8, 2, 16
key = jax.random.PRNGKey(0)
params = {"w": jax.random.normal(key, (n_stages, d, d)) * 0.3,
          "b": jax.random.normal(key, (n_stages, d)) * 0.1}
def stage_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])
x = jax.random.normal(key, (n_micro, mb, d))
got = jax.jit(lambda p, xs: pipeline_apply(mesh, stage_fn, p, xs))(params, x)
ref = x
for s in range(n_stages):
    ref = stage_fn({"w": params["w"][s], "b": params["b"][s]}, ref)
assert np.array_equal(np.asarray(got), np.asarray(ref)), (
    float(np.abs(np.asarray(got) - np.asarray(ref)).max()))
apply = lambda p, xs: pipeline_apply(mesh, stage_fn, p, xs)
sched = collective_schedule(apply, params, x)
assert sched[0] == "ppermute", sched      # transfer leads the tick body
cnt = count_collectives(apply, params, x)
assert cnt["ppermute"] == 1 and cnt["psum"] == 1, cnt  # scan body + broadcast
assert cnt["all_gather"] == 0 and cnt["all_to_all"] == 0, cnt
print("PIPELINE_OK")
""")
    assert "PIPELINE_OK" in out


def test_pipelined_train_step_matches_flat():
    """plan.stages > 1 swaps the trainer's step for the pipelined one; its
    loss must equal the flat train step exactly and its updated params must
    match to accumulation tolerance (scan-of-scan vs flat scan)."""
    out = _run("""
from repro.configs.base import ArchConfig
from repro.distributed import make_local_mesh, make_plan, pipeline_train_step_fn
from repro.models import transformer as tf_model
from repro.optim import AdamW

mesh = make_local_mesh(1, 1, stage=4)
cfg = ArchConfig(name="pp_t", family="dense", n_layers=4, d_model=32,
                 n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=64, head_dim=16,
                 remat="none", compute_dtype="float32",
                 matmul_backend="pallas_dip", sharding="pp")
plan = make_plan(mesh, cfg, "train")
assert plan.stages == 4 and plan.stage == "stage"
assert plan.explicit_backend is None  # stages run the config's backend

opt = AdamW(lr=1e-3)
params = tf_model.init_params(jax.random.PRNGKey(2), cfg)
state = {"params": params, "opt_state": opt.init(params),
         "step": jnp.zeros((), jnp.int32)}
tok = jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0, cfg.vocab_size)
batch = {"tokens": tok, "labels": tok}

pstep = jax.jit(pipeline_train_step_fn(cfg, opt, plan, n_micro=4))
fstep = jax.jit(tf_model.train_step_fn(cfg, opt, fused_ce=False))
s1, m1 = pstep(state, batch)
s2, m2 = fstep(state, batch)
np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
np.testing.assert_allclose(float(m1["grad_norm"]), float(m2["grad_norm"]), rtol=1e-4)
for a, b in zip(jax.tree_util.tree_leaves(s1["params"]),
                jax.tree_util.tree_leaves(s2["params"])):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=1e-4)
s1b, m1b = pstep(s1, batch)   # state threads through a second step
assert np.isfinite(float(m1b["loss"]))
print("PP_TRAIN_OK")
""")
    assert "PP_TRAIN_OK" in out


def test_compressed_psum_shard_map():
    out = _run("""
from functools import partial
from repro.kernels.common import shard_map
from jax.sharding import PartitionSpec as P
from repro.distributed.compression import compressed_psum
mesh = make_mesh((4,), ("data",))
x = jnp.arange(16, dtype=jnp.float32).reshape(4, 4) / 7.0
f = shard_map(lambda v: compressed_psum(v, "data"), mesh=mesh,
              in_specs=P("data", None), out_specs=P("data", None))
got = f(x)
want = jnp.broadcast_to(x.mean(0), (1, 4))  # mean over the axis
np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), rtol=0.02)
print("PSUM_OK")
""")
    assert "PSUM_OK" in out


def test_sharded_train_step_matches_single_device():
    out = _run("""
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.base import ArchConfig
from repro.models import transformer as tf_model
from repro.optim import AdamW
from repro.distributed.plan import make_plan

cfg = ArchConfig(name="t", family="dense", n_layers=2, d_model=64, n_heads=4,
                 n_kv_heads=4, d_ff=128, vocab_size=256, head_dim=16,
                 remat="none", compute_dtype="float32")
key = jax.random.PRNGKey(0)
params = tf_model.init_params(key, cfg)
toks = jax.random.randint(key, (4, 16), 0, 256)
batch = {"tokens": toks, "labels": toks}
opt = AdamW(lr=1e-3)
state = {"params": params, "opt_state": opt.init(params), "step": jnp.zeros((), jnp.int32)}

# single-device reference
ref_step = jax.jit(tf_model.train_step_fn(cfg, opt))
sref, mref = ref_step(state, batch)

# sharded on a (2, 2) data x model mesh, threaded as a first-class plan
mesh = make_mesh((2, 2), ("data", "model"))
plan = make_plan(mesh, cfg, "train")
pshard = plan.param_shardings(tf_model.param_template(cfg))
with mesh:
    params_s = jax.tree_util.tree_map(jax.device_put, params, pshard)
    state_s = {"params": params_s, "opt_state": opt.init(params_s),
               "step": jnp.zeros((), jnp.int32)}
    batch_s = jax.device_put(batch, NamedSharding(mesh, P(("data",), None)))
    step_s = jax.jit(tf_model.train_step_fn(cfg, opt, plan=plan))
    ss, ms = step_s(state_s, batch_s)
assert abs(float(mref["loss"]) - float(ms["loss"])) < 1e-4, (float(mref["loss"]), float(ms["loss"]))
d = jax.tree_util.tree_map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                           sref["params"], jax.device_get(ss["params"]))
assert max(jax.tree_util.tree_leaves(d)) < 1e-4, max(jax.tree_util.tree_leaves(d))
print("SHARDED_TRAIN_OK")
""", devices=4)
    assert "SHARDED_TRAIN_OK" in out


def test_plan_pspecs_valid_for_all_archs():
    out = _run("""
from repro.configs import ALL_ARCHS, get_config
from repro.distributed.plan import make_plan
from repro.models.transformer import param_template
mesh = make_mesh((2, 2), ("data", "model"))
for arch in ALL_ARCHS:
    cfg = get_config(arch)
    for mode in ("train", "decode"):
        plan = make_plan(mesh, cfg, mode)
        shards = plan.param_shardings(param_template(cfg))   # raises if invalid
        n = len(jax.tree_util.tree_leaves(shards))
        assert n > 5
print("PLAN_OK")
""")
    assert "PLAN_OK" in out


def test_divisibility_fallback_warns_once_and_strict_raises():
    """Satellite bugfix: the old policy silently replicated mis-sized leaves.
    The plan warns once (with the leaf name and axis sizes) and raises under
    strict=True."""
    out = _run("""
import warnings
from repro.configs.base import ArchConfig
from repro.distributed.plan import make_plan

# d_ff=70 does not divide the 4-wide model axis -> w_gate/w_up fall back
cfg = ArchConfig(name="t", family="dense", n_layers=2, d_model=64, n_heads=4,
                 n_kv_heads=4, d_ff=70, vocab_size=256, head_dim=16)
mesh = make_mesh((1, 4), ("data", "model"))
plan = make_plan(mesh, cfg, "train")
with warnings.catch_warnings(record=True) as caught:
    warnings.simplefilter("always")
    spec = plan.param_pspec("w_gate", (2, 64, 70))
    plan.param_pspec("w_gate", (2, 64, 70))  # second call: warn-once
assert spec[-1] is None  # replicated, as before — but no longer silently
msgs = [str(w.message) for w in caught if "ShardingPlan" in str(w.message)]
assert len(msgs) == 1, msgs
assert "w_gate" in msgs[0] and "70" in msgs[0] and "model" in msgs[0], msgs[0]

strict = make_plan(mesh, cfg, "train", strict=True)
try:
    strict.param_pspec("w_up", (2, 64, 70))
except ValueError as e:
    assert "w_up" in str(e) and "strict" in str(e)
else:
    raise AssertionError("strict plan did not raise on a mis-sized leaf")
print("FALLBACK_OK")
""")
    assert "FALLBACK_OK" in out


@pytest.mark.slow
def test_reduced_production_dryrun():
    """CI-sized dry-run: a reduced config against the real 512-device
    multi-pod mesh — proves the launch stack end to end."""
    out = _run("""
from repro.configs import get_config
from repro.configs.base import ShapeCell
from repro.distributed.plan import make_plan, make_production_mesh
from repro.launch.specs import input_specs

cfg = get_config("llama3-8b").reduced(d_model=256, n_heads=16, n_kv_heads=16,
                                      head_dim=64, vocab_size=4096, n_layers=2)
cell = ShapeCell("train_tiny", 512, 32, "train")
mesh = make_production_mesh(multi_pod=True)
plan = make_plan(mesh, cfg, "train")
fn, args = input_specs(cfg, cell, plan)
with mesh:
    compiled = jax.jit(fn, donate_argnums=(0,)).lower(*args).compile()
ca = compiled.cost_analysis()
ca = ca[0] if isinstance(ca, list) else ca
assert ca.get("flops", 0) > 0
print("DRYRUN_OK", int(ca["flops"]))
""", devices=512, timeout=900)
    assert "DRYRUN_OK" in out
