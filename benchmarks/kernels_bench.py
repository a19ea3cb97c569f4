"""Kernel micro-benchmarks (beyond paper): wall-time of the jit'd DiP ops on
this host plus the structural de-shear overhead ablation and the
fused-vs-unfused epilogue comparison.

On CPU the Pallas kernels run in interpret mode, so absolute times are not
TPU-representative; what IS meaningful here: (a) the XLA-path DiP storage
format overhead (unpermute-then-dot vs plain dot — the fast path the
framework uses when not on TPU), (b) interpret-mode parity checks that
accompany the timing so a regression cannot silently pass, and (c) the
*structural* fused-epilogue evidence — the fused SwiGLU dispatch issues ONE
kernel launch where the unfused path issues three ops (two matmul launches
plus the elementwise silu*mul), counted directly in the jaxpr.

Every run writes ``BENCH_kernels.json`` (schema below) so the perf
trajectory is machine-readable across PRs; the CI ``bench-smoke`` job runs
``python benchmarks/kernels_bench.py --compare-epilogues --tiny`` and
validates the file with :func:`validate_bench_json`.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import time
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import api

BENCH_SCHEMA_VERSION = 1
DEFAULT_JSON = "BENCH_kernels.json"

# epilogues exercised by the fused-vs-unfused comparison (every variant with
# at least one extra operand or a second weight; "none" is the baseline)
_COMPARE_EPILOGUES = ("bias", "bias_silu", "swiglu", "residual")


def _time(fn, *args, iters=20):
    fn(*args).block_until_ready()  # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6  # us


# ---------------------------------------------------------------------------
# structural evidence: kernel launches per dispatch, counted in the jaxpr
def count_pallas_calls(fn, *args) -> int:
    """Number of pallas_call equations a traced call would launch (recursing
    through pjit/custom_vjp/scan sub-jaxprs)."""
    closed = jax.make_jaxpr(fn)(*args)

    def walk(jaxpr) -> int:
        total = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                total += 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                total += walk(sub)
        return total

    return walk(closed.jaxpr)


# ---------------------------------------------------------------------------
# fused-vs-unfused epilogue comparison
def compare_epilogues(
    *,
    backend: str = "pallas_dip",
    m: int = 64,
    k: int = 256,
    n: int = 256,
    iters: int = 3,
    interpret: Optional[bool] = None,
    verbose: bool = True,
) -> dict:
    """Time every fused epilogue against its decomposed (unfused) form on
    the same backend and count kernel launches for both.

    Returns the machine-readable dict recorded under ``epilogue_compare`` in
    ``BENCH_kernels.json``.  Parity against the shared f32 epilogue
    arithmetic is asserted alongside the timings, so a fused-path regression
    cannot silently pass the benchmark.
    """
    if interpret is None:
        interpret = api.default_interpret()
    r = np.random.default_rng(0)
    x = jnp.asarray(r.normal(0, 1, (m, k)).astype(np.float32))
    wg = jnp.asarray(r.normal(0, 1, (k, n)).astype(np.float32))
    wu = jnp.asarray(r.normal(0, 1, (k, n)).astype(np.float32))
    bias = jnp.asarray(r.normal(0, 1, (n,)).astype(np.float32))
    resid = jnp.asarray(r.normal(0, 1, (m, n)).astype(np.float32))

    be = api.get_backend(backend)
    if be.layout == "dip_q":
        wrap = lambda w: api.quant.quantize(w, be.scheme)
    elif be.layout == "dip":
        wrap = api.DipWeight.from_natural
    else:
        wrap = lambda w: w
    g, u = wrap(wg), wrap(wu)

    def operands_for(epilogue):
        if epilogue == "swiglu":
            return (g, u), ()
        if epilogue.startswith("bias"):
            return g, (bias,)
        return g, (resid,)

    def fused_fn(epilogue):
        w, eops = operands_for(epilogue)
        return jax.jit(lambda: api.matmul(
            x, w, backend=backend, epilogue=epilogue, epilogue_operands=eops,
            interpret=interpret,
        ))

    def unfused_fn(epilogue):
        # the decomposed form every call site used before this subsystem:
        # separate matmul launch(es) + elementwise glue through HBM
        def f():
            if epilogue == "swiglu":
                zg = api.matmul(x, g, backend=backend, interpret=interpret)
                zu = api.matmul(x, u, backend=backend, interpret=interpret)
                return (jax.nn.silu(zg.astype(jnp.float32))
                        * zu.astype(jnp.float32)).astype(zg.dtype)
            z = api.matmul(x, g, backend=backend, interpret=interpret)
            z32 = z.astype(jnp.float32)
            if epilogue == "bias":
                out = z32 + bias
            elif epilogue == "bias_silu":
                out = jax.nn.silu(z32 + bias)
            else:
                out = z32 + resid
            return out.astype(z.dtype)
        return jax.jit(f)

    results = []
    for epilogue in _COMPARE_EPILOGUES:
        fused, unfused = fused_fn(epilogue), unfused_fn(epilogue)
        got, want = fused(), unfused()
        np.testing.assert_allclose(   # parity rides with the timing
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=2e-3, rtol=2e-3,
        )
        t_fused = _time(fused, iters=iters)
        t_unfused = _time(unfused, iters=iters)
        n_fused = count_pallas_calls(fused)
        n_unfused = count_pallas_calls(unfused)
        rec = {
            "epilogue": epilogue,
            "fused_us": round(t_fused, 1),
            "unfused_us": round(t_unfused, 1),
            "speedup": round(t_unfused / t_fused, 3),
            "fused_pallas_calls": n_fused,
            "unfused_pallas_calls": n_unfused,
        }
        results.append(rec)
        if verbose:
            ops = "3 ops (2 matmul + silu*mul)" if epilogue == "swiglu" else \
                  f"{n_unfused} launch(es) + elementwise"
            print(f"  {epilogue:>9}: fused {t_fused:9.1f} us "
                  f"({n_fused} kernel launch) vs unfused {t_unfused:9.1f} us "
                  f"({ops}) -> {rec['speedup']:.2f}x")
    if be.tiled:
        swiglu = next(r_ for r_ in results if r_["epilogue"] == "swiglu")
        assert swiglu["fused_pallas_calls"] == 1, (
            f"fused swiglu must be ONE kernel launch, traced "
            f"{swiglu['fused_pallas_calls']}"
        )
        assert swiglu["unfused_pallas_calls"] >= 2, "unfused swiglu lost its launches?"
    return {
        "backend": backend,
        "shape": [m, k, n],
        "mode": "interpret" if interpret else "compiled",
        "results": results,
    }


# ---------------------------------------------------------------------------
# fused-vs-unfused prologue comparison (the load-stage mirror of the above)
def compare_prologues(
    *,
    backend: str = "pallas_dip",
    m: int = 64,
    k: int = 256,
    n: int = 256,
    iters: int = 3,
    interpret: Optional[bool] = None,
    verbose: bool = True,
) -> dict:
    """Time the fused rmsnorm prologue against its decomposed form (the
    rms_norm -> matmul composition every block ran before this subsystem)
    and count kernel launches for both.  Parity is asserted alongside the
    timings.  Recorded under ``prologue_compare`` in BENCH_kernels.json."""
    from repro.kernels import prologue as prologue_lib

    if interpret is None:
        interpret = api.default_interpret()
    r = np.random.default_rng(0)
    x = jnp.asarray(r.normal(0, 1, (m, k)).astype(np.float32))
    wn = jnp.asarray(r.normal(0, 1, (k, n)).astype(np.float32))
    g = jnp.asarray(r.normal(1, 0.1, (k,)).astype(np.float32))
    bias = jnp.asarray(r.normal(0, 1, (n,)).astype(np.float32))

    be = api.get_backend(backend)
    if be.layout == "dip_q":
        w = api.quant.quantize(wn, be.scheme)
    elif be.layout == "dip":
        w = api.DipWeight.from_natural(wn)
    else:
        w = wn

    # with and without a fused epilogue riding the same launch: the second
    # row is the full per-projection story (norm + matmul + bias_silu, ONE
    # kernel where the unfused path pays three HBM round-trips)
    cases = [("rmsnorm", "none", ()), ("rmsnorm", "bias_silu", (bias,))]
    results = []
    for prologue, epilogue, eops in cases:
        fused = jax.jit(lambda _e=epilogue, _o=eops: api.matmul(
            x, w, backend=backend, prologue="rmsnorm", prologue_operands=(g,),
            epilogue=_e, epilogue_operands=_o, interpret=interpret,
        ))

        def unfused(_e=epilogue, _o=eops):
            xn = prologue_lib.apply("rmsnorm", x, g)  # separate norm pass
            z = api.matmul(xn, w, backend=backend, interpret=interpret)
            if _e == "none":
                return z
            return jax.nn.silu(z.astype(jnp.float32) + _o[0]).astype(z.dtype)

        unfused = jax.jit(unfused)
        got, want = fused(), unfused()
        np.testing.assert_allclose(   # parity rides with the timing
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=2e-3, rtol=2e-3,
        )
        t_fused = _time(fused, iters=iters)
        t_unfused = _time(unfused, iters=iters)
        n_fused = count_pallas_calls(fused)
        n_unfused = count_pallas_calls(unfused)
        label = prologue if epilogue == "none" else f"{prologue}+{epilogue}"
        rec = {
            "prologue": prologue,
            "epilogue": epilogue,
            "fused_us": round(t_fused, 1),
            "unfused_us": round(t_unfused, 1),
            "speedup": round(t_unfused / t_fused, 3),
            "fused_pallas_calls": n_fused,
            "unfused_pallas_calls": n_unfused,
        }
        results.append(rec)
        if verbose:
            print(f"  {label:>18}: fused {t_fused:9.1f} us "
                  f"({n_fused} kernel launch) vs unfused {t_unfused:9.1f} us "
                  f"({n_unfused} launch(es) + norm pass) -> {rec['speedup']:.2f}x")
    if be.tiled:
        for rec in results:
            assert rec["fused_pallas_calls"] == 1, (
                f"fused prologue dispatch must be ONE kernel launch, traced "
                f"{rec['fused_pallas_calls']} ({rec['epilogue']})"
            )
    return {
        "backend": backend,
        "shape": [m, k, n],
        "mode": "interpret" if interpret else "compiled",
        "results": results,
    }


# ---------------------------------------------------------------------------
# fused lm_head+CE and flash-attention structural smoke
def fused_upstream_smoke(
    *,
    t_tokens: int = 96,
    d_model: int = 64,
    vocab: int = 512,
    iters: int = 3,
    interpret: Optional[bool] = None,
    verbose: bool = True,
) -> dict:
    """Structural evidence for the two fused losses of the upstream story:

    * fused lm_head+CE — ONE pallas launch forward, and NO logits-sized
      ((rows >= T) x (cols >= V)) intermediate anywhere in the loss+grad
      jaxpr (the unfused oracle has one — asserted as the control);
    * flash attention through the registry — ONE pallas launch vs zero for
      the dense xla oracle, parity asserted.

    Recorded under ``fused_upstream`` in BENCH_kernels.json.
    """
    from repro.kernels import lm_head_ce
    from repro.kernels.flash_attention import flash_attention_pallas  # noqa: F401

    if interpret is None:
        interpret = api.default_interpret()
    r = np.random.default_rng(0)
    tt, d, v = t_tokens, d_model, vocab
    assert tt > d, "T must exceed d_model so dW cannot alias the predicate"
    x = jnp.asarray(r.normal(0, 1, (tt, d)).astype(np.float32))
    w = jnp.asarray(r.normal(0, 1, (d, v)).astype(np.float32))
    labels = jnp.asarray(r.integers(0, v, (tt,)).astype(np.int32))

    def fused_loss(xx, ww):
        return lm_head_ce.fused_cross_entropy_loss(
            xx, ww, labels, vocab_size=v, block_v=128, interpret=interpret)

    def unfused_loss(xx, ww):
        return lm_head_ce.reference_lm_head_ce(xx, ww, labels, vocab_size=v)

    got, want = float(fused_loss(x, w)), float(unfused_loss(x, w))
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)

    def logits_like(closed):
        hits = []

        def walk(jx):
            for eqn in jx.eqns:
                for var in eqn.outvars:
                    shape = getattr(getattr(var, "aval", None), "shape", ())
                    if (len(shape) >= 2 and shape[-1] >= v
                            and int(np.prod(shape[:-1])) >= tt):
                        hits.append(tuple(shape))
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

        walk(closed.jaxpr)
        return hits

    grad_fused = jax.make_jaxpr(jax.grad(fused_loss, argnums=(0, 1)))(x, w)
    grad_unfused = jax.make_jaxpr(jax.grad(unfused_loss, argnums=(0, 1)))(x, w)
    ce_logits_free = not logits_like(grad_fused)
    assert ce_logits_free, "fused CE materialized a logits-sized tensor"
    assert logits_like(grad_unfused), "control: oracle should materialize logits"
    ce_launches = count_pallas_calls(lambda a, b: fused_loss(a, b), x, w)
    assert ce_launches == 1, f"fused CE forward traced {ce_launches} launches"
    jit_f, jit_u = jax.jit(fused_loss), jax.jit(unfused_loss)
    t_f = _time(jit_f, x, w, iters=iters)
    t_u = _time(jit_u, x, w, iters=iters)
    ce = {
        "shape": [tt, d, v],
        "fused_us": round(t_f, 1),
        "unfused_us": round(t_u, 1),
        "pallas_calls": ce_launches,
        "logits_free_grad": bool(ce_logits_free),
    }
    if verbose:
        print(f"  fused lm_head+CE {tt}x{d}x{v}: {t_f:9.1f} us "
              f"({ce_launches} launch, logits-free grad) vs unfused "
              f"{t_u:9.1f} us")

    bh, sq, sk, hd = 4, 64, 64, 32
    q = jnp.asarray(r.normal(0, 1, (bh, sq, hd)).astype(np.float32))
    kk = jnp.asarray(r.normal(0, 1, (bh, sk, hd)).astype(np.float32))
    vv = jnp.asarray(r.normal(0, 1, (bh, sk, hd)).astype(np.float32))
    flash = jax.jit(lambda a, b, c: api.attention(
        a, b, c, backend="flash", block_q=32, block_k=32, interpret=interpret))
    dense = jax.jit(lambda a, b, c: api.attention(a, b, c, backend="xla"))
    np.testing.assert_allclose(
        np.asarray(flash(q, kk, vv)), np.asarray(dense(q, kk, vv)),
        atol=2e-3, rtol=2e-3,
    )
    fl_launches = count_pallas_calls(flash, q, kk, vv)
    assert fl_launches == 1, f"flash dispatch traced {fl_launches} launches"
    t_fl = _time(flash, q, kk, vv, iters=iters)
    t_dn = _time(dense, q, kk, vv, iters=iters)
    fa = {
        "shape": [bh, sq, sk, hd],
        "flash_us": round(t_fl, 1),
        "xla_us": round(t_dn, 1),
        "pallas_calls": fl_launches,
    }
    if verbose:
        print(f"  flash attention {bh}x{sq}x{sk}x{hd}: {t_fl:9.1f} us "
              f"({fl_launches} launch) vs dense xla {t_dn:9.1f} us")
    return {
        "mode": "interpret" if interpret else "compiled",
        "lm_head_ce": ce,
        "flash_attention": fa,
    }


# ---------------------------------------------------------------------------
# explicit-sharding comparison (dip_tp vs GSPMD-xla on virtual devices)
def compare_sharded(
    *,
    m: int = 16,
    k: int = 256,
    n: int = 256,
    iters: int = 3,
    verbose: bool = True,
) -> dict:
    """Time the explicit ``dip_tp``/``dip_fsdp`` shard_map dispatch against
    the implicit GSPMD-on-xla path on the live (virtual) mesh, and record
    launch/collective counts for both.

    Structural evidence, not wall-clock truth: on forced-host CPU devices
    both paths run emulated, so the *counts* are the durable signal — the
    explicit backends' collectives come straight from the jaxpr (zero for
    column, one psum for row, one all_gather for fsdp) while GSPMD's are
    counted from the partitioned HLO, where XLA chose them.  Parity between
    the two paths is asserted alongside the timings.
    """
    from repro.distributed.plan import WeightPlan, make_local_mesh
    from repro.kernels.dip_matmul_sharded import count_collectives

    devs = jax.device_count()
    model = 4 if devs % 4 == 0 else devs
    mesh = make_local_mesh(data=devs // model, model=model)
    col = WeightPlan("column", axis="model", fsdp="data", mesh=mesh)
    row = WeightPlan("row", axis="model", fsdp="data", mesh=mesh)

    r = np.random.default_rng(0)
    x = jnp.asarray(r.normal(0, 1, (m, k)).astype(np.float32))
    wn = jnp.asarray(r.normal(0, 1, (k, n)).astype(np.float32))

    def gspmd_fn(spec):
        # the implicit path: same DiP storage placed with the CASE's
        # partitioning (column: N over TP; row: K over TP; fsdp: K over
        # data) and the dot left to GSPMD
        dw = api.DipWeight.from_natural(wn)
        dws = dw.with_data(
            jax.device_put(dw.data, jax.sharding.NamedSharding(mesh, spec))
        )
        return jax.jit(lambda a: api.matmul(a, dws, backend="xla")), dws

    def hlo_collectives(jitted, *args) -> int:
        txt = jitted.lower(*args).compile().as_text()
        return sum(txt.count(s) for s in
                   ("all-reduce(", "all-gather(", "collective-permute(",
                    "all-to-all("))

    P = jax.sharding.PartitionSpec
    cases = [("column", "dip_tp", col, P(None, "model")),
             ("row", "dip_tp", row, P("model", None)),
             ("fsdp", "dip_fsdp", col, P("data", None))]
    results = []
    for label, backend, plan, gspmd_spec in cases:
        dw = api.DipWeight.from_natural(wn, plan=plan)
        explicit = jax.jit(lambda a, _dw=dw, _b=backend: api.matmul(a, _dw, backend=_b))
        gspmd, _ = gspmd_fn(gspmd_spec)
        with mesh:
            got = explicit(x)
            want = gspmd(x)
            np.testing.assert_allclose(  # parity rides with the timing
                np.asarray(got), np.asarray(want), atol=2e-3, rtol=2e-3,
            )
            t_explicit = _time(explicit, x, iters=iters)
            t_gspmd = _time(gspmd, x, iters=iters)
            counts = count_collectives(explicit, x)
            n_hlo = hlo_collectives(gspmd, x)
        rec = {
            "case": label,
            "backend": backend,
            "explicit_us": round(t_explicit, 1),
            "gspmd_us": round(t_gspmd, 1),
            "psums": counts["psum"],
            "all_gathers": counts["all_gather"],
            "pallas_calls": counts["pallas_call"],
            "gspmd_hlo_collectives": n_hlo,
        }
        results.append(rec)
        if verbose:
            print(f"  {label:>7} ({backend}): explicit {t_explicit:9.1f} us "
                  f"[{counts['psum']} psum, {counts['all_gather']} all_gather, "
                  f"{counts['pallas_call']} launch] vs GSPMD-xla "
                  f"{t_gspmd:9.1f} us [{n_hlo} HLO collectives]")
    assert next(r_ for r_ in results if r_["case"] == "column")["psums"] == 0
    assert next(r_ for r_ in results if r_["case"] == "row")["psums"] == 1
    assert next(r_ for r_ in results if r_["case"] == "fsdp")["all_gathers"] == 1
    return {
        "mesh_axes": {str(a): int(s) for a, s in mesh.shape.items()},
        "shape": [m, k, n],
        "mode": "interpret" if api.default_interpret() else "compiled",
        "results": results,
    }


def _held_to_cpu_without_forced_devices() -> bool:
    """Whether ``JAX_PLATFORMS=cpu`` holds this process to the CPU and no
    host device count is forced yet.  Decided from the environment alone,
    before JAX picks a backend: a process on an accelerator never starts a
    CPU child."""
    return (os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
            and "--xla_force_host_platform_device_count"
            not in os.environ.get("XLA_FLAGS", ""))


def _reexec_with_devices(argv: Sequence[str], devices: int) -> int:
    """`--sharded` on the CPU needs a multi-device topology; XLA locks the
    device count at first init, so spawn a fresh interpreter with forced
    host devices.  The child sees the forced count in XLA_FLAGS and never
    re-execs again."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices} "
        + env.get("XLA_FLAGS", "")
    ).strip()
    root = pathlib.Path(__file__).resolve().parent.parent
    env["PYTHONPATH"] = os.pathsep.join(
        [str(root / "src")] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    proc = subprocess.run(
        [sys.executable, str(pathlib.Path(__file__).resolve()), *argv],
        env=env, cwd=str(root),
    )
    return proc.returncode


# ---------------------------------------------------------------------------
# machine-readable output
def write_bench_json(path, csv_rows, epilogue_compare: Optional[dict],
                     sharded_compare: Optional[dict] = None,
                     prologue_compare: Optional[dict] = None,
                     fused_upstream: Optional[dict] = None) -> pathlib.Path:
    p = pathlib.Path(path)
    payload = {
        "schema_version": BENCH_SCHEMA_VERSION,
        "generated_by": "benchmarks/kernels_bench.py",
        "jax_backend": jax.default_backend(),
        "entries": [
            {"name": name, "us_per_call": round(float(us), 1), "derived": str(derived)}
            for name, us, derived in csv_rows
        ],
    }
    if epilogue_compare is not None:
        payload["epilogue_compare"] = epilogue_compare
    if sharded_compare is not None:
        payload["sharded_compare"] = sharded_compare
    if prologue_compare is not None:
        payload["prologue_compare"] = prologue_compare
    if fused_upstream is not None:
        payload["fused_upstream"] = fused_upstream
    p.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
    return p


def validate_bench_json(path) -> dict:
    """Schema check for BENCH_kernels.json; returns the parsed payload.
    Raises ValueError on any violation (run by the CI bench-smoke job)."""
    payload = json.loads(pathlib.Path(path).read_text())

    def need(cond, msg):
        if not cond:
            raise ValueError(f"BENCH_kernels.json schema violation: {msg}")

    need(payload.get("schema_version") == BENCH_SCHEMA_VERSION,
         f"schema_version != {BENCH_SCHEMA_VERSION}")
    need(isinstance(payload.get("entries"), list), "entries must be a list")
    for e in payload["entries"]:
        need(isinstance(e.get("name"), str) and e["name"], "entry without name")
        need(isinstance(e.get("us_per_call"), (int, float)), f"{e.get('name')}: bad us_per_call")
    if "epilogue_compare" in payload:
        ec = payload["epilogue_compare"]
        need(isinstance(ec.get("backend"), str), "epilogue_compare.backend")
        need(isinstance(ec.get("shape"), list) and len(ec["shape"]) == 3,
             "epilogue_compare.shape must be [m, k, n]")
        need(isinstance(ec.get("results"), list) and ec["results"],
             "epilogue_compare.results empty")
        for rec in ec["results"]:
            for key in ("epilogue", "fused_us", "unfused_us", "speedup",
                        "fused_pallas_calls", "unfused_pallas_calls"):
                need(key in rec, f"epilogue_compare result missing {key!r}")
        swiglu = [r for r in ec["results"] if r["epilogue"] == "swiglu"]
        need(bool(swiglu), "epilogue_compare must include the swiglu headline")
        need(swiglu[0]["fused_pallas_calls"] <= 1,
             "fused swiglu recorded more than one kernel launch")
    if "prologue_compare" in payload:
        pc = payload["prologue_compare"]
        need(isinstance(pc.get("backend"), str), "prologue_compare.backend")
        need(isinstance(pc.get("shape"), list) and len(pc["shape"]) == 3,
             "prologue_compare.shape must be [m, k, n]")
        need(isinstance(pc.get("results"), list) and pc["results"],
             "prologue_compare.results empty")
        for rec in pc["results"]:
            for key in ("prologue", "epilogue", "fused_us", "unfused_us",
                        "speedup", "fused_pallas_calls", "unfused_pallas_calls"):
                need(key in rec, f"prologue_compare result missing {key!r}")
            # the structural contract IS the schema: norm + matmul (+ any
            # epilogue) must stay ONE launch on the fused backends
            need(rec["fused_pallas_calls"] <= 1,
                 "fused prologue recorded more than one kernel launch")
    if "fused_upstream" in payload:
        fu = payload["fused_upstream"]
        need(isinstance(fu.get("lm_head_ce"), dict), "fused_upstream.lm_head_ce")
        need(isinstance(fu.get("flash_attention"), dict),
             "fused_upstream.flash_attention")
        ce = fu["lm_head_ce"]
        for key in ("shape", "fused_us", "unfused_us", "pallas_calls",
                    "logits_free_grad"):
            need(key in ce, f"fused_upstream.lm_head_ce missing {key!r}")
        need(ce["pallas_calls"] == 1,
             "fused lm_head+CE must be exactly one kernel launch")
        need(ce["logits_free_grad"] is True,
             "fused lm_head+CE grad materialized logits-sized tensors")
        fa = fu["flash_attention"]
        for key in ("shape", "flash_us", "xla_us", "pallas_calls"):
            need(key in fa, f"fused_upstream.flash_attention missing {key!r}")
        need(fa["pallas_calls"] == 1,
             "flash attention dispatch must be exactly one kernel launch")
    if "sharded_compare" in payload:
        sc = payload["sharded_compare"]
        need(isinstance(sc.get("mesh_axes"), dict) and sc["mesh_axes"],
             "sharded_compare.mesh_axes")
        need(isinstance(sc.get("shape"), list) and len(sc["shape"]) == 3,
             "sharded_compare.shape must be [m, k, n]")
        need(isinstance(sc.get("results"), list) and sc["results"],
             "sharded_compare.results empty")
        by_case = {}
        for rec in sc["results"]:
            for key in ("case", "backend", "explicit_us", "gspmd_us",
                        "psums", "all_gathers", "pallas_calls",
                        "gspmd_hlo_collectives"):
                need(key in rec, f"sharded_compare result missing {key!r}")
            by_case[rec["case"]] = rec
        need({"column", "row", "fsdp"} <= set(by_case),
             "sharded_compare must cover column, row, and fsdp")
        # the collective-placement contract IS the schema: a drifting count
        # fails the bench, not just a test somewhere else
        need(by_case["column"]["psums"] == 0 and by_case["column"]["all_gathers"] == 0,
             "column-parallel recorded collectives (contract: zero)")
        need(by_case["row"]["psums"] == 1,
             "row-parallel must record exactly one psum")
        need(by_case["fsdp"]["all_gathers"] == 1,
             "fsdp must record exactly one all_gather per weight")
    if "reliability" in payload:
        # fused payloads may embed the ABFT verify-overhead + chaos-smoke
        # sections; the contracts live with the reliability bench
        from benchmarks.reliability_bench import validate_reliability_section

        validate_reliability_section(payload["reliability"], need)
    return payload


# ---------------------------------------------------------------------------
def run(csv_rows, *, out_json=DEFAULT_JSON):
    print("\n== kernel micro-benchmarks (CPU host; Pallas in interpret mode) ==")
    # the harness (benchmarks/run.py) shares one csv_rows across modules;
    # BENCH_kernels.json must record only THIS module's rows or the tracked
    # perf trajectory diffs spurious fig5/table4 entries across invocations
    first_own_row = len(csv_rows)
    r = np.random.default_rng(0)
    m, k, n = 512, 1024, 1024
    x = jnp.asarray(r.normal(size=(m, k)).astype(np.float32))
    w = jnp.asarray(r.normal(size=(k, n)).astype(np.float32))
    dw = api.DipWeight.from_natural(w)

    plain = jax.jit(lambda a, b: a @ b)
    # the distributed default: de-shear as a gather, then the XLA dot
    desheared = jax.jit(lambda a, d: api.matmul(a, d, backend="xla"))

    t_plain = _time(plain, x, w)
    t_dip_xla = _time(desheared, x, dw)
    overhead = (t_dip_xla - t_plain) / t_plain * 100
    print(f"XLA plain matmul {m}x{k}x{n}:          {t_plain:9.1f} us")
    print(f"XLA matmul from DiP storage (+unshear): {t_dip_xla:9.1f} us "
          f"({overhead:+.1f}% — amortized de-shear cost)")

    # correctness parity accompanying the timings
    got = desheared(x, dw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(plain(x, w)), atol=2e-3)

    # tuning-table resolution for this shape (what the Pallas path would use)
    blocks = api.lookup_blocks("pallas_dip", m, k, n, x.dtype)
    print(f"tuning table -> pallas_dip blocks for {m}x{k}x{n} f32: {tuple(blocks)}")

    # interpret-mode pallas timing (documentation only — Python emulation)
    tiny_x = x[:64, :256]
    tiny_w = api.DipWeight.from_natural(w[:256, :256])
    t_pallas = _time(
        lambda a, d: api.matmul(a, d, backend="pallas_dip", interpret=True),
        tiny_x, tiny_w, iters=3,
    )
    print(f"Pallas pallas_dip 64x256x256 (interpret): {t_pallas:9.1f} us "
          f"(Python emulation — TPU path compiles via Mosaic)")

    # tuned-vs-heuristic delta on the same workload: what the autotuner's
    # measured entry buys over whatever the table currently resolves
    # (register=False keeps the benchmark from mutating the global table)
    from repro.api import autotune

    res = autotune.autotune_shape(
        "pallas_dip", 64, 256, 256, "float32",
        iters=2, warmup=1, interpret=True, max_candidates=4, register=False,
    )
    t_inc, t_best = res.incumbent_time_us, res.best.time_us
    speedup = res.speedup_vs_incumbent() or 1.0
    print(f"autotune 64x256x256 f32: incumbent {tuple(res.incumbent)} "
          f"{t_inc:9.1f} us -> best {tuple(res.best.blocks)} {t_best:9.1f} us "
          f"({speedup:.2f}x; {len(res.measurements)} candidates)")

    # quantized-vs-float deltas (the dip_int8w / dip_fp8 backends).  On this
    # CPU host the meaningful comparison is the XLA-path analog: a quantized
    # weight served through the natural-layout backend (dequant + dot) vs the
    # plain bf16 dot — storage shrinks 4x (int8) / 2x (fp8) while the dequant
    # epilogue rides the same amortization as the de-shear.  The Pallas
    # quantized kernel itself is timed at interpret scale like the float one.
    from repro.api import quant

    xb = x.astype(jnp.bfloat16)
    wb = w.astype(jnp.bfloat16)
    plain_bf16 = jax.jit(lambda a, b: a @ b)
    t_bf16 = _time(plain_bf16, xb, wb)
    for scheme in ("int8", "fp8_e4m3"):
        qw = quant.quantize(w, scheme)
        deq = jax.jit(lambda a, d: api.matmul(a, d, backend="xla"))
        t_q = _time(deq, xb, qw)
        delta = (t_q - t_bf16) / t_bf16 * 100
        bytes_ratio = jnp.dtype(qw.dtype).itemsize / 2.0  # vs bf16 storage
        print(f"XLA matmul from {scheme} storage (+dequant):  {t_q:9.1f} us "
              f"({delta:+.1f}% vs bf16 dot; {bytes_ratio:.1f}x weight bytes)")
        err = np.abs(
            np.asarray(deq(xb, qw), np.float32)
            - np.asarray(plain_bf16(xb, wb), np.float32)
        ).max() / np.abs(np.asarray(plain_bf16(xb, wb), np.float32)).max()
        print(f"  max rel deviation vs bf16: {err:.4f} "
              f"(documented bound: docs/quantization.md)")
        assert err < 0.05, f"{scheme} deviation {err} beyond documented bound"
        csv_rows.append((f"kern_xla_{scheme}_storage", t_q,
                         f"delta_vs_bf16_{delta:+.1f}%"))

    t_q_pallas = _time(
        lambda a, d: api.matmul(a, d, backend="dip_int8w", interpret=True),
        tiny_x, quant.quantize(w[:256, :256], "int8"), iters=3,
    )
    print(f"Pallas dip_int8w 64x256x256 (interpret):  {t_q_pallas:9.1f} us "
          f"(Python emulation; vs float pallas_dip {t_pallas:9.1f} us)")

    # fused-vs-unfused epilogue deltas (the flush-stage fusion subsystem)
    print("fused-vs-unfused epilogues (pallas_dip 64x256x256, interpret):")
    ec = compare_epilogues(backend="pallas_dip", m=64, k=256, n=256, iters=2)
    for rec in ec["results"]:
        csv_rows.append((f"kern_epilogue_{rec['epilogue']}_fused",
                         rec["fused_us"],
                         f"vs_unfused_{rec['speedup']:.2f}x_"
                         f"launches_{rec['fused_pallas_calls']}v{rec['unfused_pallas_calls']}"))

    # fused-vs-unfused prologue deltas (the load-stage fusion subsystem)
    print("fused-vs-unfused rmsnorm prologue (pallas_dip 64x256x256, interpret):")
    pc = compare_prologues(backend="pallas_dip", m=64, k=256, n=256, iters=2)
    for rec in pc["results"]:
        label = (rec["prologue"] if rec["epilogue"] == "none"
                 else f"{rec['prologue']}_{rec['epilogue']}")
        csv_rows.append((f"kern_prologue_{label}_fused", rec["fused_us"],
                         f"vs_unfused_{rec['speedup']:.2f}x_"
                         f"launches_{rec['fused_pallas_calls']}v{rec['unfused_pallas_calls']}"))

    # fused lm_head+CE and flash-attention structural smoke
    print("fused upstream smoke (lm_head+CE, flash attention; interpret):")
    fu = fused_upstream_smoke(iters=2)
    csv_rows.append(("kern_fused_ce", fu["lm_head_ce"]["fused_us"],
                     f"vs_unfused_{fu['lm_head_ce']['unfused_us']}us_logits_free"))
    csv_rows.append(("kern_flash_attention", fu["flash_attention"]["flash_us"],
                     f"vs_xla_{fu['flash_attention']['xla_us']}us_1launch"))

    csv_rows.append(("kern_xla_plain_matmul", t_plain, f"{2*m*k*n/ (t_plain*1e-6) /1e9:.1f}GFLOP/s"))
    csv_rows.append(("kern_xla_dip_storage", t_dip_xla, f"overhead_{overhead:+.1f}%"))
    csv_rows.append(("kern_pallas_interpret", t_pallas, "interpret_mode"))
    csv_rows.append(("kern_pallas_int8w_interpret", t_q_pallas, "interpret_mode"))
    csv_rows.append(("kern_autotune_best", t_best, f"tuned_vs_incumbent_{speedup:.2f}x"))

    path = write_bench_json(out_json, csv_rows[first_own_row:], ec,
                            prologue_compare=pc, fused_upstream=fu)
    validate_bench_json(path)
    print(f"machine-readable record: {path}")


# ---------------------------------------------------------------------------
def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python benchmarks/kernels_bench.py",
        description="DiP kernel micro-benchmarks; writes BENCH_kernels.json.",
    )
    ap.add_argument("--compare-epilogues", action="store_true",
                    help="run ONLY the fused-vs-unfused epilogue comparison")
    ap.add_argument("--sharded", action="store_true",
                    help="run ONLY the explicit-sharding comparison (dip_tp/"
                         "dip_fsdp vs GSPMD-xla); under JAX_PLATFORMS=cpu it "
                         "re-execs itself with "
                         "--xla_force_host_platform_device_count")
    ap.add_argument("--devices", type=int, default=8,
                    help="virtual device count for --sharded (default 8)")
    ap.add_argument("--upstream", action="store_true",
                    help="run ONLY the upstream-fusion smoke: rmsnorm-"
                         "prologue compare + fused lm_head+CE + flash "
                         "attention (CI bench-smoke)")
    ap.add_argument("--backend", default="pallas_dip",
                    help="backend for --compare-epilogues (default pallas_dip)")
    ap.add_argument("--tiny", action="store_true",
                    help="tiny interpret-friendly shape (CI smoke)")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--out", default=DEFAULT_JSON,
                    help=f"output JSON path (default {DEFAULT_JSON})")
    args = ap.parse_args(argv)

    csv_rows: List = []
    if args.sharded:
        if _held_to_cpu_without_forced_devices():
            return _reexec_with_devices(
                ["--sharded", "--devices", str(args.devices),
                 "--iters", str(args.iters), "--out", args.out]
                + (["--tiny"] if args.tiny else []),
                args.devices,
            )
        if jax.device_count() < args.devices:
            raise SystemExit(
                f"--sharded needs {args.devices} devices; "
                f"{jax.default_backend()} has {jax.device_count()} (on a "
                "CPU host, JAX_PLATFORMS=cpu forces host devices)"
            )
        m, k, n = (8, 256, 256) if args.tiny else (64, 512, 512)
        print(f"== explicit sharding vs GSPMD-xla "
              f"({jax.device_count()} devices, {m}x{k}x{n}) ==")
        sc = compare_sharded(m=m, k=k, n=n, iters=args.iters)
        for rec in sc["results"]:
            csv_rows.append((
                f"kern_sharded_{rec['case']}_explicit", rec["explicit_us"],
                f"vs_gspmd_{rec['gspmd_us']}us_psum{rec['psums']}"
                f"_ag{rec['all_gathers']}_launch{rec['pallas_calls']}",
            ))
        path = write_bench_json(args.out, csv_rows, None, sc)
        validate_bench_json(path)
        print(f"machine-readable record: {path}")
        return 0
    if args.upstream:
        m, k, n = (32, 64, 64) if args.tiny else (64, 256, 256)
        print(f"== fused-vs-unfused rmsnorm prologue ({args.backend} {m}x{k}x{n}) ==")
        pc = compare_prologues(backend=args.backend, m=m, k=k, n=n,
                               iters=args.iters)
        print("== fused upstream smoke (lm_head+CE, flash attention) ==")
        fu = fused_upstream_smoke(iters=args.iters)
        for rec in pc["results"]:
            label = (rec["prologue"] if rec["epilogue"] == "none"
                     else f"{rec['prologue']}_{rec['epilogue']}")
            csv_rows.append((f"kern_prologue_{label}_fused", rec["fused_us"],
                             f"vs_unfused_{rec['speedup']:.2f}x"))
        csv_rows.append(("kern_fused_ce", fu["lm_head_ce"]["fused_us"],
                         "logits_free"))
        csv_rows.append(("kern_flash_attention", fu["flash_attention"]["flash_us"],
                         "1launch"))
        path = write_bench_json(args.out, csv_rows, None,
                                prologue_compare=pc, fused_upstream=fu)
        validate_bench_json(path)
        print(f"machine-readable record: {path}")
        return 0
    if args.compare_epilogues:
        m, k, n = (32, 64, 64) if args.tiny else (64, 256, 256)
        print(f"== fused-vs-unfused epilogues ({args.backend} {m}x{k}x{n}) ==")
        ec = compare_epilogues(
            backend=args.backend, m=m, k=k, n=n, iters=args.iters,
        )
        swiglu = next(r for r in ec["results"] if r["epilogue"] == "swiglu")
        print(f"fused SwiGLU: {swiglu['fused_pallas_calls']} kernel launch "
              f"(vs three ops unfused: {swiglu['unfused_pallas_calls']} matmul "
              f"launches + elementwise glue)")
        for rec in ec["results"]:
            csv_rows.append((f"kern_epilogue_{rec['epilogue']}_fused",
                             rec["fused_us"], f"vs_unfused_{rec['speedup']:.2f}x"))
        path = write_bench_json(args.out, csv_rows, ec)
        validate_bench_json(path)
        print(f"machine-readable record: {path}")
        return 0

    run(csv_rows, out_json=args.out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
