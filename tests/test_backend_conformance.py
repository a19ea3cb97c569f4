"""Cross-backend conformance harness: the acceptance bar for new backends.

Every registered matmul backend x supported operand dtype must

  * match its pure-jnp oracle in ``kernels/ref.py`` on *aligned and ragged*
    shapes (property-generated with hypothesis), within
    the per-dtype tolerances documented in ``docs/quantization.md``;
  * honour autodiff where the backend is differentiable (activation grads
    everywhere, weight grads on the float backends — quantized storage is a
    frozen artifact, its cotangent is zero by design);
  * keep the pytree / jit / scan invariants for BOTH weight types
    (``DipWeight`` and ``QuantizedDipWeight``).

A backend that cannot pass this file must not be registered as a builtin.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro import api
from repro.kernels import ref

# ---------------------------------------------------------------------------
# the conformance matrix: backend -> operand (activation) dtypes it supports.
# xla omits int8 deliberately: a bare jnp.matmul accumulates int8 in int8
# (overflow) — integer workloads go through the tiled kernels.
CONFORMANCE = {
    "xla": ("float32", "bfloat16"),
    "ws": ("float32", "bfloat16", "int8"),
    "pallas_dip": ("float32", "bfloat16", "int8"),
    "pallas_systolic": ("float32", "int8"),
    "dip_int8w": ("float32", "bfloat16"),
    "dip_fp8": ("float32", "bfloat16"),
}

# parity tolerance vs the oracle, keyed on activation dtype.  The quantized
# backends compare against their *quantized* oracles, where the integer
# arithmetic is exact and only f32 epilogue rounding differs.
TOL = {
    "float32": dict(atol=2e-3, rtol=2e-3),
    "bfloat16": dict(atol=0.5, rtol=0.05),
    "int8": dict(atol=0, rtol=0),
}

# shape pools mix tile-aligned and ragged (non-multiple-of-64) dims; kept
# small so interpret-mode jit caches hit across drawn examples.
MS = (1, 8, 17, 64)
KS = (64, 100, 128)
NS = (64, 127, 130, 192)


def _operands(m, k, n, dtype, seed):
    r = np.random.default_rng(seed)
    if dtype == "int8":
        x = r.integers(-20, 21, (m, k)).astype(np.int8)
        w = r.integers(-20, 21, (k, n)).astype(np.int8)
        return jnp.asarray(x), jnp.asarray(w)
    x = r.normal(0, 1, (m, k)).astype(np.float32)
    w = r.normal(0, 1, (k, n)).astype(np.float32)
    return jnp.asarray(x).astype(dtype), jnp.asarray(w).astype(dtype)


def _weight_for(backend, w):
    """The weight object a call site would hold for this backend."""
    be = api.get_backend(backend)
    if be.layout == "dip_q":
        return api.quant.quantize(w.astype(jnp.float32), be.scheme)
    if be.layout == "dip":
        return api.DipWeight.from_natural(w)
    return w


def _oracle(backend, x, wobj, w):
    """kernels/ref.py oracle for one dispatch, cropped to the logical shape."""
    be = api.get_backend(backend)
    if be.layout == "natural":
        return ref.ws_matmul_ref(x, w)
    n = wobj.d_out
    xk = jnp.pad(x, [(0, 0), (0, (-x.shape[-1]) % wobj.perm_tile)])
    if be.layout == "dip":
        return ref.dip_matmul_ref(xk, wobj.data, perm_tile=wobj.perm_tile)[..., :n]
    if be.scheme == "int8":
        return ref.dip_matmul_int8w_ref(
            xk, wobj.data, wobj.scale, perm_tile=wobj.perm_tile
        )[..., :n]
    return ref.dip_matmul_fp8_ref(
        xk, wobj.data, wobj.scale, perm_tile=wobj.perm_tile
    )[..., :n]


def test_matrix_covers_every_builtin_backend():
    missing = set(CONFORMANCE) - set(api.list_backends())
    assert not missing, f"matrix names unregistered backends: {missing}"
    builtin = {"xla", "ws", "pallas_dip", "pallas_systolic", "dip_int8w", "dip_fp8"}
    assert builtin <= set(CONFORMANCE), "a builtin backend escaped conformance"


def test_epilogue_capability_flags():
    """Every tiled builtin fuses the full epilogue set in-kernel; xla (and
    any non-tiled backend) declares none and relies on decomposition."""
    for backend in CONFORMANCE:
        be = api.get_backend(backend)
        if be.tiled:
            assert set(api.backend_epilogues(backend)) == set(api.EPILOGUES), backend
        else:
            assert api.backend_epilogues(backend) == ["none"], backend


# ----------------------------------------------------------------- parity ---
@pytest.mark.parametrize(
    "backend,dtype",
    [(b, d) for b, dts in CONFORMANCE.items() for d in dts],
)
@settings(max_examples=5)
@given(
    m=st.sampled_from(MS),
    k=st.sampled_from(KS),
    n=st.sampled_from(NS),
    seed=st.integers(0, 2**16),
)
def test_backend_matches_oracle(backend, dtype, m, k, n, seed):
    x, w = _operands(m, k, n, dtype, seed)
    wobj = _weight_for(backend, w)
    got = api.matmul(x, wobj, backend=backend)
    want = _oracle(backend, x, wobj, w)
    assert got.shape == (m, n)
    if api.get_backend(backend).layout == "dip_q":
        # kernel vs quantized oracle: integer/f32 arithmetic is exact, only
        # epilogue rounding (and the output-dtype cast) differs
        tol = (
            dict(atol=1e-3, rtol=1e-3) if dtype == "float32"
            else dict(atol=0.1, rtol=0.02)
        )
    else:
        tol = TOL[dtype]
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **tol,
        err_msg=f"{backend}/{dtype} {m}x{k}x{n} seed={seed}",
    )


@pytest.mark.parametrize("scheme,bound", [("int8", 0.02), ("fp8_e4m3", 0.05)])
def test_quantized_accuracy_vs_float_reference_documented_bound(scheme, bound):
    """Acceptance: quantized matmul vs the float32 reference within the
    accuracy expectation documented in docs/quantization.md (normalized
    worst-case deviation on well-conditioned random operands)."""
    r = np.random.default_rng(7)
    for m, k, n in [(32, 128, 192), (17, 100, 130)]:
        x = jnp.asarray(r.normal(0, 1, (m, k)).astype(np.float32))
        w = jnp.asarray(r.normal(0, 1, (k, n)).astype(np.float32))
        qw = api.quant.quantize(w, scheme)
        got = api.matmul(x, qw)
        want = np.asarray(ref.ws_matmul_ref(x, w))
        dev = np.abs(np.asarray(got) - want).max() / np.abs(want).max()
        assert dev < bound, f"{scheme} {m}x{k}x{n}: deviation {dev:.4f}"


@settings(max_examples=5)
@given(
    k=st.sampled_from(KS),
    n=st.sampled_from(NS),
    scheme=st.sampled_from(sorted(api.quant.SCHEMES)),
    seed=st.integers(0, 2**16),
)
def test_quantize_dequantize_error_within_per_channel_bound(k, n, scheme, seed):
    """Elementwise |dequant(quantize(w)) - w| <= the per-channel bound
    api.quant.max_abs_error_bound documents (half a step / half a ulp)."""
    r = np.random.default_rng(seed)
    w = jnp.asarray(r.normal(0, 1, (k, n)).astype(np.float32))
    qw = api.quant.quantize(w, scheme)
    back = api.quant.dequantize_natural(qw)
    err = np.abs(np.asarray(back) - np.asarray(w))
    bound = np.asarray(api.quant.max_abs_error_bound(qw))  # (n,)
    assert (err <= bound[None, :] + 1e-7).all()


def test_quantize_of_dipweight_equals_quantize_of_natural():
    r = np.random.default_rng(3)
    w = jnp.asarray(r.normal(0, 1, (100, 130)).astype(np.float32))
    qa = api.quant.quantize(w, "int8")
    qb = api.quant.quantize(api.DipWeight.from_natural(w), "int8")
    np.testing.assert_array_equal(np.asarray(qa.data), np.asarray(qb.data))
    np.testing.assert_allclose(np.asarray(qa.scale), np.asarray(qb.scale))


def test_scheme_mismatch_and_requantization_are_rejected():
    w = jnp.ones((64, 64), jnp.float32)
    qw = api.quant.quantize(w, "fp8_e4m3")
    with pytest.raises(ValueError, match="consumes scheme"):
        api.matmul(jnp.ones((4, 64), jnp.float32), qw, backend="dip_int8w")
    with pytest.raises(ValueError, match="requantiz"):
        api.quant.quantize(qw, "int8")
    assert api.quant.quantize(qw, "fp8_e4m3") is qw  # same scheme passes through
    with pytest.raises(ValueError, match="unknown quantization scheme"):
        api.quant.quantize(w, "int4")


def test_every_backend_accepts_a_quantized_weight():
    """Dispatch is weight-type aware: non-quantized backends dequantize a
    QuantizedDipWeight to their declared layout instead of crashing."""
    r = np.random.default_rng(5)
    x = jnp.asarray(r.normal(0, 1, (16, 100)).astype(np.float32))
    w = jnp.asarray(r.normal(0, 1, (100, 130)).astype(np.float32))
    qw = api.quant.quantize(w, "int8")
    want = np.asarray(api.matmul(x, api.quant.dequantize(qw), backend="xla"))
    for backend in sorted(CONFORMANCE):
        if api.get_backend(backend).layout == "dip_q":
            continue
        got = np.asarray(api.matmul(x, qw, backend=backend))
        np.testing.assert_allclose(got, want, atol=2e-3, rtol=2e-3,
                                   err_msg=backend)


# -------------------------------------------------------------- epilogues ---
# every backend x epilogue x (representative) dtype against the kernels/ref
# epilogue oracles.  "none" is covered by test_backend_matches_oracle; the
# fused variants here exercise the flush-stage fusion AND the decomposition
# path (xla declares no fused epilogues, so its rows prove the decomposed
# fallback against the same oracles).
EPILOGUES_TESTED = ("bias", "bias_gelu", "bias_silu", "swiglu", "residual")

# int8 activations are excluded: any epilogue other than "none" widens the
# accumulator to f32 and produces a float output, which the pure-int8
# conformance rows don't model.
EPILOGUE_DTYPES = {
    b: tuple(d for d in dts if d != "int8") for b, dts in CONFORMANCE.items()
}


def _epilogue_inputs(backend, epilogue, m, k, n, dtype, seed):
    """(x, w-or-pair, epilogue_operands) as a call site would hold them."""
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(0, 1, (m, k)).astype(np.float32)).astype(dtype)
    wg = jnp.asarray(r.normal(0, 1, (k, n)).astype(np.float32)).astype(dtype)
    wu = jnp.asarray(r.normal(0, 1, (k, n)).astype(np.float32)).astype(dtype)
    bias = jnp.asarray(r.normal(0, 1, (n,)).astype(np.float32))
    resid = jnp.asarray(r.normal(0, 1, (m, n)).astype(np.float32)).astype(dtype)
    if epilogue == "swiglu":
        return x, (_weight_for(backend, wg), _weight_for(backend, wu)), ()
    if epilogue.startswith("bias"):
        return x, _weight_for(backend, wg), (bias,)
    return x, _weight_for(backend, wg), (resid,)


def _epilogue_oracle(backend, x, wobj, epilogue, operands):
    """kernels/ref.py fused oracle for one dispatch, cropped to logical N."""
    be = api.get_backend(backend)
    primary = wobj[0] if isinstance(wobj, tuple) else wobj
    if be.layout == "natural":
        ops = (wobj[1],) if epilogue == "swiglu" else operands
        return ref.ws_matmul_epilogue_ref(x, primary if epilogue != "swiglu" else wobj[0],
                                          epilogue=epilogue, operands=ops)
    n = primary.d_out
    pad_n = (-n) % primary.perm_tile
    xk = jnp.pad(x, [(0, 0), (0, (-x.shape[-1]) % primary.perm_tile)])
    pad_cols = lambda t: jnp.pad(t, [(0, 0)] * (t.ndim - 1) + [(0, pad_n)])
    if epilogue == "swiglu":
        ops = ((wobj[1].data, wobj[1].scale) if be.layout == "dip_q"
               else (wobj[1].data,))
    elif epilogue.startswith("bias"):
        ops = (pad_cols(operands[0].reshape(1, n)),)
    else:
        ops = (pad_cols(operands[0]),)
    if be.layout == "dip":
        out = ref.dip_matmul_epilogue_ref(
            xk, primary.data, epilogue=epilogue, operands=ops,
            perm_tile=primary.perm_tile,
        )
    elif be.scheme == "int8":
        out = ref.dip_matmul_int8w_epilogue_ref(
            xk, primary.data, primary.scale, epilogue=epilogue, operands=ops,
            perm_tile=primary.perm_tile,
        )
    else:
        out = ref.dip_matmul_fp8_epilogue_ref(
            xk, primary.data, primary.scale, epilogue=epilogue, operands=ops,
            perm_tile=primary.perm_tile,
        )
    return out[..., :n]


@pytest.mark.parametrize("epilogue", EPILOGUES_TESTED)
@pytest.mark.parametrize(
    "backend,dtype",
    [(b, d) for b, dts in EPILOGUE_DTYPES.items() for d in dts],
)
def test_backend_epilogue_matches_oracle(backend, dtype, epilogue):
    """Fused-epilogue parity: every backend x epilogue x dtype against the
    kernels/ref.py fused oracles on an aligned AND a ragged shape."""
    for m, k, n, seed in ((8, 64, 64, 0), (17, 100, 130, 1)):
        x, wobj, operands = _epilogue_inputs(backend, epilogue, m, k, n, dtype, seed)
        got = api.matmul(x, wobj, backend=backend, epilogue=epilogue,
                         epilogue_operands=operands)
        want = _epilogue_oracle(backend, x, wobj, epilogue, operands)
        assert got.shape == (m, n)
        assert jnp.issubdtype(got.dtype, jnp.floating)
        if api.get_backend(backend).layout == "dip_q":
            tol = (dict(atol=2e-3, rtol=2e-3) if dtype == "float32"
                   else dict(atol=0.1, rtol=0.05))
        else:
            tol = TOL[dtype]
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), **tol,
            err_msg=f"{backend}/{dtype}/{epilogue} {m}x{k}x{n}",
        )


@pytest.mark.parametrize("epilogue", EPILOGUES_TESTED)
def test_fused_and_decomposed_paths_agree(epilogue):
    """The same weights through a fused backend (pallas_dip) and the
    decomposing backend (xla) must agree — the decomposition rule is
    'identical semantics, different fusion'."""
    m, k, n = 17, 100, 130
    x, wobj, operands = _epilogue_inputs("pallas_dip", epilogue, m, k, n,
                                         "float32", 3)
    fused = api.matmul(x, wobj, backend="pallas_dip", epilogue=epilogue,
                       epilogue_operands=operands)
    decomposed = api.matmul(x, wobj, backend="xla", epilogue=epilogue,
                            epilogue_operands=operands)
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(decomposed), atol=2e-3, rtol=2e-3,
    )


@pytest.mark.parametrize("epilogue", EPILOGUES_TESTED)
@pytest.mark.parametrize("backend", sorted(CONFORMANCE))
def test_epilogue_gradients_match_decomposed_xla(backend, epilogue):
    """Grad parity for the custom_vjp recompute path: d/dx, d/d(bias|resid),
    and d/dw (float backends) through the FUSED kernel must match the
    natively-differentiated decomposed XLA path.  The fused backward
    recomputes the pre-activation from the saved matmul residuals — this is
    the test that keeps that recompute exact."""
    m, k, n = 16, 100, 130
    r = np.random.default_rng(29)
    c = jnp.asarray(r.normal(0, 1, (m, n)).astype(np.float32))
    x, wobj, operands = _epilogue_inputs(backend, epilogue, m, k, n,
                                         "float32", 31)
    be = api.get_backend(backend)
    if be.layout == "dip_q":
        # straight-through reference: the DEQUANTIZED weights through xla
        ref_w = (tuple(api.quant.dequantize(wi) for wi in wobj)
                 if isinstance(wobj, tuple) else api.quant.dequantize(wobj))
    else:
        ref_w = wobj

    def loss(backend_name, w):
        def f(xx, *ops):
            out = api.matmul(xx, w, backend=backend_name, epilogue=epilogue,
                             epilogue_operands=ops)
            return jnp.sum(out * c)
        return f

    argnums = tuple(range(1 + len(operands)))
    g = jax.grad(loss(backend, wobj), argnums=argnums)(x, *operands)
    g_ref = jax.grad(loss("xla", ref_w), argnums=argnums)(x, *operands)
    for got, want in zip(g, g_ref):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-3, rtol=1e-3,
            err_msg=f"{backend}/{epilogue}",
        )

    # weight grads on the float backends (quantized storage is frozen)
    if be.layout in ("natural", "dip") and be.tiled:
        gw = jax.grad(
            lambda w: loss(backend, w)(x, *operands)
        )(wobj)
        gw_ref = jax.grad(
            lambda w: loss("xla", w)(x, *operands)
        )(wobj)
        for a, b in zip(jax.tree_util.tree_leaves(gw),
                        jax.tree_util.tree_leaves(gw_ref)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-3, rtol=1e-3,
                err_msg=f"{backend}/{epilogue} weight grad",
            )


@pytest.mark.parametrize("scheme", sorted(api.quant.SCHEMES))
def test_quantized_scale_bias_activation_composition(scheme):
    """The quantized flush composes scale-on-output THEN bias THEN
    activation (kernels/dip_matmul_q.py): assert that exact ordering against
    a hand-built jnp expression, not just the packaged oracle."""
    m, k, n = 16, 64, 128
    r = np.random.default_rng(37)
    x = jnp.asarray(r.normal(0, 1, (m, k)).astype(np.float32))
    w = jnp.asarray(r.normal(0, 1, (k, n)).astype(np.float32))
    bias = jnp.asarray(r.normal(0, 1, (n,)).astype(np.float32))
    qw = api.quant.quantize(w, scheme)
    got = api.matmul(x, qw, epilogue="bias_silu", epilogue_operands=(bias,))
    from repro.core import permute
    wn = permute.unpermute_tiled(qw.data, qw.perm_tile)
    if scheme == "int8":
        xq, xs = ref.quantize_acts_int8(x)
        z = jnp.matmul(xq, wn, preferred_element_type=jnp.int32).astype(jnp.float32)
        z = z * xs * qw.scale
    else:
        z = jnp.matmul(x, wn.astype(jnp.float32)) * qw.scale
    want = jax.nn.silu(z + bias)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-3, rtol=2e-3,
    )


def test_decomposed_epilogue_keeps_float_output_for_integer_matmuls():
    """The decomposition rule is 'identical semantics': an epilogue on an
    integer-accumulating dispatch yields a FLOAT result on the fused kernels
    (f32 epilogue arithmetic), so the decomposed path must too — not a
    silent truncation back to the matmul's integer dtype."""
    r = np.random.default_rng(43)
    x = jnp.asarray(r.integers(-1, 2, (8, 64)).astype(np.int8))
    w = jnp.asarray(r.integers(-1, 2, (64, 64)).astype(np.int8))
    bias = jnp.asarray(r.normal(0, 1, (64,)).astype(np.float32))
    fused = api.matmul(x, w, backend="ws", epilogue="bias_silu",
                       epilogue_operands=(bias,))
    # ws with block overrides pinned to the problem == the kernel's own
    # dtype rule; xla decomposes (declares no fused epilogues)
    decomposed = api.matmul(x.astype(jnp.float32), w.astype(jnp.float32),
                            backend="xla", epilogue="bias_silu",
                            epilogue_operands=(bias,))
    assert fused.dtype == jnp.float32
    assert decomposed.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(decomposed), atol=2e-3, rtol=2e-3,
    )


def test_epilogue_validation_rejects_malformed_inputs():
    x = jnp.ones((4, 64), jnp.float32)
    w = jnp.ones((64, 64), jnp.float32)
    with pytest.raises(ValueError, match="unknown epilogue"):
        api.matmul(x, w, epilogue="bias_relu")
    with pytest.raises(ValueError, match="weight pair"):
        api.matmul(x, w, epilogue="swiglu")
    with pytest.raises(ValueError, match="only valid with the dual-weight"):
        api.matmul(x, (w, w), epilogue="bias", epilogue_operands=(jnp.ones((64,)),))
    with pytest.raises(ValueError, match="epilogue_operands"):
        api.matmul(x, w, epilogue="bias")
    with pytest.raises(ValueError, match="bias must be"):
        api.matmul(x, w, epilogue="bias", epilogue_operands=(jnp.ones((65,)),))
    with pytest.raises(ValueError, match="residual must match"):
        api.matmul(x, w, epilogue="residual",
                   epilogue_operands=(jnp.ones((5, 64)),))
    with pytest.raises(ValueError, match="share logical dims"):
        api.matmul(x, (w, jnp.ones((64, 128), jnp.float32)), epilogue="swiglu")
    with pytest.raises(ValueError, match="share a quantization scheme"):
        api.matmul(
            x,
            (api.quant.quantize(w, "int8"), api.quant.quantize(w, "fp8_e4m3")),
            backend="xla", epilogue="swiglu",
        )


def test_swiglu_pair_through_scan_and_jit():
    """The dual-weight dispatch must cross jit/scan boundaries like any
    other matmul (layer-stacked gate/up pairs scan transparently)."""
    r = np.random.default_rng(41)
    wg = jnp.asarray(r.normal(0, 1, (3, 100, 130)).astype(np.float32))
    wu = jnp.asarray(r.normal(0, 1, (3, 100, 130)).astype(np.float32))
    sg = api.DipWeight.from_natural(wg)
    su = api.DipWeight.from_natural(wu)
    x = jnp.asarray(r.normal(0, 1, (8, 100)).astype(np.float32))

    @jax.jit
    def f(xx, g, u):
        return api.matmul(xx, (g, u), backend="pallas_dip", epilogue="swiglu")

    def body(carry, lw):
        g, u = lw
        return carry, f(x, g, u)

    _, scanned = jax.lax.scan(body, 0, (sg, su))
    assert scanned.shape == (3, 8, 130)
    for i in range(3):
        want = jax.nn.silu(x @ wg[i]) * (x @ wu[i])
        np.testing.assert_allclose(
            np.asarray(scanned[i]), np.asarray(want), atol=2e-3, rtol=2e-3,
        )


# -------------------------------------------------------------- gradients ---
@pytest.mark.parametrize("backend", sorted(CONFORMANCE))
def test_activation_gradients_match_xla(backend):
    """d/dx through every backend == the natively-differentiated XLA path.

    A *linear* functional (sum(out * c)) pins the output cotangent to a
    constant, so the comparison isolates the VJP rule from forward-value
    differences (the quantized forward is approximate by design)."""
    r = np.random.default_rng(11)
    x = jnp.asarray(r.normal(0, 1, (16, 100)).astype(np.float32))
    w = jnp.asarray(r.normal(0, 1, (100, 130)).astype(np.float32))
    c = jnp.asarray(r.normal(0, 1, (16, 130)).astype(np.float32))
    wobj = _weight_for(backend, w)
    be = api.get_backend(backend)
    # the quantized VJP is straight-through w.r.t. the DEQUANTIZED weight
    w_ref = api.quant.dequantize(wobj) if be.layout == "dip_q" else w

    g = jax.grad(lambda xx: jnp.sum(api.matmul(xx, wobj, backend=backend) * c))(x)
    g_ref = jax.grad(lambda xx: jnp.sum(api.matmul(xx, w_ref, backend="xla") * c))(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("backend", ["ws", "pallas_dip", "pallas_systolic"])
def test_weight_gradients_match_xla_on_float_backends(backend):
    r = np.random.default_rng(13)
    x = jnp.asarray(r.normal(0, 1, (16, 100)).astype(np.float32))
    w = jnp.asarray(r.normal(0, 1, (100, 130)).astype(np.float32))
    c = jnp.asarray(r.normal(0, 1, (16, 130)).astype(np.float32))
    wobj = _weight_for(backend, w)
    dw_xla = jax.grad(
        lambda d: jnp.sum(api.matmul(x, d, backend="xla") * c)
    )(api.DipWeight.from_natural(w))
    dw = jax.grad(lambda d: jnp.sum(api.matmul(x, d, backend=backend) * c))(wobj)
    if isinstance(wobj, api.DipWeight):
        assert isinstance(dw, api.DipWeight)
        np.testing.assert_allclose(
            np.asarray(dw.data), np.asarray(dw_xla.data), atol=1e-4, rtol=1e-4
        )
    else:  # natural-layout backend: plain array cotangent
        np.testing.assert_allclose(
            np.asarray(dw), np.asarray(dw_xla.to_natural()), atol=1e-4, rtol=1e-4
        )


def test_quantized_weight_cotangent_is_zero_not_garbage():
    """grad w.r.t. a QuantizedDipWeight's float leaves is exactly zero (the
    storage is frozen); the integer storage has no tangent at all."""
    x = jnp.asarray(np.random.default_rng(17).normal(0, 1, (8, 64)), jnp.float32)
    qw = api.quant.quantize(
        jnp.asarray(np.random.default_rng(18).normal(0, 1, (64, 64)), jnp.float32),
        "fp8_e4m3",
    )
    g = jax.grad(lambda q: jnp.sum(api.matmul(x, q)), allow_int=True)(qw)
    assert isinstance(g, api.QuantizedDipWeight)
    assert not np.asarray(jnp.abs(g.scale)).any()


# ------------------------------------------- pytree / jit / scan invariants --
def _mk_weights(stacked: bool):
    r = np.random.default_rng(21)
    shape = (3, 100, 130) if stacked else (100, 130)
    w = jnp.asarray(r.normal(0, 1, shape).astype(np.float32))
    return {
        "dip": api.DipWeight.from_natural(w),
        "quant_int8": api.quant.quantize(w, "int8"),
        "quant_fp8": api.quant.quantize(w, "fp8_e4m3"),
    }


@pytest.mark.parametrize("kind", ["dip", "quant_int8", "quant_fp8"])
def test_pytree_flatten_roundtrip_preserves_type_and_metadata(kind):
    wobj = _mk_weights(stacked=False)[kind]
    leaves, treedef = jax.tree_util.tree_flatten(wobj)
    assert len(leaves) == (1 if kind == "dip" else 2)
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert type(back) is type(wobj)
    assert (back.d_in, back.d_out, back.perm_tile) == (100, 130, 64)
    if kind != "dip":
        assert back.scheme == wobj.scheme
    # eval_shape routes ShapeDtypeStructs through the same container
    spec = jax.eval_shape(lambda t: t, wobj)
    assert type(spec) is type(wobj)
    assert spec.data.shape == wobj.data.shape


@pytest.mark.parametrize("kind", ["dip", "quant_int8", "quant_fp8"])
def test_jit_boundary_and_scan_match_unjitted_per_layer_calls(kind):
    stacked = _mk_weights(stacked=True)[kind]
    x = jnp.asarray(np.random.default_rng(22).normal(0, 1, (8, 100)), jnp.float32)

    @jax.jit
    def f(xx, wobj):
        return api.matmul(xx, wobj)

    def body(carry, lw):
        return carry, f(x, lw)

    _, scanned = jax.lax.scan(body, 0, stacked)
    assert scanned.shape == (3, 8, 130)
    for i in range(3):
        sliced = jax.tree_util.tree_map(lambda t: t[i], stacked)
        assert type(sliced) is type(stacked)
        np.testing.assert_allclose(
            np.asarray(scanned[i]), np.asarray(api.matmul(x, sliced)),
            atol=1e-5, rtol=1e-5,
        )


@pytest.mark.parametrize("scheme", sorted(api.quant.SCHEMES))
def test_checkpoint_roundtrip_quantized_weight_bit_exact(tmp_path, scheme):
    """save -> restore keeps storage and scales bit-exact, the scheme in the
    manifest, and matmul parity after restore; a scheme mismatch on restore
    is detected, not silently mis-dequantized."""
    from repro.checkpoint import restore_pytree, save_pytree

    r = np.random.default_rng(23)
    w = jnp.asarray(r.normal(0, 1, (100, 130)).astype(np.float32))
    qw = api.quant.quantize(w, scheme)
    tree = {"w": qw}
    path = str(tmp_path / "ck")
    save_pytree(path, tree)

    got = restore_pytree(path, jax.eval_shape(lambda: tree))["w"]
    assert isinstance(got, api.QuantizedDipWeight) and got.scheme == scheme
    np.testing.assert_array_equal(
        np.asarray(got.data).view(np.uint8), np.asarray(qw.data).view(np.uint8)
    )
    np.testing.assert_array_equal(np.asarray(got.scale), np.asarray(qw.scale))
    x = jnp.asarray(r.normal(0, 1, (4, 100)).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(api.matmul(x, got)), np.asarray(api.matmul(x, qw)),
        atol=1e-6, rtol=1e-6,
    )

    other = "fp8_e4m3" if scheme == "int8" else "int8"
    bad = {"w": api.QuantizedDipWeight(
        jax.eval_shape(lambda: tree)["w"].data,
        jax.eval_shape(lambda: tree)["w"].scale,
        100, 130, scheme=other,
    )}
    with pytest.raises(ValueError, match="metadata mismatch"):
        restore_pytree(path, bad)


def test_dequantized_fallback_keeps_activation_dtype():
    """A quantized weight through a non-quantized backend must not promote
    the output: dequantization happens AT the activation dtype, so bf16
    serving stays bf16 exactly like the float-weight path."""
    x = jnp.ones((4, 64), jnp.bfloat16)
    w = jnp.ones((64, 64), jnp.float32)
    qw = api.quant.quantize(w, "int8")
    for backend in ("xla", "ws", "pallas_dip"):
        got = api.matmul(x, qw, backend=backend)
        want = api.matmul(x, api.DipWeight.from_natural(w).astype(jnp.bfloat16),
                          backend=backend)
        assert got.dtype == want.dtype == jnp.bfloat16, backend


def test_quantize_params_validates_scheme_on_requantization():
    """quantize_params routes already-quantized nodes through quant.quantize:
    same scheme passes through untouched, a mismatch raises instead of
    silently leaving a mixed-scheme model."""
    from repro.models.transformer import quantize_params

    dw = api.DipWeight.from_natural(jnp.ones((64, 64), jnp.float32))
    qw = api.quant.quantize(jnp.ones((64, 64), jnp.float32), "fp8_e4m3")
    out = quantize_params({"a": dw, "b": qw}, "fp8_e4m3")
    assert out["a"].scheme == "fp8_e4m3" and out["b"] is qw
    with pytest.raises(ValueError, match="requantiz"):
        quantize_params({"a": dw, "b": qw}, "int8")


def test_contraction_validation_matches_float_path():
    """Quantized dispatch validates x against the LOGICAL d_in exactly like
    the float dip path (no silent zero-imputation into padding rows)."""
    qw = api.quant.quantize(jnp.ones((100, 130), jnp.float32), "int8")
    with pytest.raises(ValueError, match="contraction"):
        api.matmul(jnp.ones((4, 128), jnp.float32), qw)  # padded width
    with pytest.raises(ValueError, match="contraction"):
        api.matmul(jnp.ones((4, 96), jnp.float32), qw)   # too narrow
    with pytest.raises(ValueError, match="2-D"):
        api.matmul(
            jnp.ones((4, 100), jnp.float32),
            api.quant.quantize(jnp.ones((2, 100, 130), jnp.float32), "int8"),
        )


# -------------------------------------------------------------- prologues ---
# the mirror of the epilogue rows for the load-stage fusion: rmsnorm folded
# into the kernels' x-block load.  int8 activations are excluded for the
# same reason as epilogues (the normalized block is float arithmetic).
PROLOGUE_DTYPES = EPILOGUE_DTYPES


def test_prologue_capability_flags():
    """Every tiled builtin fuses the full prologue set at its load stage;
    xla declares none and relies on decomposition."""
    for backend in CONFORMANCE:
        be = api.get_backend(backend)
        if be.tiled:
            assert set(api.backend_prologues(backend)) == set(api.PROLOGUES), backend
        else:
            assert set(api.backend_prologues(backend)) == {"none"}, backend


def test_prologue_registration_rules():
    """Non-tiled, non-sharded backends cannot declare fused prologues (no
    load stage to fuse into); unknown prologue names are rejected at
    dispatch, not silently unfused."""
    with pytest.raises(ValueError, match="cannot fuse prologues"):
        api.register_backend("bad_pro", lambda *a, **k: None,
                             layout="natural", tiled=False,
                             prologues=("rmsnorm",))
    x = jnp.ones((4, 64), jnp.float32)
    w = jnp.ones((64, 64), jnp.float32)
    with pytest.raises(ValueError, match="unknown prologue"):
        api.matmul(x, w, prologue="layernorm")
    with pytest.raises(ValueError, match="operand"):
        api.matmul(x, w, prologue="rmsnorm")  # missing gain


@pytest.mark.parametrize(
    "backend,dtype",
    [(b, d) for b, dts in PROLOGUE_DTYPES.items() for d in dts],
)
def test_backend_prologue_matches_decomposed(backend, dtype):
    """Fused rmsnorm prologue == rms_norm(x, g) -> matmul through the SAME
    backend, on an aligned and a ragged shape.  xla's rows prove the
    decomposition path; the tiled rows prove the in-kernel load rescale
    (including the ragged-K case, where the mean's divisor must stay the
    logical width, not the padded one)."""
    from repro.kernels import prologue as prologue_lib

    for m, k, n, seed in ((8, 64, 64, 0), (17, 100, 130, 1)):
        r = np.random.default_rng(seed)
        x = jnp.asarray(r.normal(0, 1, (m, k)).astype(np.float32)).astype(dtype)
        w = jnp.asarray(r.normal(0, 1, (k, n)).astype(np.float32)).astype(dtype)
        g = jnp.asarray(r.normal(1, 0.1, (k,)).astype(np.float32))
        wobj = _weight_for(backend, w)
        got = api.matmul(x, wobj, backend=backend,
                         prologue="rmsnorm", prologue_operands=(g,))
        xn = prologue_lib.apply("rmsnorm", x, g)
        want = api.matmul(xn, wobj, backend=backend)
        assert got.shape == (m, n)
        if api.get_backend(backend).layout == "dip_q":
            tol = (dict(atol=2e-3, rtol=2e-3) if dtype == "float32"
                   else dict(atol=0.1, rtol=0.05))
        else:
            tol = TOL[dtype]
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), **tol,
            err_msg=f"{backend}/{dtype} {m}x{k}x{n}",
        )


def test_prologue_epilogue_composition_single_launch():
    """rmsnorm prologue + bias_silu epilogue + the matmul is still exactly
    ONE pallas launch on the fused backends, and matches the three-step
    decomposed composition."""
    m, k, n = 16, 100, 130
    r = np.random.default_rng(47)
    x = jnp.asarray(r.normal(0, 1, (m, k)).astype(np.float32))
    w = jnp.asarray(r.normal(0, 1, (k, n)).astype(np.float32))
    g = jnp.asarray(r.normal(1, 0.1, (k,)).astype(np.float32))
    bias = jnp.asarray(r.normal(0, 1, (n,)).astype(np.float32))
    dw = api.DipWeight.from_natural(w)

    def fused(xx):
        return api.matmul(xx, dw, backend="pallas_dip",
                          prologue="rmsnorm", prologue_operands=(g,),
                          epilogue="bias_silu", epilogue_operands=(bias,))

    def decomposed(xx):
        return api.matmul(xx, dw, backend="xla",
                          prologue="rmsnorm", prologue_operands=(g,),
                          epilogue="bias_silu", epilogue_operands=(bias,))

    np.testing.assert_allclose(np.asarray(fused(x)), np.asarray(decomposed(x)),
                               atol=2e-3, rtol=2e-3)

    def count_pallas(fn, *args):
        closed = jax.make_jaxpr(fn)(*args)

        def walk(jx):
            return sum(
                (eqn.primitive.name == "pallas_call")
                + sum(walk(sub) for sub in jax.core.jaxprs_in_params(eqn.params))
                for eqn in jx.eqns
            )

        return walk(closed.jaxpr)

    assert count_pallas(fused, x) == 1
    assert count_pallas(decomposed, x) == 0


@pytest.mark.parametrize("backend", sorted(CONFORMANCE))
def test_prologue_gradients_match_decomposed_xla(backend):
    """d/dx, d/d(gain), and d/dw (float backends) through the FUSED
    rmsnorm-prologue kernel must match the natively-differentiated
    decomposed XLA path — the recompute VJP re-derives the normalized block
    from the raw activations, and this test keeps that recompute exact."""
    m, k, n = 16, 100, 130
    r = np.random.default_rng(53)
    c = jnp.asarray(r.normal(0, 1, (m, n)).astype(np.float32))
    x = jnp.asarray(r.normal(0, 1, (m, k)).astype(np.float32))
    w = jnp.asarray(r.normal(0, 1, (k, n)).astype(np.float32))
    g = jnp.asarray(r.normal(1, 0.1, (k,)).astype(np.float32))
    wobj = _weight_for(backend, w)
    be = api.get_backend(backend)
    ref_w = api.quant.dequantize(wobj) if be.layout == "dip_q" else wobj

    def loss(backend_name, wgt):
        def f(xx, gg):
            out = api.matmul(xx, wgt, backend=backend_name,
                             prologue="rmsnorm", prologue_operands=(gg,))
            return jnp.sum(out * c)
        return f

    got = jax.grad(loss(backend, wobj), argnums=(0, 1))(x, g)
    want = jax.grad(loss("xla", ref_w), argnums=(0, 1))(x, g)
    for a, b in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-3, rtol=1e-3,
            err_msg=f"{backend} prologue grad",
        )

    if be.layout in ("natural", "dip") and be.tiled:
        gw = jax.grad(lambda wgt: loss(backend, wgt)(x, g))(wobj)
        gw_ref = jax.grad(lambda wgt: loss("xla", wgt)(x, g))(wobj)
        for a, b in zip(jax.tree_util.tree_leaves(gw),
                        jax.tree_util.tree_leaves(gw_ref)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-3, rtol=1e-3,
                err_msg=f"{backend} prologue weight grad",
            )
