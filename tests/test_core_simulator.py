"""Cycle-accurate simulators: numerically exact + timing == eqs. (1)-(7)."""

import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import analytical, permute, simulator


@settings(max_examples=20)
@given(
    n=st.integers(2, 12),
    extra=st.integers(0, 12),
    s=st.sampled_from([1, 2]),
    seed=st.integers(0, 2**31 - 1),
)
def test_dip_simulator_exact_and_on_time(n, extra, s, seed):
    m = n + extra
    r = np.random.default_rng(seed)
    x = r.integers(-50, 50, size=(m, n))
    w = r.integers(-50, 50, size=(n, n))
    res = simulator.simulate_dip(x, w, stages=s)
    np.testing.assert_array_equal(res.output, x @ w)
    assert res.latency == analytical.dip_streaming_latency(n, m, s)
    assert res.tfpu == analytical.dip_tfpu(n)


@settings(max_examples=20)
@given(
    n=st.integers(2, 10),
    extra=st.integers(0, 10),
    s=st.sampled_from([1, 2]),
    seed=st.integers(0, 2**31 - 1),
)
def test_ws_simulator_exact_and_on_time(n, extra, s, seed):
    m = n + extra
    r = np.random.default_rng(seed)
    x = r.integers(-50, 50, size=(m, n))
    w = r.integers(-50, 50, size=(n, n))
    res = simulator.simulate_ws(x, w, stages=s)
    np.testing.assert_array_equal(res.output, x @ w)
    assert res.latency == analytical.ws_streaming_latency(n, m, s)
    # WS needs M >= 2N-1 rows to ever reach full utilization
    if m >= 2 * n - 1:
        assert res.tfpu == analytical.ws_tfpu(n)
    else:
        assert res.tfpu is None


def test_fig4_walkthrough_timing():
    """Paper Fig. 4 (3x3, 2-stage MAC): first output at cycle 3, last at 5."""
    x = np.arange(1, 10).reshape(3, 3)
    w = np.arange(9).reshape(3, 3)
    res = simulator.simulate_dip(x, w, stages=2)
    assert res.first_output_cycle == 3
    assert res.latency == 6            # cycles 0..5  == 2N+S-2
    assert res.tfpu == 3               # eq. (7)


def test_weight_load_shifts_to_permuted_layout():
    w = np.random.default_rng(1).integers(-5, 5, size=(6, 6))
    resident = simulator.simulate_weight_load_dip(w)
    np.testing.assert_array_equal(resident, permute.permute_weights_np(w))


def test_dip_fills_with_m_equals_n_but_ws_does_not():
    """DiP reaches 100% PE rows at M=N; WS's diagonal wavefront cannot."""
    n = 8
    r = np.random.default_rng(2)
    x = r.integers(-5, 5, size=(n, n))
    w = r.integers(-5, 5, size=(n, n))
    dip = simulator.simulate_dip(x, w)
    ws = simulator.simulate_ws(x, w)
    assert dip.tfpu == n
    assert ws.tfpu is None
    assert max(dip.active_rows) == n
    assert max(ws.active_rows) < n * n


def test_float_and_prepermuted_paths():
    n, m = 8, 16
    r = np.random.default_rng(3)
    x = r.normal(size=(m, n))
    w = r.normal(size=(n, n))
    res = simulator.simulate_dip(x, w)
    np.testing.assert_allclose(res.output, x @ w, rtol=1e-12)
    p = permute.permute_weights_np(w)
    res2 = simulator.simulate_dip(x, p, weights_prepermuted=True)
    np.testing.assert_allclose(res2.output, x @ w, rtol=1e-12)
