"""Property tests for the DiP weight permutation (paper Fig. 3)."""

import numpy as np
import jax.numpy as jnp
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import dataflow, permute

dims = st.integers(min_value=1, max_value=24)


@settings(max_examples=40)
@given(rows=dims, cols=dims, seed=st.integers(0, 2**31 - 1))
def test_permute_matches_paper_pseudocode(rows, cols, seed):
    w = np.random.default_rng(seed).integers(-100, 100, size=(rows, cols))
    got = np.asarray(permute.permute_weights(jnp.asarray(w)))
    want = permute.permute_weights_np(w)
    np.testing.assert_array_equal(got, want)


@settings(max_examples=40)
@given(rows=dims, cols=dims, seed=st.integers(0, 2**31 - 1))
def test_permute_roundtrip(rows, cols, seed):
    w = np.random.default_rng(seed).normal(size=(rows, cols)).astype(np.float32)
    p = permute.permute_weights(jnp.asarray(w))
    back = permute.unpermute_weights(p)
    np.testing.assert_allclose(np.asarray(back), w)


@settings(max_examples=25)
@given(
    rows=st.integers(1, 150),
    cols=st.integers(1, 150),
    tile=st.sampled_from([4, 8, 16, 64]),
    seed=st.integers(0, 2**31 - 1),
)
def test_tiled_permute_roundtrip_any_shape(rows, cols, tile, seed):
    w = np.random.default_rng(seed).normal(size=(rows, cols)).astype(np.float32)
    p = permute.permute_tiled(jnp.asarray(w), tile)
    assert p.shape[-2] % tile == 0 and p.shape[-1] % tile == 0  # padded storage
    back = permute.unpermute_tiled(p, tile)[:rows, :cols]
    np.testing.assert_allclose(np.asarray(back), w)


@settings(max_examples=25)
@given(n=st.integers(2, 16), m=st.integers(1, 32), seed=st.integers(0, 2**31 - 1))
def test_rolled_mac_identity(n, m, seed):
    """out[m,i] = sum_r x[m,(i+r)%N] * P[r,i]  ==  x @ W  (paper Sec III-B)."""
    r = np.random.default_rng(seed)
    x = r.integers(-20, 20, size=(m, n))
    w = r.integers(-20, 20, size=(n, n))
    p = permute.permute_weights_np(w)
    got = dataflow.dip_matmul_rolled_np(x, p)
    np.testing.assert_array_equal(got, x @ w)
    # jax version agrees
    got_jax = dataflow.dip_matmul_rolled(jnp.asarray(x), jnp.asarray(p))
    np.testing.assert_array_equal(np.asarray(got_jax), x @ w)


def test_permutation_is_column_rotation():
    """Each column i is rotated up by i (the Fig. 2c description)."""
    n = 8
    w = np.arange(n * n).reshape(n, n)
    p = permute.permute_weights_np(w)
    for i in range(n):
        np.testing.assert_array_equal(p[:, i], np.roll(w[:, i], -i))


def test_batched_permute():
    w = np.random.default_rng(0).normal(size=(3, 2, 16, 16)).astype(np.float32)
    p = permute.permute_weights(jnp.asarray(w))
    for a in range(3):
        for b in range(2):
            np.testing.assert_allclose(
                np.asarray(p[a, b]), permute.permute_weights_np(w[a, b])
            )
