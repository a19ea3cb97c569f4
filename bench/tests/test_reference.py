"""The plain float32 reference against the program's float32 ``xla``
forward, on the CPU at a small size, with the benchmark's weights loaded
into the program's storage."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import program, reference
from bench import weights as W
from bench.tests.conftest import TINY


@pytest.mark.parametrize("bias", [False, True])
def test_reference_matches_program_forward(bias):
    from repro.models import transformer as tf_model

    conf = dict(TINY, attention_bias=bias, num_key_value_heads=4 if bias else 2,
                run={"matmul_backend": "xla", "compute_dtype": "float32"})
    dims = W.Dims({k: v for k, v in conf.items() if k not in ("program_arch", "run")})
    cfg = program.arch_config(conf)
    key = W.seed_key(2**33 + 17)
    params = program.load_params(cfg, dims, key)
    toks = np.random.default_rng(0).integers(0, dims.vocab, 77).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = tf_model.forward(params, cfg, tokens=jnp.asarray(toks[None]))[0]
    got = np.asarray(got[0, :, :dims.vocab])
    want = reference.logits_at(key, dims, toks, np.arange(toks.size))
    assert np.abs(got - want).max() < 1e-4 * max(1.0, np.abs(want).max())
    low = reference.logits_at(key, dims, toks, np.arange(toks.size), mode="int8")
    assert np.abs(low - want).max() > 10 * np.abs(got - want).max()


def test_layer_weights_remade_alike():
    dims = W.Dims(TINY)
    key = W.seed_key(5)
    a = jax.jit(lambda k: W.layer_weights(k, dims, 1))(key)
    b = W.layer_weights(key, dims, jnp.int32(1))
    for nm in a:
        np.testing.assert_allclose(np.asarray(a[nm]), np.asarray(b[nm]), rtol=1e-6, atol=1e-7)
    assert not np.array_equal(np.asarray(a["wq"]),
                              np.asarray(W.layer_weights(key, dims, 0)["wq"]))
