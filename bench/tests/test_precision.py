"""The precisions of the references and their controls: each step down
errs more, the same way under jit as eagerly."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import precision as P


def _errors():
    r = np.random.default_rng(0)
    a = r.standard_normal((64, 256)).astype(np.float32)
    b = r.standard_normal((256, 32)).astype(np.float32)
    exact = a.astype(np.float64) @ b.astype(np.float64)
    out = {}
    for kind in P.KINDS:
        got = np.asarray(jax.jit(lambda x, y, k=kind: P.matmul(x, y, k))(
            jnp.asarray(a), jnp.asarray(b)), np.float64)
        eager = np.asarray(P.matmul(jnp.asarray(a), jnp.asarray(b), kind))
        np.testing.assert_allclose(got, eager, rtol=1e-5, atol=1e-4)
        out[kind] = np.max(np.abs(got - exact)) / np.max(np.abs(exact))
    return out


def test_each_step_down_errs_more():
    e = _errors()
    assert e["highest"] < 1e-6
    assert 1e-6 < e["high"] < 1e-4          # three bf16 passes: ~2^-17
    assert 5e-4 < e["bf16"] < 1e-2          # one bf16 pass: ~2^-9
    assert 1e-2 < e["fp8"] < 2e-1           # e4m3: ~2^-4
    assert e["highest"] < e["high"] < e["bf16"] < e["fp8"]


def test_control_precision_of_each_configuration():
    assert P.control({"dtype": "float32", "matmul_precision": "highest"}) == "high"
    assert P.control({"dtype": "float32", "matmul_precision": "default"}) == "bf16"
    assert P.control({"dtype": "bfloat16", "matmul_precision": "default"}) == "fp8"
    with pytest.raises(ValueError):
        P.control({"dtype": "int8", "matmul_precision": "default"})
    with pytest.raises(ValueError):
        P.einsum("ij,jk->ik", jnp.ones((2, 2)), jnp.ones((2, 2)), "int4")
