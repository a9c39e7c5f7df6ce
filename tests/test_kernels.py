"""Pallas kernels (interpret mode) vs pure-jnp oracles: shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dg.basis import diff_matrix, lgl_nodes_weights
from repro.kernels import ref
from repro.kernels.dg_flux import dg_flux_pallas
from repro.kernels.dg_volume import dg_volume_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ops import dg_flux, dg_volume, flash_attention_op

RNG = np.random.default_rng(7)


def _tol(dt):
    return dict(rtol=5e-4, atol=5e-4) if dt == "float32" else dict(rtol=1e-11, atol=1e-11)


@pytest.mark.parametrize("K,order", [(16, 7), (37, 7), (24, 3), (7, 5), (1, 2)])
@pytest.mark.parametrize("dt", ["float32", "float64"])
def test_dg_volume_kernel(K, order, dt):
    M = order + 1
    x, _ = lgl_nodes_weights(order)
    D = jnp.asarray(diff_matrix(x), dt)
    q = jnp.asarray(RNG.standard_normal((K, 9, M, M, M)), dt)
    rho = jnp.asarray(RNG.uniform(0.5, 2, K), dt)
    lam = jnp.asarray(RNG.uniform(0.5, 2, K), dt)
    mu = jnp.asarray(RNG.uniform(0, 2, K), dt)
    metrics = (2.0, 3.0, 4.0)
    out = dg_volume_pallas(q, D, metrics, rho, lam, mu, interpret=True)
    want = ref.dg_volume_ref(q, D, metrics, rho, lam, mu)
    np.testing.assert_allclose(out, want, **_tol(dt))


def test_dg_volume_operators_built_once_outside_the_trace():
    """A concrete D gives cached host operators, which enter the traced
    program as a constant: no op of the trace builds an (M^3, M^3) array.
    A traced D builds the same operators inside the trace."""
    from repro.kernels.dg_volume import derivative_operators

    order, K = 3, 8
    M, N = order + 1, (order + 1) ** 3
    x, _ = lgl_nodes_weights(order)
    D = jnp.asarray(diff_matrix(x), "float32")
    metrics = (2.0, 3.0, 4.0)
    ops = derivative_operators(D, metrics)
    assert isinstance(ops, np.ndarray) and ops.shape == (3, N, N) and ops.dtype == np.float32
    assert derivative_operators(D, metrics) is ops
    traced = jax.jit(lambda d: derivative_operators(d, metrics))(D)
    np.testing.assert_array_equal(np.asarray(traced), ops)

    q = jnp.zeros((K, 9, M, M, M), jnp.float32)
    mat = jnp.ones(K, jnp.float32)
    jaxpr = jax.make_jaxpr(lambda q: dg_volume_pallas(
        q, D, metrics, mat, mat, mat, interpret=True))(q)
    built = [e for e in jaxpr.jaxpr.eqns
             if any(getattr(v.aval, "shape", ())[-2:] == (N, N) for v in e.outvars)]
    assert not built, built


@pytest.mark.parametrize("F,M", [(10, 8), (200, 4), (128, 8), (300, 8)])
@pytest.mark.parametrize("dt", ["float32", "float64"])
@pytest.mark.parametrize("axis,sign", [(0, 1.0), (1, -1.0), (2, 1.0)])
def test_dg_flux_kernel(F, M, dt, axis, sign):
    Sm = jnp.asarray(RNG.standard_normal((F, 6, M, M)), dt)
    vm = jnp.asarray(RNG.standard_normal((F, 3, M, M)), dt)
    Sp = jnp.asarray(RNG.standard_normal((F, 6, M, M)), dt)
    vp = jnp.asarray(RNG.standard_normal((F, 3, M, M)), dt)
    mats = np.abs(RNG.standard_normal((F, 8))) + 0.5
    mats[: F // 3, 3] = 0.0  # acoustic minus side -> k1 = 0 branch
    mats = jnp.asarray(mats, dt)
    FE1, Fv1 = dg_flux_pallas(Sm, vm, Sp, vp, mats, axis, sign, interpret=True)
    FE2, Fv2 = ref.dg_flux_ref(Sm, vm, Sp, vp, mats, axis, sign)
    np.testing.assert_allclose(FE1, FE2, **_tol(dt))
    np.testing.assert_allclose(Fv1, Fv2, **_tol(dt))


@pytest.mark.parametrize("S,D,blocks", [(256, 64, (64, 64)), (192, 32, (64, 32)), (128, 128, (128, 128))])
@pytest.mark.parametrize("mode", ["causal", "encoder", "swa"])
@pytest.mark.parametrize("dt", ["float32", "bfloat16"])
def test_flash_kernel(S, D, blocks, mode, dt):
    B, H = 2, 2
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), dt)
    k = jax.random.normal(ks[1], (B, H, S, D), dt)
    v = jax.random.normal(ks[2], (B, H, S, D), dt)
    kw = dict(causal=(mode != "encoder"), window=(S // 4 if mode == "swa" else None))
    out = flash_attention_pallas(q, k, v, block_q=blocks[0], block_k=blocks[1],
                                 interpret=True, **kw)
    want = ref.flash_attention_ref(q, k, v, **kw)
    tol = dict(rtol=5e-4, atol=5e-4) if dt == "float32" else dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want, np.float32), **tol)


def test_ops_impl_switch():
    """xla / interpret impls agree through the ops wrappers."""
    order = 3
    M = order + 1
    x, _ = lgl_nodes_weights(order)
    D = jnp.asarray(diff_matrix(x), "float32")
    q = jnp.asarray(RNG.standard_normal((8, 9, M, M, M)), "float32")
    rho = jnp.ones(8, jnp.float32)
    lam = jnp.ones(8, jnp.float32)
    mu = jnp.ones(8, jnp.float32)
    a = dg_volume(q, D, (2.0, 2.0, 2.0), rho, lam, mu, impl="xla")
    b = dg_volume(q, D, (2.0, 2.0, 2.0), rho, lam, mu, impl="interpret")
    np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-4)
