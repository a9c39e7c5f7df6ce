"""The flux stage (``surface_rhs``) against the per-face formulation it
replaced: six passes, each slicing one face of the volume stress and of
the velocity, gathering the neighbours' opposite faces, solving the
Riemann problem and adding the lifted correction into the state.  That
formulation is kept here only as the oracle.

Both run op by op (no ``jit``): each operation is then compiled alone, and
the stage, doing the same operations in the same order at every node, must
match the oracle bitwise.  Inside a fusion XLA's CPU backend contracts a
multiply and an add into one fused multiply-add, which rounds once where
the oracle rounds twice; the Pallas interpreter compiles the kernel body
as one unit, so the ``interpret`` body is held to under one ulp of the
largest value instead."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.dg.operators import FACE_AXIS, FACE_SIGN, OPPOSITE, SYM, stress, surface_rhs
from repro.dg.solver import make_two_tree_solver


def _extract_face(u, face):
    ax = FACE_AXIS[face]
    idx = 0 if FACE_SIGN[face] < 0 else u.shape[2 + ax] - 1
    return jnp.take(u, idx, axis=2 + ax)


def _riemann_correction(Sm, vm, Sp, vp, axis, sign, mat_m, mat_p):
    e = lambda x: x[:, None, None]
    k0 = 1.0 / (e(mat_m["rho"] * mat_m["cp"]) + e(mat_p["rho"] * mat_p["cp"]))
    denom_s = e(mat_m["rho"] * mat_m["cs"]) + e(mat_p["rho"] * mat_p["cs"])
    k1 = jnp.where(e(mat_m["mu"]) > 0, 1.0 / jnp.maximum(denom_s, 1e-300), 0.0)
    S_j = Sm - Sp
    v_j = vm - vp
    a0, a1, a2 = axis, (axis + 1) % 3, (axis + 2) % 3
    S_aa, S_a1, S_a2 = S_j[:, SYM[a0, a0]], S_j[:, SYM[a0, a1]], S_j[:, SYM[a0, a2]]
    rcp_p = e(mat_p["rho"] * mat_p["cp"])
    rcs_p = e(mat_p["rho"] * mat_p["cs"])
    rcp_m = e(mat_m["rho"] * mat_m["cp"])
    rcs_m = e(mat_m["rho"] * mat_m["cs"])
    a = k0 * (S_aa + rcp_p * sign * v_j[:, a0])
    FE = jnp.zeros_like(S_j)
    FE = FE.at[:, SYM[a0, a0]].set(a)
    FE = FE.at[:, SYM[a0, a1]].set(0.5 * k1 * (S_a1 + rcs_p * sign * v_j[:, a1]))
    FE = FE.at[:, SYM[a0, a2]].set(0.5 * k1 * (S_a2 + rcs_p * sign * v_j[:, a2]))
    Fv = jnp.zeros_like(v_j)
    Fv = Fv.at[:, a0].set(a * rcp_m * sign)
    Fv = Fv.at[:, a1].set(k1 * rcs_m * (sign * S_a1 + rcs_p * v_j[:, a1]))
    Fv = Fv.at[:, a2].set(k1 * rcs_m * (sign * S_a2 + rcs_p * v_j[:, a2]))
    return FE, Fv


def per_face_surface_rhs(q, neighbors, lift, rho, lam, mu, cp, cs):
    """The per-face loop over (K, F, M, M) face arrays, state-sized adds."""
    S = stress(q, lam, mu)
    out = jnp.zeros_like(q)
    mats = {"rho": rho, "cp": cp, "cs": cs, "mu": mu}
    for face in range(6):
        ax, sign, nbr = FACE_AXIS[face], FACE_SIGN[face], neighbors[:, face]
        has_nbr, skip, nbr_safe = nbr >= 0, nbr == -2, jnp.maximum(nbr, 0)
        Sm, vm = _extract_face(S, face), _extract_face(q[:, 6:9], face)
        Sp = _extract_face(S, OPPOSITE[face])[nbr_safe]
        vp = _extract_face(q[:, 6:9], OPPOSITE[face])[nbr_safe]
        hn = has_nbr[:, None, None, None]
        Sp, vp = jnp.where(hn, Sp, -Sm), jnp.where(hn, vp, vm)
        mat_p = {k: jnp.where(has_nbr, v[nbr_safe], v) for k, v in mats.items()}
        FE, Fv = _riemann_correction(Sm, vm, Sp, vp, ax, sign, mats, mat_p)
        corr = -lift[ax] * jnp.concatenate([FE, Fv / rho[:, None, None, None]], axis=1)
        corr = jnp.where(skip[:, None, None, None], 0.0, corr)
        idx = 0 if sign < 0 else q.shape[2 + ax] - 1
        sl = [slice(None)] * 5
        sl[2 + ax] = idx
        out = out.at[tuple(sl)].add(corr)
    return out


@pytest.mark.parametrize("order", [2, 3, 4])
@pytest.mark.parametrize("kernel_impl", ["xla", "interpret"])
@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_surface_rhs_matches_per_face_formulation(order, kernel_impl, dtype):
    """Coupled elastic/acoustic materials (mu = 0 on the acoustic half), a
    non-periodic brick (-1 physical faces), and a tenth of the faces marked
    -2 (cross-partition skip faces), on a random state."""
    s = make_two_tree_solver(grid=(4, 3, 3), order=order, dtype=dtype)
    K, M = s.mesh.K, s.M
    rng = np.random.default_rng(order)
    nbr = np.asarray(s.neighbors).copy()
    assert (nbr == -1).any() and (np.asarray(s.mu_j) == 0).any()
    nbr[rng.random(nbr.shape) < 0.1] = -2
    q = jnp.asarray(rng.standard_normal((K, 9, M, M, M)), dtype)
    # the lift as Python floats: the scale multiplies in the state's dtype
    lift = tuple(float(x) for x in s.lift)
    args = (jnp.asarray(nbr), lift, s.rho_j, s.lam_j, s.mu_j, s.cp_j, s.cs_j)
    got = np.asarray(surface_rhs(q, *args, kernel_impl=kernel_impl))
    want = np.asarray(per_face_surface_rhs(q, *args))
    assert got.shape == want.shape and got.dtype == want.dtype
    if kernel_impl == "xla":
        np.testing.assert_array_equal(got, want)
    else:
        ulp = np.finfo(dtype).eps * np.abs(want).max()
        np.testing.assert_allclose(got, want, rtol=0, atol=ulp)
