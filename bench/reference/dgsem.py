"""Plain reference of the coupled elastic-acoustic DGSEM step.

Written from the method's description (arXiv:1307.4731 sections 3-4; the
exact Riemann flux of Wilcox et al. 2010), not from the program: it
imports nothing of ``repro`` and takes no table the program builds.  It
shares only the interface conventions a caller of the program uses: the
state's nine fields (strain xx, yy, zz, yz, xz, xy, then velocity x, y, z),
element ``k = ix + nx * (iy + ny * iz)`` of the brick, and node axes
``(r1, r2, r3)`` along ``(x, y, z)``.

Layout: ``(9, M, M, M, K)``, elements on the minor axis.  A face neighbour
is the element one grid stride away, so the plus-side traces of a face are
the minus-side traces of the opposite face shifted by that stride, masked
at the brick's walls (traction-free mirror: ``[v] = 0``, ``S^+ = -S^-``).

``precision`` is the precision of the derivative contractions, the only
products of two arrays in the step: ``"highest"`` is float32 throughout;
``"high"`` is the three-pass bfloat16 product (``bf16_3x``), written out so
that it reads the same on every backend (the CPU computes a float32 dot in
full whatever precision it is asked for).  The rest is float32 arithmetic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# Carpenter & Kennedy (1994) 2N-storage RK4(5), solution 3
RK_A = np.array([0.0, -567301805773.0 / 1357537059087.0, -2404267990393.0 / 2016746695238.0,
                 -3550918686646.0 / 2091501179385.0, -1275806237668.0 / 842570457699.0])
RK_B = np.array([1432997174477.0 / 9575080441755.0, 5161836677717.0 / 13612068292357.0,
                 1720146321549.0 / 2090206949498.0, 3134564353537.0 / 4481467310338.0,
                 2277821191437.0 / 14882151754819.0])

# strain slot of the symmetric tensor entry (a, b)
VOIGT = ((0, 5, 4), (5, 1, 3), (4, 3, 2))


def lgl(order: int):
    """Legendre-Gauss-Lobatto nodes and weights on [-1, 1], float64."""
    leg = np.polynomial.legendre.Legendre.basis(order)
    x = np.concatenate([[-1.0], np.sort(leg.deriv().roots().real), [1.0]])
    w = 2.0 / (order * (order + 1) * leg(x) ** 2)
    return x, w


def diff_matrix(x: np.ndarray) -> np.ndarray:
    """D[i, j] = l_j'(x_i) for the Lagrange basis on nodes x (barycentric)."""
    diff = x[:, None] - x[None, :]
    np.fill_diagonal(diff, 1.0)
    bw = 1.0 / diff.prod(axis=1)
    D = (bw[None, :] / bw[:, None]) / diff
    np.fill_diagonal(D, 0.0)
    np.fill_diagonal(D, -D.sum(axis=1))
    return D


class Problem:
    """The brick, its materials and the time step of one configuration."""

    def __init__(self, cfg: dict):
        self.order = int(cfg["order"])
        self.M = self.order + 1
        self.grid = tuple(int(n) for n in cfg["grid"])
        self.extent = tuple(float(e) for e in cfg["extent"])
        self.K = int(np.prod(self.grid))
        self.h = tuple(e / n for e, n in zip(self.extent, self.grid))
        self.nodes, self.weights = lgl(self.order)
        self.D = diff_matrix(self.nodes)
        nx, ny, nz = self.grid
        k = np.arange(self.K)
        self.index = (k % nx, (k // nx) % ny, k // (nx * ny))  # (ix, iy, iz)
        self.strides = (1, nx, nx * ny)
        mat = cfg["materials"]
        # Fig 6.1: acoustic below the x midplane, elastic above it
        side = ((self.index[0] + 0.5) * self.h[0] >= self.extent[0] / 2).astype(int)
        self.rho = np.asarray(mat["rho"], float)[side]
        cp = np.asarray(mat["cp"], float)[side]
        cs = np.asarray(mat["cs"], float)[side]
        self.mu = self.rho * cs**2
        self.lam = self.rho * (cp**2 - 2 * cs**2)
        self.cp, self.cs = cp, cs
        self.dt = float(cfg["cfl"]) * min(self.h) / (cp.max() * self.order**2)


def _contract(D, u, axis, precision):
    """sum_m D[i, m] u[..., m along node axis, ...] for u (C, M, M, M, K)."""
    spec = ("im,cmjlk->cijlk", "im,cjmlk->cjilk", "im,cjlmk->cjlik")[axis]
    if precision == "highest":
        return jnp.einsum(spec, D, u, precision=jax.lax.Precision.HIGHEST)
    if precision == "high":
        # reduce_precision, not a round trip through bfloat16: XLA may drop a
        # convert pair as excess precision, and on the TPU it does
        bf = lambda x: jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        D_hi, u_hi = bf(D), bf(u)
        D_lo, u_lo = bf(D - D_hi), bf(u - u_hi)
        hp = functools.partial(jnp.einsum, spec, precision=jax.lax.Precision.HIGHEST)
        return hp(D_hi, u_hi) + (hp(D_hi, u_lo) + hp(D_lo, u_hi))
    raise ValueError(f"precision must be 'highest' or 'high', got {precision!r}")


def _stress(E, lam, mu):
    tr = E[0] + E[1] + E[2]
    return jnp.stack([lam * tr + 2 * mu * E[0], lam * tr + 2 * mu * E[1],
                      lam * tr + 2 * mu * E[2], 2 * mu * E[3], 2 * mu * E[4], 2 * mu * E[5]])


def _face(u, axis, last):
    """Node slice of u (C, M, M, M, K) on the low (last=False) or high face."""
    i = -1 if last else 0
    return (u[:, i], u[:, :, i], u[:, :, :, i])[axis]


def _shift(x, s):
    """y[..., k] = x[..., k + s], zero past either end."""
    if s > 0:
        return jnp.concatenate([x[..., s:], jnp.zeros(x.shape[:-1] + (s,), x.dtype)], -1)
    return jnp.concatenate([jnp.zeros(x.shape[:-1] + (-s,), x.dtype), x[..., :s]], -1)


def make_rhs(prob: Problem, precision: str = "highest"):
    """rhs(q) of the semi-discrete system for q (9, M, M, M, K) float32."""
    f32 = lambda x: jnp.asarray(np.asarray(x, np.float64), jnp.float32)
    rho, lam, mu = f32(prob.rho), f32(prob.lam), f32(prob.mu)
    zc, zs = f32(prob.rho * prob.cp), f32(prob.rho * prob.cs)  # impedances
    Dm = [f32(prob.D * (2.0 / h)) for h in prob.h]
    lift = [2.0 / h / prob.weights[0] for h in prob.h]
    n = prob.grid
    inside = {  # (axis, side) -> (K,) True where that face has a neighbour
        (a, s): jnp.asarray(prob.index[a] < n[a] - 1 if s > 0 else prob.index[a] > 0)
        for a in range(3) for s in (-1, 1)
    }

    def rhs(q):
        E, v = q[:6], q[6:]
        S = _stress(E, lam, mu)
        # volume: dE/dt = sym grad v, rho dv/dt = div S
        dv = [_contract(Dm[a], v, a, precision) for a in range(3)]
        dS = [_contract(Dm[a], jnp.stack([S[i] for i in VOIGT[a]]), a, precision)
              for a in range(3)]
        dE = jnp.stack([dv[0][0], dv[1][1], dv[2][2], 0.5 * (dv[1][2] + dv[2][1]),
                        0.5 * (dv[0][2] + dv[2][0]), 0.5 * (dv[0][1] + dv[1][0])])
        dvel = (dS[0] + dS[1] + dS[2]) / rho
        out = jnp.concatenate([dE, dvel])
        # surface: exact Riemann correction on each of the six faces
        for a in range(3):
            a1, a2 = (a + 1) % 3, (a + 2) % 3
            for sign in (-1.0, 1.0):
                last = sign > 0
                Sm, vm = _face(S, a, last), _face(v, a, last)
                stride = int(sign) * prob.strides[a]
                has = inside[(a, int(sign))]
                Sp = jnp.where(has, _shift(_face(S, a, not last), stride), -Sm)
                vp = jnp.where(has, _shift(_face(v, a, not last), stride), vm)
                zc_p = jnp.where(has, _shift(zc, stride), zc)
                zs_p = jnp.where(has, _shift(zs, stride), zs)
                k0 = 1.0 / (zc + zc_p)
                k1 = jnp.where(mu > 0, 1.0 / jnp.maximum(zs + zs_p, 1e-30), 0.0)
                dSj, dvj = Sm - Sp, vm - vp
                t0 = dSj[VOIGT[a][a]]
                t1, t2 = dSj[VOIGT[a][a1]], dSj[VOIGT[a][a2]]
                fa = k0 * (t0 + zc_p * sign * dvj[a])
                corr_E = [jnp.zeros_like(fa)] * 6
                corr_E[VOIGT[a][a]] = fa
                corr_E[VOIGT[a][a1]] = 0.5 * k1 * (t1 + zs_p * sign * dvj[a1])
                corr_E[VOIGT[a][a2]] = 0.5 * k1 * (t2 + zs_p * sign * dvj[a2])
                corr_v = [None] * 3
                corr_v[a] = fa * zc * sign
                corr_v[a1] = k1 * zs * (sign * t1 + zs_p * dvj[a1])
                corr_v[a2] = k1 * zs * (sign * t2 + zs_p * dvj[a2])
                corr = -lift[a] * jnp.stack(corr_E + [c / rho for c in corr_v])
                i = -1 if last else 0
                idx = (slice(None),) * (1 + a) + (i,)
                out = out.at[idx].add(corr)
        return out

    return rhs


def make_run(prob: Problem, precision: str = "highest"):
    """jit(run)(q, n): n LSRK4(5) steps of the reference from q, n traced."""
    rhs = make_rhs(prob, precision)
    dt = jnp.float32(prob.dt)
    AB = jnp.asarray(np.stack([RK_A, RK_B], 1), jnp.float32)

    def step(_, carry):
        def stage(c, ab):
            q, res = c
            res = ab[0] * res + dt * rhs(q)
            return (q + ab[1] * res, res), None

        return jax.lax.scan(stage, carry, AB)[0]

    @jax.jit
    def run(q, n):
        return jax.lax.fori_loop(0, n, step, (q, jnp.zeros_like(q)))[0]

    return run
