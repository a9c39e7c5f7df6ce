"""DGSEM operators: volume derivatives, face extraction, exact Riemann flux,
lift — the paper's volume_loop / interp_q / int_flux / lift kernels, in jnp.

Field layout: q (K, 9, M, M, M) with fields
  0..5 = strain E (xx, yy, zz, yz, xz, xy)   [symmetric, 6 stored]
  6..8 = velocity v (x, y, z)
Element axes are (r1, r2, r3) = (x, y, z) on the affine brick.

Flux formulas are the paper's exact Riemann solutions (Rankine-Hugoniot,
Wilcox et al.): with S_j = S^- - S^+, v_j = v^- - v^+, n = s*e_a,
  k0 = 1/(rho^- cp^- + rho^+ cp^+),  k1 = 1/(rho^- cs^- + rho^+ cs^+)
  (k1 = 0 where mu^- = 0, i.e. the acoustic side),
the strain correction has nonzero components only in row/col a, and the
velocity correction couples through rho^- c^-.  Traction boundaries use the
mirror principle [v]=0, [S] = -2(t_bc - S^- n).

These jnp implementations are ALSO the oracles (`ref.py`) for the Pallas
kernels in repro/kernels/.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# strain component index for the (a, b) entry of the symmetric tensor
SYM = np.array([
    [0, 5, 4],
    [5, 1, 3],
    [4, 3, 2],
])
# face ordering (-x,+x,-y,+y,-z,+z)
FACE_AXIS = (0, 0, 1, 1, 2, 2)
FACE_SIGN = (-1.0, 1.0, -1.0, 1.0, -1.0, 1.0)
OPPOSITE = (1, 0, 3, 2, 5, 4)


def deriv(u: jnp.ndarray, D: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Apply the differentiation matrix along element axis (0,1,2) of
    u (K, F, M, M, M) — the paper's IIAX/IAIX/AIIX tensor applications."""
    if axis == 0:
        return jnp.einsum("am,kfmjl->kfajl", D, u)
    if axis == 1:
        return jnp.einsum("am,kfiml->kfial", D, u)
    return jnp.einsum("am,kfijm->kfija", D, u)


def stress(q: jnp.ndarray, lam: jnp.ndarray, mu: jnp.ndarray) -> jnp.ndarray:
    """S (K, 6, M, M, M) from strain fields of q; lam/mu (K,)."""
    E = q[:, :6]
    tr = E[:, 0] + E[:, 1] + E[:, 2]
    lam_ = lam[:, None, None, None]
    mu_ = mu[:, None, None, None]
    Sxx = lam_ * tr + 2 * mu_ * E[:, 0]
    Syy = lam_ * tr + 2 * mu_ * E[:, 1]
    Szz = lam_ * tr + 2 * mu_ * E[:, 2]
    Syz = 2 * mu_ * E[:, 3]
    Sxz = 2 * mu_ * E[:, 4]
    Sxy = 2 * mu_ * E[:, 5]
    return jnp.stack([Sxx, Syy, Szz, Syz, Sxz, Sxy], axis=1)


def volume_rhs(
    q: jnp.ndarray,  # (K, 9, M, M, M)
    D: jnp.ndarray,
    metrics: Tuple[float, float, float],  # 2/h per axis
    rho: jnp.ndarray,
    lam: jnp.ndarray,
    mu: jnp.ndarray,
) -> jnp.ndarray:
    """The paper's volume_loop: dE/dt = sym(grad v); rho dv/dt = div S."""
    v = q[:, 6:9]
    dv = [deriv(v, D, a) * metrics[a] for a in range(3)]  # each (K, 3, M,M,M)
    dE = jnp.stack(
        [
            dv[0][:, 0],
            dv[1][:, 1],
            dv[2][:, 2],
            0.5 * (dv[2][:, 1] + dv[1][:, 2]),
            0.5 * (dv[2][:, 0] + dv[0][:, 2]),
            0.5 * (dv[1][:, 0] + dv[0][:, 1]),
        ],
        axis=1,
    )
    S = stress(q, lam, mu)
    # div S rows: x: Sxx,x + Sxy,y + Sxz,z ; using SYM indexing
    dS = [deriv(S, D, a) * metrics[a] for a in range(3)]
    rho_ = rho[:, None, None, None]
    dvx = (dS[0][:, SYM[0, 0]] + dS[1][:, SYM[0, 1]] + dS[2][:, SYM[0, 2]]) / rho_
    dvy = (dS[0][:, SYM[1, 0]] + dS[1][:, SYM[1, 1]] + dS[2][:, SYM[1, 2]]) / rho_
    dvz = (dS[0][:, SYM[2, 0]] + dS[1][:, SYM[2, 1]] + dS[2][:, SYM[2, 2]]) / rho_
    return jnp.concatenate([dE, jnp.stack([dvx, dvy, dvz], axis=1)], axis=1)


def extract_face(u: jnp.ndarray, face: int) -> jnp.ndarray:
    """interp_q (LGL collocation: a slice). u (K, F, M, M, M) -> (K, F, M, M)."""
    ax = FACE_AXIS[face]
    last = u.shape[2 + ax] - 1
    idx = 0 if FACE_SIGN[face] < 0 else last
    if ax == 0:
        return u[:, :, idx, :, :]
    if ax == 1:
        return u[:, :, :, idx, :]
    return u[:, :, :, :, idx]


def riemann_correction(
    Sm: jnp.ndarray,  # (K, 6, M, M) minus-side stress at face nodes
    vm: jnp.ndarray,  # (K, 3, M, M)
    Sp: jnp.ndarray,
    vp: jnp.ndarray,
    axis: int,
    sign: float,
    mat_m: Dict[str, jnp.ndarray],  # rho, cp, cs, mu — (K,) minus side
    mat_p: Dict[str, jnp.ndarray],
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """n.(F* - F) for strain (K,6,M,M) and velocity (K,3,M,M)."""
    e = lambda x: x[:, None, None]
    k0 = 1.0 / (e(mat_m["rho"] * mat_m["cp"]) + e(mat_p["rho"] * mat_p["cp"]))
    denom_s = e(mat_m["rho"] * mat_m["cs"]) + e(mat_p["rho"] * mat_p["cs"])
    # k1 = 0 where the minus side is acoustic (mu^- = 0)
    k1 = jnp.where(e(mat_m["mu"]) > 0, 1.0 / jnp.maximum(denom_s, 1e-300), 0.0)

    S_j = Sm - Sp  # (K, 6, M, M)
    v_j = vm - vp
    a0, a1, a2 = axis, (axis + 1) % 3, (axis + 2) % 3
    S_aa = S_j[:, SYM[a0, a0]]
    S_a1 = S_j[:, SYM[a0, a1]]
    S_a2 = S_j[:, SYM[a0, a2]]
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


def surface_rhs(
    q: jnp.ndarray,  # (K, 9, M, M, M)
    neighbors: jnp.ndarray,  # (K, 6)
    lift: Tuple[float, float, float],  # metric(a)/w_edge per axis
    rho: jnp.ndarray,
    lam: jnp.ndarray,
    mu: jnp.ndarray,
    cp: jnp.ndarray,
    cs: jnp.ndarray,
    kernel_impl: str = "xla",
) -> jnp.ndarray:
    """int_flux + bound_flux + lift: Riemann corrections on all 6 faces.

    ``kernel_impl`` selects the Riemann-flux body: ``xla`` is the jnp
    reference, ``pallas``/``interpret`` run ``dg_flux_pallas`` (the paper's
    int_flux/godonov_flux hot-spot as a TPU kernel) — one instantiation per
    face direction, exactly the solver's face loop.
    """
    # the flux stage: stress, face traces, neighbour gathers, the Riemann
    # correction (kernel and its relayouts), lift and the face add
    with jax.named_scope("dg.flux"):
        S = stress(q, lam, mu)
        out = jnp.zeros_like(q)
        mats = {"rho": rho, "cp": cp, "cs": cs, "mu": mu}
        for face in range(6):
            ax = FACE_AXIS[face]
            sign = FACE_SIGN[face]
            nbr = neighbors[:, face]
            has_nbr = nbr >= 0
            skip = nbr == -2  # cross-partition face: handled by the halo pass
            nbr_safe = jnp.maximum(nbr, 0)

            Sm = extract_face(S, face)
            vm = extract_face(q[:, 6:9], face)
            Sp_all = extract_face(S, OPPOSITE[face])
            vp_all = extract_face(q[:, 6:9], OPPOSITE[face])
            Sp = Sp_all[nbr_safe]
            vp = vp_all[nbr_safe]
            # physical boundary: traction-free mirror [v]=0, S_j = 2 S^- n
            hn = has_nbr[:, None, None, None]
            Sp = jnp.where(hn, Sp, -Sm)  # S_j = Sm - Sp = 2 Sm
            vp = jnp.where(hn, vp, vm)  # v_j = 0
            mat_m = mats
            mat_p = {k: jnp.where(has_nbr, v[nbr_safe], v) for k, v in mats.items()}

            if kernel_impl == "xla":
                FE, Fv = riemann_correction(Sm, vm, Sp, vp, ax, sign, mat_m, mat_p)
            else:  # pallas | interpret — the flux kernel behind the same switch
                from repro.kernels.dg_flux import dg_flux_pallas

                mats8 = jnp.stack(
                    [mat_m["rho"], mat_m["cp"], mat_m["cs"], mat_m["mu"],
                     mat_p["rho"], mat_p["cp"], mat_p["cs"], mat_p["mu"]],
                    axis=1,
                )
                FE, Fv = dg_flux_pallas(Sm, vm, Sp, vp, mats8, ax, sign,
                                        interpret=_interpret(kernel_impl))
            corr = jnp.concatenate([FE, Fv / rho[:, None, None, None]], axis=1)  # Q^-1 on v rows
            corr = -lift[ax] * corr
            corr = jnp.where(skip[:, None, None, None], 0.0, corr)
            last = q.shape[2 + ax] - 1
            idx = 0 if sign < 0 else last
            if ax == 0:
                out = out.at[:, :, idx, :, :].add(corr)
            elif ax == 1:
                out = out.at[:, :, :, idx, :].add(corr)
            else:
                out = out.at[:, :, :, :, idx].add(corr)
    return out


def _interpret(kernel_impl: str) -> bool:
    """The Pallas ``interpret`` flag a non-xla ``kernel_impl`` names:
    ``pallas`` is the Mosaic-compiled kernel (a TPU, or an error),
    ``interpret`` the Pallas interpreter (the CPU test path)."""
    if kernel_impl not in ("pallas", "interpret"):
        raise ValueError(
            f"kernel_impl must be 'xla', 'pallas' or 'interpret', got {kernel_impl!r}"
        )
    return kernel_impl == "interpret"


def volume_rhs_impl(q, D, metrics, rho, lam, mu, kernel_impl: str = "xla"):
    """``volume_rhs`` behind the kernel switch: ``xla`` is the jnp reference,
    ``pallas``/``interpret`` run the paper's volume_loop as a TPU kernel."""
    with jax.named_scope("dg.volume"):
        if kernel_impl == "xla":
            return volume_rhs(q, D, metrics, rho, lam, mu)
        from repro.kernels.dg_volume import dg_volume_pallas

        return dg_volume_pallas(q, D, metrics, rho, lam, mu,
                                interpret=_interpret(kernel_impl))


def dg_rhs(q, D, metrics, lift, neighbors, rho, lam, mu, cp, cs, kernel_impl: str = "xla"):
    vol = volume_rhs_impl(q, D, metrics, rho, lam, mu, kernel_impl=kernel_impl)
    return vol + surface_rhs(q, neighbors, lift, rho, lam, mu, cp, cs,
                             kernel_impl=kernel_impl)
