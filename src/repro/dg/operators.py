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

from typing import Tuple

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


def stress_components(E, lam, mu):
    """The six stress components ``(Sxx, Syy, Szz, Syz, Sxz, Sxy)`` from the
    six strain components ``E``; ``lam``/``mu`` broadcast against them."""
    tr = E[0] + E[1] + E[2]
    Sxx = lam * tr + 2 * mu * E[0]
    Syy = lam * tr + 2 * mu * E[1]
    Szz = lam * tr + 2 * mu * E[2]
    Syz = 2 * mu * E[3]
    Sxz = 2 * mu * E[4]
    Sxy = 2 * mu * E[5]
    return [Sxx, Syy, Szz, Syz, Sxz, Sxy]


def stress(q: jnp.ndarray, lam: jnp.ndarray, mu: jnp.ndarray) -> jnp.ndarray:
    """S (K, 6, M, M, M) from strain fields of q; lam/mu (K,)."""
    e = lambda x: x[:, None, None, None]
    return jnp.stack(stress_components([q[:, c] for c in range(6)], e(lam), e(mu)), axis=1)


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


# rows of the per-face material table ``mat`` (10, R) of ``riemann_correction``:
# minus side rho, cp, cs, mu; plus side rho, cp, cs, mu; HAS (1 where the face
# has a neighbour, 0 at a physical boundary); KEEP (0 on a skip face)
HAS, KEEP = 8, 9


def face_fields(face: int) -> Tuple[int, ...]:
    """The six fields the flux of a face direction reads and corrects, in
    its own frame (a, a+1, a+2 mod 3 of its axis a): the traction
    components S_aa, S_a(a+1), S_a(a+2) as stress/strain slots, then the
    velocity components v_a, v_(a+1), v_(a+2) as field indices."""
    a0 = FACE_AXIS[face]
    a1, a2 = (a0 + 1) % 3, (a0 + 2) % 3
    return (int(SYM[a0, a0]), int(SYM[a0, a1]), int(SYM[a0, a2]), 6 + a0, 6 + a1, 6 + a2)


def face_traces(q: jnp.ndarray, lam: jnp.ndarray, mu: jnp.ndarray) -> jnp.ndarray:
    """interp_q (LGL collocation: a slice) for all six faces at once, in the
    flux stage's lane-dense layout: q (R, 9, M, M, M) -> (6, 6, M*M, R),
    faces in FACE_AXIS order, element rows on the minor axis.  Each face
    holds the fields of ``face_fields``: the traction of the stress
    (computed on the traces: it is per node) and the velocity; the other
    three stress components never enter that face's flux.  Face nodes keep
    the volume's order of the two other axes.

    q is read through its (R, 9*M^3) row view, turned rows-minor: where q is
    laid out rows-minor already (the loop-carried state, the sharded slab)
    that is free, and it lets XLA give a gathered q the same layout instead
    of a field-minor one whose tiles are 9 lanes of 128 wide."""
    R, M = q.shape[0], q.shape[2]
    qt = q.reshape(R, -1).T.reshape(9, M, M, M, R)
    faces = [qt[:, 0], qt[:, M - 1], qt[:, :, 0], qt[:, :, M - 1],
             qt[:, :, :, 0], qt[:, :, :, M - 1]]
    t = jnp.stack(faces).reshape(6, 9, M * M, R)
    fields = stress_components([t[:, c] for c in range(6)], lam, mu)
    fields += [t[:, c] for c in range(6, 9)]  # each (6, M*M, R)
    return jnp.stack([jnp.stack([fields[c][f] for c in face_fields(f)]) for f in range(6)])


def neighbour_traces(tm: jnp.ndarray, nbr: jnp.ndarray) -> jnp.ndarray:
    """The plus side of every face: face f of row r takes the opposite face
    of row ``nbr[r, f]`` (same axis, so the same fields); (6, 6, M*M, R)
    like ``tm``.  A TPU gathers rows, not lanes, so the traces are gathered
    as rows of 6*M*M (one gather per face direction) between two
    transposes."""
    rows = tm.reshape(6, -1, tm.shape[-1]).transpose(2, 0, 1)  # (R, 6, 6*M*M)
    tp = jnp.stack([rows[nbr[:, f], OPPOSITE[f]] for f in range(6)])  # (6, R, 6*M*M)
    return tp.transpose(0, 2, 1).reshape(tm.shape)


def riemann_correction(
    tm: jnp.ndarray,  # (..., 6, M*M, N) minus-side traction and velocity at face nodes
    tp: jnp.ndarray,  # (..., 6, M*M, N) plus side (the neighbour's opposite face)
    mat: jnp.ndarray,  # (..., 10, N) rows as HAS/KEEP above
    sign,  # the face normal's sign: a float, or (..., 1, 1) per face
    scale,  # the lift scale: a float, or (..., 1, 1) per face
) -> jnp.ndarray:
    """The lifted correction ``scale * n.(F* - F)`` in each face's own frame,
    (..., 6, M*M, N): the strain rows of ``face_fields``, then its velocity
    rows over rho^-.  Material rows broadcast over the face nodes.  At a
    physical boundary (HAS 0) the plus side is the traction-free mirror
    [v]=0, S_j = 2 S^- n; a skip face (KEEP 0) gets no correction.  Written
    once for both bodies: the ``xla`` path applies it to all six faces at
    once, the Pallas kernel to one face direction's blocks."""
    e = lambda c: mat[..., c:c + 1, :]  # (..., 1, N), broadcast over the face nodes
    fld = lambda x, c: x[..., c, :, :]
    has = e(HAS) > 0
    rcp_m, rcs_m = e(0) * e(1), e(0) * e(2)
    rcp_p, rcs_p = e(4) * e(5), e(4) * e(6)
    k0 = 1.0 / (rcp_m + rcp_p)
    # k1 = 0 where the minus side is acoustic (mu^- = 0)
    k1 = jnp.where(e(3) > 0, 1.0 / jnp.maximum(rcs_m + rcs_p, 1e-30), 0.0)

    # the jumps; at a boundary S_j = 2 S^- n and v_j = 0
    S0, S1, S2 = (fld(tm, c) - jnp.where(has, fld(tp, c), -fld(tm, c)) for c in range(3))
    v0, v1, v2 = (fld(tm, c) - jnp.where(has, fld(tp, c), fld(tm, c)) for c in range(3, 6))

    a = k0 * (S0 + rcp_p * sign * v0)
    FE = [a,
          0.5 * k1 * (S1 + rcs_p * sign * v1),
          0.5 * k1 * (S2 + rcs_p * sign * v2)]
    Fv = [a * rcp_m * sign,
          k1 * rcs_m * (sign * S1 + rcs_p * v1),
          k1 * rcs_m * (sign * S2 + rcs_p * v2)]
    # Q^-1 (1/rho^-) on the velocity rows
    corr = jnp.stack([scale * x for x in FE + [v / e(0) for v in Fv]], axis=-3)
    return jnp.where(e(KEEP)[..., None, :, :] > 0, corr, 0.0)


def face_corrections(tm, tp, mat, scale) -> jnp.ndarray:
    """``riemann_correction`` of all six faces, (6, 6, M*M, R): the ``xla``
    body of the flux stage and the oracle of ``dg_flux_pallas``."""
    per_face = lambda v: jnp.asarray(v, tm.dtype)[:, None, None]
    return riemann_correction(tm, tp, mat, per_face(FACE_SIGN),
                              per_face([scale[a] for a in FACE_AXIS]))


def lift_faces(corr: jnp.ndarray, M: int) -> jnp.ndarray:
    """lift: the six faces' corrections (6, 6, M*M, R) written into the
    volume layout (R, 9, M, M, M) in one pass, each face selected by an iota
    mask on its element axis.  Each field adds the faces that correct it in
    FACE_AXIS order; the per-face accumulation into zeros added exact zeros
    for the rest, so every node sees the same additions."""
    R = corr.shape[-1]
    c = corr.reshape(6, 6, M, M, R)
    node = lambda a: jax.lax.broadcasted_iota(jnp.int32, (M, M, M, 1), a)
    terms = [[] for _ in range(9)]
    for face in range(6):
        ax = FACE_AXIS[face]
        at = node(ax) == (0 if FACE_SIGN[face] < 0 else M - 1)
        for row, fld in enumerate(face_fields(face)):
            terms[fld].append(jnp.where(at, jnp.expand_dims(c[face, row], ax), 0.0))
    out = jnp.stack([sum(t[1:], t[0]) for t in terms])  # (9, M, M, M, R)
    return jnp.moveaxis(out, -1, 0)


def surface_rhs(
    q: jnp.ndarray,  # (R, 9, M, M, M)
    neighbors: jnp.ndarray,  # (R, 6)
    lift: Tuple[float, float, float],  # metric(a)/w_edge per axis
    rho: jnp.ndarray,
    lam: jnp.ndarray,
    mu: jnp.ndarray,
    cp: jnp.ndarray,
    cs: jnp.ndarray,
    kernel_impl: str = "xla",
) -> jnp.ndarray:
    """int_flux + bound_flux + lift: Riemann corrections on all 6 faces.

    The stage works on face traces in one lane-dense layout, (6, 6, M*M, R)
    with element rows minor and each face's traction and velocity in its
    own frame (``face_fields``): one pass reads q into the traces
    (``face_traces``), one gather per face direction takes the neighbours'
    opposite traces by ``neighbors`` (``neighbour_traces``; -1: physical
    boundary, mirrored; -2: cross-partition face, no correction), the
    Riemann correction runs on all six faces, and one pass writes the
    output (``lift_faces``).  ``kernel_impl`` selects the correction's
    body: ``xla`` is the jnp ``riemann_correction``, ``pallas``/``interpret``
    run ``dg_flux_pallas`` (the paper's int_flux/godonov_flux hot-spot as a
    TPU kernel), one call over all six faces.
    """
    with jax.named_scope("dg.flux"):
        M = q.shape[2]
        tm = face_traces(q, lam, mu)
        has = neighbors >= 0
        nbr = jnp.maximum(neighbors, 0)
        tp = neighbour_traces(tm, nbr)
        own = jnp.stack([rho, cp, cs, mu])  # (4, R)
        mat = jnp.stack([
            jnp.concatenate([own, jnp.where(has[:, f], own[:, nbr[:, f]], own),
                             has[None, :, f].astype(own.dtype),
                             (neighbors[None, :, f] != -2).astype(own.dtype)])
            for f in range(6)])  # (6, 10, R)
        scale = tuple(-float(x) for x in lift)  # weak-typed: the state's dtype
        if kernel_impl == "xla":
            corr = face_corrections(tm, tp, mat, scale)
        else:  # pallas | interpret — the flux kernel behind the same switch
            from repro.kernels.dg_flux import dg_flux_pallas

            corr = dg_flux_pallas(tm, tp, mat, scale, interpret=_interpret(kernel_impl))
        return lift_faces(corr, M)


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
