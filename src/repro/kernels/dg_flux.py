"""Pallas TPU kernel for the paper's ``int_flux`` / ``godonov_flux`` hot-spot.

The exact Riemann correction is embarrassingly parallel over face nodes
(paper section 4) — pure VPU work.  The TPU layout puts faces on lanes: the
wrapper transposes each ``(F, C, M, M)`` trace array to ``(C, M*M, F)``, so
one grid step holds a ``(C, M*M, BF)`` block — the field index is a leading
(untiled) axis, each field a lane-dense ``(M*M, BF)`` tile — and the 8
material scalars ride along as an ``(8, BF)`` block broadcast over
sublanes.  The strain/velocity corrections are assembled by stacking their
components, never by scatter.  Axis/sign are compile-time parameters (one
kernel instantiation per face direction, as in the solver's face loop).

VMEM per step at order 7, BF = 128, f32: (2 x 9 inputs + 9 outputs) x 64 x
128 x 4 B = 0.9 MiB, x2 buffers.

Validated against ``ref.dg_flux_ref`` in interpret mode across orders,
dtypes, and acoustic/elastic/coupled material draws;
``tests/test_tpu_compile.py`` compiles it for a v5e.

Reached from the solver via the ``kernel_impl`` switch
(``dg.operators.surface_rhs(kernel_impl="pallas"|"interpret")``): one
instantiation per face direction inside the solver's face loop, on the flat
rhs, the SPMD slab interior, the blocked engine's correction phase, and the
fused step pipeline (``runtime.pipeline``) alike.

BF = 128 is the hand-derived default; ``repro.kernels.autotune`` sweeps it
per device class and installs the measured winner via ``set_block_faces``
(or per call via ``dg_flux_pallas(..., bf=...)``).  The kernel is pure
per-face VPU work, so results are bitwise-invariant in BF.  Faces are the
lane axis, so on a TPU BF must be a multiple of 128.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BF = 128  # default faces per grid step (one lane width)

# autotuned override (repro.kernels.autotune.activate): None = use BF.
# Baked into programs at trace time — activate BEFORE building pipelines.
_ACTIVE_BF: Optional[int] = None


def set_block_faces(bf: Optional[int]) -> None:
    """Install an autotuned faces-per-grid-step block size (None resets to
    the default ``BF``).  Affects subsequent traces only."""
    global _ACTIVE_BF
    _ACTIVE_BF = None if bf is None else int(bf)


def block_faces() -> int:
    """The BF the next ``dg_flux_pallas`` trace will use."""
    return BF if _ACTIVE_BF is None else _ACTIVE_BF

# SYM[a][b]: 6-component slot of the symmetric (a,b) entry
SYM = ((0, 5, 4), (5, 1, 3), (4, 3, 2))


def _flux_kernel(Sm_ref, vm_ref, Sp_ref, vp_ref, mat_ref, FE_ref, Fv_ref, *, axis: int, sign: float):
    """S*_ref: (6, MM, BF); v*_ref: (3, MM, BF); mat_ref: (8, BF);
    FE_ref: (6, MM, BF); Fv_ref: (3, MM, BF)."""
    # compute in >= f32 (bf16 inputs upcast; f64 kept when x64 is on)
    cdt = jnp.result_type(Sm_ref.dtype, jnp.float32)
    S_j = lambda c: Sm_ref[c].astype(cdt) - Sp_ref[c].astype(cdt)  # (MM, BF)
    v_j = lambda c: vm_ref[c].astype(cdt) - vp_ref[c].astype(cdt)
    mat = mat_ref[...].astype(cdt)  # (8, BF)

    e = lambda c: mat[c:c + 1]  # (1, BF), broadcast over the face nodes
    rcp_m, rcs_m = e(0) * e(1), e(0) * e(2)
    rcp_p, rcs_p = e(4) * e(5), e(4) * e(6)
    mu_m = e(3)
    k0 = 1.0 / (rcp_m + rcp_p)
    denom = rcs_m + rcs_p
    k1 = jnp.where(mu_m > 0, 1.0 / jnp.maximum(denom, 1e-30), 0.0)

    a0, a1, a2 = axis, (axis + 1) % 3, (axis + 2) % 3
    S_aa = S_j(SYM[a0][a0])
    S_a1 = S_j(SYM[a0][a1])
    S_a2 = S_j(SYM[a0][a2])
    v0, v1, v2 = v_j(a0), v_j(a1), v_j(a2)

    a = k0 * (S_aa + rcp_p * sign * v0)
    zero = jnp.zeros_like(a)
    FE = [zero] * 6
    FE[SYM[a0][a0]] = a
    FE[SYM[a0][a1]] = 0.5 * k1 * (S_a1 + rcs_p * sign * v1)
    FE[SYM[a0][a2]] = 0.5 * k1 * (S_a2 + rcs_p * sign * v2)

    Fv = [zero] * 3
    Fv[a0] = a * rcp_m * sign
    Fv[a1] = k1 * rcs_m * (sign * S_a1 + rcs_p * v1)
    Fv[a2] = k1 * rcs_m * (sign * S_a2 + rcs_p * v2)

    FE_ref[...] = jnp.stack(FE).astype(FE_ref.dtype)
    Fv_ref[...] = jnp.stack(Fv).astype(Fv_ref.dtype)


def dg_flux_pallas(
    Sm: jnp.ndarray,  # (F, 6, M, M)
    vm: jnp.ndarray,  # (F, 3, M, M)
    Sp: jnp.ndarray,
    vp: jnp.ndarray,
    mats: jnp.ndarray,  # (F, 8): rho-,cp-,cs-,mu-,rho+,cp+,cs+,mu+
    axis: int,
    sign: float,
    *,
    interpret: bool,
    bf: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Riemann corrections ``(FE (F, 6, M, M), Fv (F, 3, M, M))``.
    ``interpret=False`` compiles the kernel with Mosaic (TPU only);
    ``interpret=True`` runs it in the Pallas interpreter (the CPU test
    path).  There is no default."""
    BF = block_faces() if bf is None else int(bf)
    F, _, M, _ = Sm.shape
    MM = M * M
    pad = (-F) % BF
    Fp = F + pad

    def faces_last(x, fill=0.0):
        """(F, C, ...) -> (C, prod(...), Fp): faces padded onto lanes."""
        if pad:
            x = jnp.concatenate([x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)])
        return x.reshape(Fp, x.shape[1], -1).transpose(1, 2, 0)

    spec = lambda C: pl.BlockSpec((C, MM, BF), lambda i: (0, 0, i))
    FE, Fv = pl.pallas_call(
        functools.partial(_flux_kernel, axis=axis, sign=float(sign)),
        grid=(Fp // BF,),
        in_specs=[spec(6), spec(3), spec(6), spec(3), pl.BlockSpec((8, BF), lambda i: (0, i))],
        out_specs=[spec(6), spec(3)],
        out_shape=[
            jax.ShapeDtypeStruct((6, MM, Fp), Sm.dtype),
            jax.ShapeDtypeStruct((3, MM, Fp), Sm.dtype),
        ],
        interpret=interpret,
        name="dg_flux",
    )(faces_last(Sm), faces_last(vm), faces_last(Sp), faces_last(vp),
      faces_last(mats[:, :, None], fill=1.0)[:, 0])

    def faces_first(x):
        return x.transpose(2, 0, 1)[:F].reshape(F, x.shape[0], M, M)

    return faces_first(FE), faces_first(Fv)
