"""Pallas TPU kernel for the paper's ``int_flux`` / ``godonov_flux`` hot-spot.

The exact Riemann correction is embarrassingly parallel over face nodes
(paper section 4) — pure VPU work.  Its operands arrive in the flux stage's
own lane-dense layout (``dg.operators.surface_rhs``): face traces
``(6, 6, M*M, R)`` — face direction, field in the face's own frame
(traction, then velocity: ``dg.operators.face_fields``), face node, element
row — with the rows on lanes, so the wrapper relayouts nothing.  One grid
step holds a ``(6, M*M, BF)`` block of one face direction: the field index
a leading (untiled) axis, each field a lane-dense ``(M*M, BF)`` tile, and
the face's material and flag rows ride along as a ``(10, BF)`` block
broadcast over sublanes.  The grid runs over (face direction, row block) in
one call; each face direction has its own branch, with its sign and lift
scale as compile-time constants.  The body is
``dg.operators.riemann_correction``: the mirror at physical boundaries, the
jumps, the correction, ``1/rho`` on the velocity rows, the lift scale, and
zero on skip faces — the kernel's output is the stage's final per-face
correction.

VMEM per step at order 7, BF = 128, f32: (2 inputs + 1 output) x 6 x 64 x
128 x 4 B = 0.56 MiB, x2 buffers.

Validated against ``ref.dg_flux_ref`` in interpret mode across orders and
dtypes, acoustic/elastic/coupled material draws, boundary and skip faces;
``tests/test_tpu_compile.py`` compiles it for a v5e.

Reached from the solver via the ``kernel_impl`` switch
(``dg.operators.surface_rhs(kernel_impl="pallas"|"interpret")``) on the
flat rhs, the SPMD slab interior, the blocked engine's correction phase,
and the fused step pipeline (``runtime.pipeline``) alike.

BF = 128 is the hand-derived default; ``repro.kernels.autotune`` sweeps it
per device class and installs the measured winner via ``set_block_faces``
(or per call via ``dg_flux_pallas(..., bf=...)``).  The kernel is pure
per-node VPU work, so results are bitwise-invariant in BF.  Rows are the
lane axis, so on a TPU BF must be a multiple of 128; a last partial block
is masked by the grid.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.dg.operators import FACE_AXIS, FACE_SIGN, riemann_correction

BF = 128  # default element rows per grid step (one lane width)

# autotuned override (repro.kernels.autotune.activate): None = use BF.
# Baked into programs at trace time — activate BEFORE building pipelines.
_ACTIVE_BF: Optional[int] = None


def set_block_faces(bf: Optional[int]) -> None:
    """Install an autotuned rows-per-grid-step block size (None resets to
    the default ``BF``).  Affects subsequent traces only."""
    global _ACTIVE_BF
    _ACTIVE_BF = None if bf is None else int(bf)


def block_faces() -> int:
    """The BF the next ``dg_flux_pallas`` trace will use."""
    return BF if _ACTIVE_BF is None else _ACTIVE_BF


def _flux_kernel(tm_ref, tp_ref, mat_ref, out_ref, *, scale):
    """tm_ref, tp_ref, out_ref: (1, 6, MM, BF); mat_ref: (1, 10, BF)."""
    face = pl.program_id(0)
    for f in range(6):

        @pl.when(face == f)
        def _(f=f):
            corr = riemann_correction(tm_ref[0], tp_ref[0], mat_ref[0], FACE_SIGN[f],
                                      scale[FACE_AXIS[f]])
            out_ref[0] = corr.astype(out_ref.dtype)


def dg_flux_pallas(
    tm: jnp.ndarray,  # (6, 6, MM, R) minus-side traces: traction, velocity
    tp: jnp.ndarray,  # (6, 6, MM, R) plus side: the neighbours' opposite faces
    mat: jnp.ndarray,  # (6, 10, R) per face, rows as dg.operators.HAS/KEEP
    scale: Tuple[float, float, float],  # the lift scale per axis
    *,
    interpret: bool,
    bf: Optional[int] = None,
) -> jnp.ndarray:
    """The six faces' lifted Riemann corrections ``(6, 6, MM, R)``.
    ``interpret=False`` compiles the kernel with Mosaic (TPU only);
    ``interpret=True`` runs it in the Pallas interpreter (the CPU test
    path).  There is no default."""
    BF = block_faces() if bf is None else int(bf)
    _, C, MM, R = tm.shape
    spec = pl.BlockSpec((1, C, MM, BF), lambda f, i: (f, 0, 0, i))
    return pl.pallas_call(
        functools.partial(_flux_kernel, scale=tuple(float(s) for s in scale)),
        grid=(6, pl.cdiv(R, BF)),
        in_specs=[spec, spec, pl.BlockSpec((1, mat.shape[1], BF), lambda f, i: (f, 0, i))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(tm.shape, tm.dtype),
        interpret=interpret,
        name="dg_flux",
    )(tm, tp, mat)
