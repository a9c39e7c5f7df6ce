"""Pallas TPU kernel for the paper's ``volume_loop`` hot-spot.

The paper hand-vectorizes the elemental tensor-product applications
(IIAX/IAIX/AIIX) with AVX/MIC intrinsics.  The TPU adaptation rethinks the
layout for Mosaic instead of porting the vector code:

  * each element's ``M^3`` nodes (512 at order 7) are the lane axis, so the
    field array is viewed as ``(K, 9 * M^3)`` — a free reshape of the
    ``(K, 9, M, M, M)`` state — and one grid step holds a ``(BE, 9 * M^3)``
    block: BE elements on sublanes, field ``f`` at the lane-aligned slice
    ``[f * M^3, (f + 1) * M^3)``;
  * the derivative along element axis ``a`` is then ONE 2-D dot with a
    precomputed ``(M^3, M^3)`` operator, ``kron(D, I, I)^T`` and its two
    permutations (the metric ``2/h`` folded in) — no transposes or reshapes
    across tiled dimensions inside the kernel.  The six fields each axis
    needs (three velocities, one stress row) are stacked on sublanes so
    each axis is a single ``(6 * BE, M^3) x (M^3, M^3)`` MXU pass;
  * stress, the sym-grad combinations and the 1/rho scaling run on the VPU
    in the same kernel, so the block's rhs leaves VMEM exactly once.

The dense operator does ``M^3 / M`` times the multiply-adds of the
tensor-product form; which layout is fastest on the chip is not measured.

VMEM per grid step at order 7, BE = 16, f32: the operators 3 MiB (x2
buffers), q and out blocks 288 KiB each (x2 buffers), the stacked dot
operands ~0.6 MiB — ~8.5 MiB, under the 16 MiB default scoped limit.

Validated against ``ref.dg_volume_ref`` in interpret mode (CPU) across
orders/dtypes; ``tests/test_tpu_compile.py`` compiles it for a v5e.

BE = 16 is the hand-derived default; ``repro.kernels.autotune`` sweeps it
per device class and installs the measured winner via ``set_block_elems``
(or per call via ``dg_volume_pallas(..., be=...)``).  The kernel is
block-diagonal per element, so results are bitwise-invariant in BE.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

BE = 16  # default elements per grid step (a multiple of the 8 sublanes)

# autotuned override (repro.kernels.autotune.activate): None = use BE.
# Baked into programs at trace time — activate BEFORE building pipelines.
_ACTIVE_BE: Optional[int] = None

# per axis a, the stress components whose a-derivative enters div S rows
# x, y, z: SYM[r][a] for r = 0, 1, 2
_DIV_S = ((0, 5, 4), (5, 1, 3), (4, 3, 2))


def set_block_elems(be: Optional[int]) -> None:
    """Install an autotuned elements-per-grid-step block size (None resets
    to the default ``BE``).  Affects subsequent traces only."""
    global _ACTIVE_BE
    _ACTIVE_BE = None if be is None else int(be)


def block_elems() -> int:
    """The BE the next ``dg_volume_pallas`` trace will use."""
    return BE if _ACTIVE_BE is None else _ACTIVE_BE


def _build_operators(xp, D, metrics):
    M = D.shape[0]
    eye = xp.eye(M, dtype=D.dtype)
    kron3 = lambda a, b, c: xp.kron(xp.kron(a, b), c)
    ops = (kron3(D, eye, eye), kron3(eye, D, eye), kron3(eye, eye, D))
    return xp.stack([(op.T * m).astype(D.dtype) for op, m in zip(ops, metrics)])


@functools.lru_cache(maxsize=16)
def _host_operators(D_bytes: bytes, M: int, dtype: str, metrics) -> np.ndarray:
    D = np.frombuffer(D_bytes, dtype=dtype).reshape(M, M)
    return _build_operators(np, D, metrics)


def derivative_operators(D, metrics: Tuple[float, float, float], dtype=None):
    """(3, M^3, M^3): right-multiplying a row of element nodes by entry
    ``a`` differentiates along element axis ``a`` and scales by
    ``metrics[a]``.

    A concrete ``D`` (the solver's) gives a host array built once per
    (D, metrics, dtype) and cached: inside a trace it is a constant of the
    program, so no step evaluates the Kronecker products.  A traced ``D``
    builds them with jnp inside the trace.  ``dtype`` defaults to D's."""
    metrics = tuple(float(m) for m in metrics)
    dtype = D.dtype if dtype is None else dtype
    if isinstance(D, jax.core.Tracer):
        return _build_operators(jnp, D.astype(dtype), metrics)
    D = np.asarray(D, dtype)
    return _host_operators(D.tobytes(), D.shape[0], D.dtype.str, metrics)


def _volume_kernel(q_ref, ops_ref, mat_ref, out_ref, *, N: int, BE: int):
    """q_ref/out_ref: (BE, 9 * N) with N = M^3 nodes per field on lanes;
    ops_ref: (3, N, N); mat_ref: (BE, 3) = (rho, lam, mu)."""
    cdt = jnp.result_type(q_ref.dtype, jnp.float32)
    field = lambda f: q_ref[:, f * N:(f + 1) * N].astype(cdt)  # (BE, N)
    mat = mat_ref[...].astype(cdt)
    rho, lam, mu = mat[:, 0:1], mat[:, 1:2], mat[:, 2:3]

    E = [field(f) for f in range(6)]
    v = [field(6 + f) for f in range(3)]
    tr = E[0] + E[1] + E[2]
    S = [lam * tr + 2 * mu * E[c] for c in range(3)] + [2 * mu * E[c] for c in range(3, 6)]

    def d(a):
        """a-derivatives of (v0, v1, v2, the three stress components of
        div S along a), as one MXU pass over the sublane-stacked fields."""
        x = jnp.concatenate(v + [S[c] for c in _DIV_S[a]], axis=0)  # (6 BE, N)
        y = jax.lax.dot_general(x, ops_ref[a].astype(cdt), (((1,), (0,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=cdt)
        return [y[i * BE:(i + 1) * BE] for i in range(6)]

    d0, d1, d2 = d(0), d(1), d(2)
    out = [
        d0[0],
        d1[1],
        d2[2],
        0.5 * (d2[1] + d1[2]),
        0.5 * (d2[0] + d0[2]),
        0.5 * (d1[0] + d0[1]),
    ] + [(d0[3 + r] + d1[3 + r] + d2[3 + r]) / rho for r in range(3)]
    for f, o in enumerate(out):
        out_ref[:, f * N:(f + 1) * N] = o.astype(out_ref.dtype)


def dg_volume_pallas(
    q: jnp.ndarray,  # (K, 9, M, M, M)
    D: jnp.ndarray,  # (M, M)
    metrics: Tuple[float, float, float],
    rho: jnp.ndarray,
    lam: jnp.ndarray,
    mu: jnp.ndarray,
    *,
    interpret: bool,
    be: Optional[int] = None,
) -> jnp.ndarray:
    """Volume rhs (K, 9, M, M, M).  ``interpret=False`` compiles the kernel
    with Mosaic (TPU only); ``interpret=True`` runs it in the Pallas
    interpreter (the CPU test path).  There is no default."""
    BE = block_elems() if be is None else int(be)
    K, F, M = q.shape[0], q.shape[1], q.shape[2]
    N = M * M * M
    pad = (-K) % BE
    if pad:
        q = jnp.concatenate([q, jnp.zeros((pad,) + q.shape[1:], q.dtype)])
        rho = jnp.concatenate([rho, jnp.ones(pad, rho.dtype)])
        lam = jnp.concatenate([lam, jnp.ones(pad, lam.dtype)])
        mu = jnp.concatenate([mu, jnp.ones(pad, mu.dtype)])
    Kp = K + pad
    ops = derivative_operators(D, metrics, q.dtype)
    mats = jnp.stack([rho, lam, mu], axis=1).astype(q.dtype)

    out = pl.pallas_call(
        functools.partial(_volume_kernel, N=N, BE=BE),
        grid=(Kp // BE,),
        in_specs=[
            pl.BlockSpec((BE, F * N), lambda i: (i, 0)),
            pl.BlockSpec((3, N, N), lambda i: (0, 0, 0)),
            pl.BlockSpec((BE, 3), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BE, F * N), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Kp, F * N), q.dtype),
        interpret=interpret,
        name="dg_volume",
    )(q.reshape(Kp, F * N), ops, mats)
    out = out.reshape(Kp, F, M, M, M)
    return out[:K] if pad else out
