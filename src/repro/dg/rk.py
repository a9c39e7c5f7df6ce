"""Low-storage RK4(5) (Carpenter & Kennedy) — the paper's rk kernel.

Under a trace (every compiled driver: flat ``dg.solver``, SPMD
``dg.partitioned``, the blocked ``runtime.pipeline``) the stage loop is a
``lax.scan`` over the five (A, B) coefficient pairs, so the stage body is
traced exactly once instead of unrolled five times — inside an outer step
loop the whole time integration compiles to one resident program.
Coefficients live on device in the carry dtype (dtype-stable: a float32
field never promotes through a float64 numpy scalar), keeping the update
arithmetic identical to the historical Python loop up to XLA's FMA
contraction of ``a*res + dt*rhs`` (~1 ulp).

Called EAGERLY (concrete arrays — the calibration/reference paths), the
stages run as the historical Python loop instead: an eager ``lax.scan``
would re-trace and re-lower ``rhs_fn`` on every call (~10x host overhead
per step), and caching a compiled step per callable would silently pin
stale closure state (an engine's block tables change on resplice).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

LSRK_A = np.array([
    0.0,
    -567301805773.0 / 1357537059087.0,
    -2404267990393.0 / 2016746695238.0,
    -3550918686646.0 / 2091501179385.0,
    -1275806237668.0 / 842570457699.0,
])
LSRK_B = np.array([
    1432997174477.0 / 9575080441755.0,
    5161836677717.0 / 13612068292357.0,
    1720146321549.0 / 2090206949498.0,
    3134564353537.0 / 4481467310338.0,
    2277821191437.0 / 14882151754819.0,
])
LSRK_C = np.array([
    0.0,
    1432997174477.0 / 9575080441755.0,
    2526269341429.0 / 6820363962896.0,
    2006345519317.0 / 3224310063776.0,
    2802321613138.0 / 2924317926251.0,
])


# the five (A, B) stage pairs, stacked as the stage scan's xs; cast to the
# carry dtype at use (never cached: a dtype cast is itself a traced op, so a
# memoized device constant would leak tracers across jit scopes)
_LSRK_AB = np.stack([LSRK_A, LSRK_B], axis=1)


def lsrk_coeffs(dtype) -> jnp.ndarray:
    """The (5, 2) stage-coefficient table in ``dtype``, on device."""
    return jnp.asarray(_LSRK_AB, jnp.dtype(dtype))


def lsrk45_step(q, res, rhs_fn, dt):
    """One LSRK4(5) step. res is the low-storage register (same shape as q).

    Scan-compiled under a trace, plain Python loop eagerly (see module
    docstring)."""
    dtype = jnp.result_type(q)
    if not (isinstance(q, jax.core.Tracer) or isinstance(res, jax.core.Tracer)):
        dt = float(dt)  # weak-typed, like the coefficients: dtype-stable
        for s in range(5):
            res = float(LSRK_A[s]) * res + dt * rhs_fn(q)
            q = q + float(LSRK_B[s]) * res
        return q, res
    dt = jnp.asarray(dt, dtype)

    def stage(carry, ab):
        # the stage update; the rhs stages inside it carry their own scopes
        with jax.named_scope("dg.lsrk"):
            q, res = carry
            res = ab[0] * res + dt * rhs_fn(q)
            q = q + ab[1] * res
        return (q, res), None

    (q, res), _ = jax.lax.scan(stage, (q, res), lsrk_coeffs(dtype))
    return q, res
