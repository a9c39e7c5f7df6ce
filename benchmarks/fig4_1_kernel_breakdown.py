"""Paper Fig 4.1: breakdown of DG execution time by kernel.

Times each kernel of this repo's solver in isolation (jit'd, CPU) on the
paper's configuration family and reports the percentage breakdown next to
the paper's published averages (volume_loop ~40%, int_flux ~25%, ...).

On top of the XLA breakdown, the Pallas hot-spots (``dg_volume_pallas`` /
``dg_flux_pallas``) are timed at their *autotuned* block sizes — the entry
for the current device class from the ``repro.kernels.autotune`` cache
(``--autotune-cache`` / ``$REPRO_AUTOTUNE_CACHE``), falling back to an
inline smoke sweep when no cache is present — and the whole breakdown is
written to ``BENCH_kernels.json`` so the kernel roofline has a tracked
trajectory like BENCH_pipeline/BENCH_serve.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timeit
from repro.dg.operators import face_traces, surface_rhs, volume_rhs
from repro.dg.rk import lsrk45_step
from repro.dg.solver import gaussian_pulse, make_two_tree_solver

PAPER_SHARES = {"volume_loop": 40, "int_flux": 25, "interp_q": 8, "lift+rk": 18, "other": 9}

JSON_PATH = "BENCH_kernels.json"


def _autotune_entry(order: int, smoke: bool, autotune_cache=None):
    """The cache entry for the current device class, else an inline smoke
    sweep (not saved: a benchmark run should not silently overwrite the
    user's tuned cache)."""
    from repro.kernels import autotune as at

    # this suite times the CPU: the kernels run in the Pallas interpreter
    kind = at.detect_device_kind(interpret=True)
    entry = at.lookup(kind, order=order, path=autotune_cache)
    if entry is None:
        # any-order entry for this device class: block-size winners are far
        # more stable across order than across device class
        entry = at.lookup(kind, path=autotune_cache)
    if entry is not None:
        return entry, "cache"
    entry = at.autotune(
        order=order,
        device_kind=kind,
        interpret=True,
        be_candidates=at.DEFAULT_BE_CANDIDATES[:2] if smoke else at.DEFAULT_BE_CANDIDATES,
        bf_candidates=at.DEFAULT_BF_CANDIDATES[:2] if smoke else at.DEFAULT_BF_CANDIDATES,
        reps=1 if smoke else 3,
        size_factor=4 if smoke else 8,
        save=False,
    )
    return entry, "inline-sweep"


def run(grid=(8, 8, 8), order=5, smoke=False, autotune_cache=None):
    if smoke:
        grid, order = (4, 4, 4), 3
    reps = 1 if smoke else 5
    s = make_two_tree_solver(grid=grid, order=order, extent=(2.0, 1.0, 1.0), dtype="float32")
    q = gaussian_pulse(s, center=(0.5, 0.5, 0.5)).astype(jnp.float32)

    vol = jax.jit(lambda q: volume_rhs(q, s.D, s.metrics, s.rho_j, s.lam_j, s.mu_j))
    surf = jax.jit(lambda q: surface_rhs(q, s.neighbors, s.lift, s.rho_j, s.lam_j, s.mu_j, s.cp_j, s.cs_j))
    interp = jax.jit(lambda q: face_traces(q, s.lam_j, s.mu_j))
    rhs = jax.jit(s.rhs)
    rk = jax.jit(lambda q, r: lsrk45_step(q, r, lambda x: x, 1e-3))

    t_vol = timeit(vol, q, reps=reps)
    t_surf = timeit(surf, q, reps=reps)
    t_interp = timeit(interp, q, reps=reps)
    t_rk = timeit(rk, q, jnp.zeros_like(q), reps=reps)
    t_rhs = timeit(rhs, q, reps=reps)

    total = t_vol + t_surf + t_interp + t_rk
    emit("fig4_1/volume_loop", t_vol * 1e6, f"{100*t_vol/total:.0f}% (paper ~40%)")
    emit("fig4_1/int_flux+lift", t_surf * 1e6, f"{100*t_surf/total:.0f}% (paper ~33%)")
    emit("fig4_1/interp_q", t_interp * 1e6, f"{100*t_interp/total:.0f}% (paper ~8%)")
    emit("fig4_1/rk", t_rk * 1e6, f"{100*t_rk/total:.0f}% (paper ~10%)")
    emit("fig4_1/full_rhs", t_rhs * 1e6, f"K={s.mesh.K} order={order}")

    # -- the Pallas hot-spots at their autotuned block sizes ----------------
    from repro.dg.basis import diff_matrix, lgl_nodes_weights
    from repro.kernels.dg_flux import dg_flux_pallas
    from repro.kernels.dg_volume import dg_volume_pallas

    entry, source = _autotune_entry(order, smoke, autotune_cache)
    be, bf = int(entry["be"]), int(entry["bf"])
    interpret = bool(entry["interpret"])
    K = s.mesh.K
    M = order + 1
    x, _ = lgl_nodes_weights(order)
    D = jnp.asarray(diff_matrix(x), jnp.float32)
    rng = np.random.default_rng(0)
    qk = jnp.asarray(rng.standard_normal((K, 9, M, M, M)), jnp.float32)
    ones = jnp.ones(K, jnp.float32)
    pv = jax.jit(lambda q: dg_volume_pallas(
        q, D, (2.0, 2.0, 2.0), ones, ones, jnp.zeros(K, jnp.float32),
        interpret=interpret, be=be))
    F = K * 6  # six faces per element row
    tm = jnp.asarray(rng.standard_normal((6, 6, M * M, K)), jnp.float32)
    tp = jnp.asarray(rng.standard_normal((6, 6, M * M, K)), jnp.float32)
    mat = jnp.asarray(np.abs(rng.standard_normal((6, 10, K))) + 0.5, jnp.float32)
    pf = jax.jit(lambda *a: dg_flux_pallas(*a, (1.0, 1.0, 1.0), interpret=interpret, bf=bf))
    t_pv = timeit(pv, qk, reps=reps)
    t_pf = timeit(pf, tm, tp, mat, reps=reps)
    emit("fig4_1/pallas_volume", t_pv * 1e6,
         f"BE={be} ({source}) {t_pv/K*1e9:.1f}ns/elem")
    emit("fig4_1/pallas_flux", t_pf * 1e6,
         f"BF={bf} ({source}) {t_pf/F*1e9:.1f}ns/face")

    result = {
        "config": {"grid": list(grid), "order": order, "K": int(K),
                   "smoke": bool(smoke)},
        "autotune": {
            "source": source,
            "device_kind": entry["device_kind"],
            "be": be,
            "bf": bf,
            "sec_per_element": entry["sec_per_element"],
            "launch_overhead_s": entry["launch_overhead_s"],
        },
        "seconds": {
            "volume_loop": t_vol,
            "int_flux_lift": t_surf,
            "interp_q": t_interp,
            "rk": t_rk,
            "full_rhs": t_rhs,
            "pallas_volume": t_pv,
            "pallas_flux": t_pf,
        },
        "shares_vs_paper": {
            "volume_loop": [100 * t_vol / total, PAPER_SHARES["volume_loop"]],
            "int_flux+lift": [100 * t_surf / total,
                              PAPER_SHARES["int_flux"] + PAPER_SHARES["lift+rk"] - 10],
            "interp_q": [100 * t_interp / total, PAPER_SHARES["interp_q"]],
        },
    }
    with open(JSON_PATH, "w") as f:
        json.dump(result, f, indent=2)
    emit("fig4_1/json", 0.0, JSON_PATH)
    return result


if __name__ == "__main__":
    run()
