"""Fused scan-compiled pipeline vs the Python-loop blocked driver.

Measures steps/sec and host dispatches/step for the blocked DG engine's two
drivers on the same engine and split:

* **unfused** — the historical Python-loop driver: 5 LSRK stages x P blocks
  x ~6 separate device calls per RHS evaluation, a fresh ``(K+1, ...)``
  scatter target per call, stage arithmetic dispatched eagerly;
* **fused** — ``runtime.pipeline.FusedStepPipeline``: the whole time loop
  as ONE donated program (``lax.scan`` over steps, scan over stages,
  same-bucket blocks batched into one launch per bucket);
* **observe** — the same fused driver with the in-scan observation channel
  on (``run(observe=True)``): one ``run_observed`` dispatch per rebalance
  chunk, the executor fed a wall-attributed ``CalibrationReport`` per
  chunk.  The row's ``overhead_vs_fused`` tracks what continuous
  calibration costs; ``dispatches_per_step`` is measured on the
  ``DispatchStats`` ledger and CI gates it at exactly one dispatch per
  chunk.

With ``--devices N`` (and N visible devices) a third row measures the
**sharded** fused driver — ``runtime.pipeline.ShardedStepPipeline``, the
SPMD slab path's whole time loop as ONE donated ``shard_map`` program with
the ring ``ppermute`` halo exchange inside the compiled step loop.

Emits the usual CSV rows plus ``BENCH_pipeline.json`` (uploaded as a CI
artifact) so the fused-vs-unfused throughput ratio is tracked over time.

  PYTHONPATH=src python -m benchmarks.run --suite pipeline --smoke
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
      PYTHONPATH=src python -m benchmarks.run --suite pipeline --smoke --devices 4
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timeit
from repro.dg.rk import LSRK_A, LSRK_B
from repro.dg.solver import gaussian_pulse, make_two_tree_solver
from repro.runtime.executor import BlockedDGEngine, NestedPartitionExecutor

JSON_PATH = "BENCH_pipeline.json"


def _unfused_rhs(eng, q):
    """The seed's per-block rhs: fresh scatter target + sequential blocks."""
    K = eng.solver.mesh.K
    out = jnp.zeros((K + 1,) + tuple(q.shape[1:]), q.dtype)
    for b in eng._blocks:
        if b is None:
            continue
        out = out.at[b["scat"]].set(eng.block_rhs(q, b))
    return out[:K]


def _unfused_run(eng, q, n_steps, dt):
    """The seed's driver: Python loop over steps AND stages, eager updates."""
    res = jnp.zeros_like(q)
    for _ in range(n_steps):
        for s in range(5):
            res = LSRK_A[s] * res + dt * _unfused_rhs(eng, q)
            q = q + LSRK_B[s] * res
    jax.block_until_ready(q)
    return q


def _sharded_row(result, order, n_steps, devices, reps):
    """The multi-device row: the SPMD slab path's ShardedStepPipeline — one
    donated shard_map program (ring ppermute exchange inside the compiled
    step loop) across ``devices`` devices.  Requires the process to see that
    many devices (CI sets XLA_FLAGS=--xla_force_host_platform_device_count);
    emits a skip row otherwise."""
    from repro.dg.partitioned import PartitionedDG
    from repro.launch.mesh import make_mesh

    n_avail = len(jax.devices())
    if n_avail < devices:
        emit(f"pipeline/fused_sharded_{devices}dev", 0.0,
             f"SKIPPED: {n_avail} device(s) visible, need {devices} "
             "(set XLA_FLAGS=--xla_force_host_platform_device_count)")
        result["sharded"] = {"devices": devices, "skipped": True,
                             "devices_visible": n_avail}
        return
    # nx must divide by the slab count; keep the element count close to the
    # single-arena rows so steps/sec stays comparable
    grid = (2 * devices, 4, 4)
    solver = make_two_tree_solver(grid=grid, order=order,
                                  extent=(2.0, 1.0, 1.0), dtype="float32")
    q0 = gaussian_pulse(solver, center=(0.5, 0.5, 0.5)).astype(jnp.float32)
    mesh = make_mesh((devices,), ("data",))
    pdg = PartitionedDG(solver=solver, mesh_axes=mesh)
    pipe = pdg.pipeline()
    dt = solver.cfl_dt()
    qp = pdg.permute_in(q0)
    t = timeit(lambda: jax.block_until_ready(pipe.run(qp, n_steps, dt=dt)),
               reps=reps, warmup=1)
    sps = n_steps / t
    emit(f"pipeline/fused_sharded_{devices}dev", t / n_steps * 1e6,
         f"{sps:.1f} steps/s; {1.0 / n_steps:.2f} dispatches/step; "
         f"K={solver.mesh.K}")
    result["sharded"] = {
        "devices": devices, "grid": list(grid), "K": solver.mesh.K,
        "steps_per_sec": sps, "dispatches_per_step": 1.0 / n_steps,
        "host_dispatches_per_run": 1,
    }


def run(grid=(8, 8, 4), order=4, partitions=4, bucket=16, n_steps=20, smoke=False,
        devices=1):
    if smoke:
        grid, order, partitions, bucket, n_steps = (6, 4, 4), 2, 3, 8, 10
    reps = 1 if smoke else 3
    solver = make_two_tree_solver(grid=grid, order=order, extent=(2.0, 1.0, 1.0),
                                  dtype="float32")
    K = solver.mesh.K
    q0 = gaussian_pulse(solver, center=(0.5, 0.5, 0.5)).astype(jnp.float32)
    ex = NestedPartitionExecutor(K, partitions, grid_dims=grid, bucket=bucket)
    eng = BlockedDGEngine(solver, ex)
    pipe = eng.pipeline()
    dt = solver.cfl_dt()
    P = int((ex.counts > 0).sum())

    t_unfused = timeit(lambda: _unfused_run(eng, q0, n_steps, dt), reps=reps, warmup=1)
    t_fused = timeit(lambda: pipe.run(q0, n_steps, dt=dt), reps=reps, warmup=1)

    # observe-overhead row: the in-scan observation channel at rebalance-
    # chunk granularity (run_observed per chunk, one dispatch each) on its
    # own engine/executor, so the timed rebalances never touch the fused
    # row's tables.  The ledgered dispatch count is the acceptance gate CI
    # asserts on: observation must never drop below 1 dispatch per chunk.
    chunk = max(1, n_steps // 4)
    ex_obs = NestedPartitionExecutor(K, partitions, grid_dims=grid, bucket=bucket,
                                     rebalance_every=chunk)
    eng_obs = BlockedDGEngine(solver, ex_obs)
    pipe_obs = eng_obs.pipeline()
    t_observe = timeit(
        lambda: jax.block_until_ready(eng_obs.run(q0, n_steps, dt=dt, observe=True)),
        reps=reps, warmup=1,
    )
    sps_observe = n_steps / t_observe
    disp_observe = pipe_obs.stats.dispatches / max(1, pipe_obs.stats.steps_run)

    # host dispatches per step — an ANALYTIC count of the drivers timed in
    # THIS file, not a measurement: the `_unfused_run` Python-loop driver
    # issues, per stage, ~6 device calls per block (gather / interior /
    # assemble / boundary / fold / scatter) plus the scatter-target alloc,
    # final slice and 4 eager stage-update ops; the fused driver issues ONE
    # call for the whole run.
    disp_unfused = 5 * (6 * P + 2 + 4)
    disp_fused = 1.0 / n_steps
    sps_unfused = n_steps / t_unfused
    sps_fused = n_steps / t_fused
    speedup = t_unfused / t_fused

    result = {
        "config": {
            "grid": list(grid), "order": order, "K": K, "partitions": partitions,
            "bucket": bucket, "n_steps": n_steps, "smoke": bool(smoke),
            "buckets": [list(s) for s in pipe.bucket_signature],
        },
        "unfused": {"steps_per_sec": sps_unfused, "dispatches_per_step": disp_unfused},
        "fused": {"steps_per_sec": sps_fused, "dispatches_per_step": disp_fused},
        "observe": {
            "steps_per_sec": sps_observe,
            # measured on the DispatchStats ledger, not analytic
            "dispatches_per_step": disp_observe,
            "chunk": chunk,
            "observe_chunks": pipe_obs.stats.observe_chunks,
            "overhead_vs_fused": t_observe / t_fused - 1.0,
        },
        "speedup": speedup,
        # steps_per_sec is measured; dispatches_per_step is the analytic
        # count for the two drivers defined in benchmarks/pipeline_throughput
        "dispatch_model": "unfused: 5 stages x (6 calls x P blocks + alloc + "
                          "slice + 4 stage ops); fused: 1 dispatch / run",
    }
    if devices > 1:
        _sharded_row(result, order, n_steps, devices, reps)
    with open(JSON_PATH, "w") as f:
        json.dump(result, f, indent=2)

    emit("pipeline/unfused_python_loop", t_unfused / n_steps * 1e6,
         f"{sps_unfused:.1f} steps/s; {disp_unfused} dispatches/step")
    emit("pipeline/fused_scan", t_fused / n_steps * 1e6,
         f"{sps_fused:.1f} steps/s; {disp_fused:.2f} dispatches/step")
    emit("pipeline/fused_observe", t_observe / n_steps * 1e6,
         f"{sps_observe:.1f} steps/s; {disp_observe:.2f} dispatches/step; "
         f"chunk={chunk}; overhead {100 * (t_observe / t_fused - 1.0):+.1f}% "
         "vs fused")
    emit("pipeline/speedup", speedup, f"K={K} order={order} P={partitions}")
    assert np.isfinite(speedup)
    return result


if __name__ == "__main__":
    run()
