"""The nested-partition path on one device: ``BlockedDGEngine`` over a
``NestedPartitionExecutor``, each dispatch one fused ``run`` of
``steps_per_dispatch`` steps through ``FusedStepPipeline``."""

from __future__ import annotations

import numpy as np

from _solver import build_solver


class Driver:
    def __init__(self, cfg, traffic, prob, q0_ref, devices, kernel_impl):
        import jax

        from repro.runtime import BlockedDGEngine, NestedPartitionExecutor

        self.devices = devices[:1]
        self.steps_per_dispatch = int(traffic["steps_per_dispatch"])
        self.dt = prob.dt
        solver = build_solver(cfg, kernel_impl)
        ex = NestedPartitionExecutor(solver.mesh.K, int(traffic["partitions"]),
                                     grid_dims=solver.mesh.grid)
        self.engine = BlockedDGEngine(solver, ex)
        self.state = jax.jit(lambda q: jax.numpy.transpose(q, (4, 0, 1, 2, 3)))(q0_ref)

    def dispatch(self, q, n=None):
        return self.engine.run(q, n or self.steps_per_dispatch, dt=self.dt)

    def to_reference(self, q) -> np.ndarray:
        return np.asarray(q).transpose(1, 2, 3, 4, 0)

    def counters(self) -> dict:
        pipe = self.engine.pipeline()
        st = pipe.stats
        return {
            "dispatches": st.dispatches, "steps_run": st.steps_run,
            "kernel_launches": dict(st.kernel_launches),
            "partition_counts": [int(c) for c in self.engine.executor.counts],
            "gathered_rows_per_rhs": sum(env * nb for env, _, nb, _ in pipe.bucket_signature),
        }
