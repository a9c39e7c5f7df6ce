"""The heterogeneous node on one device: ``BlockedDGEngine`` over a
``NestedPartitionExecutor`` that starts from the configuration's split,
observes each chunk's rates and re-solves the split on its schedule.  Each
dispatch is one ``run(..., observe=True)`` of ``steps_per_dispatch`` steps:
one priced fused program per observe chunk through ``FusedStepPipeline``,
a sync, then the re-solve and, after it, the tables rebuilt.

On one chip every partition runs at one rate, so the configuration's rate
gap enters through ``inject_straggler``, which scales the observed times.

Counters that count events are the window's: the first dispatch is the
harness's warm dispatch, and what it counted is reported apart (``warm``)
and taken off.  A counter the program does not keep is left out.
"""

from __future__ import annotations

import numpy as np

from _solver import build_solver

# cumulative counts, by the attribute that keeps each
STATS = ("observe_chunks", "dispatches", "steps_run", "table_builds", "programs_built")
EXECUTOR = ("rebalances", "plans_changed")


class Driver:
    def __init__(self, cfg, traffic, prob, q0_ref, devices, kernel_impl):
        import jax

        from repro.runtime import BlockedDGEngine, NestedPartitionExecutor

        node = cfg["node"]
        self.devices = devices[:1]
        self.steps_per_dispatch = int(traffic["steps_per_dispatch"])
        self.dt = prob.dt
        solver = build_solver(cfg, kernel_impl)
        ex = NestedPartitionExecutor(
            solver.mesh.K, int(node["partitions"]), grid_dims=solver.mesh.grid,
            initial_weights=node["initial_weights"],
            rebalance_every=int(node["rebalance_every"]), bucket=int(node["bucket"]))
        ex.inject_straggler(int(node["straggler"]["partition"]),
                            float(node["straggler"]["factor"]))
        self.engine = BlockedDGEngine(solver, ex)
        self.warm = None
        self.state = jax.jit(lambda q: jax.numpy.transpose(q, (4, 0, 1, 2, 3)))(q0_ref)

    def dispatch(self, q, n=None):
        q = self.engine.run(q, n or self.steps_per_dispatch, dt=self.dt, observe=True)
        if self.warm is None:
            self.warm = self._events()
        return q

    def to_reference(self, q) -> np.ndarray:
        return np.asarray(q).transpose(1, 2, 3, 4, 0)

    def _events(self) -> dict:
        owned = [(self.engine.pipeline().stats, STATS), (self.engine.executor, EXECUTOR)]
        return {k: int(getattr(o, k)) for o, keys in owned for k in keys if hasattr(o, k)}

    def counters(self) -> dict:
        pipe = self.engine.pipeline()
        now, warm = self._events(), self.warm or {}
        return {
            **{k: v - warm.get(k, 0) for k, v in now.items()},
            "warm": warm,
            "partition_counts": [int(c) for c in self.engine.executor.counts],
            "gathered_rows_per_rhs": sum(env * nb for env, _, nb, _ in pipe.bucket_signature),
            # each block's own and halo rows, bucketed, before the envelope pads it
            "block_rows_per_rhs": sum(int(b["nbr_local"].shape[0])
                                      for b in self.engine._blocks if b is not None),
        }
