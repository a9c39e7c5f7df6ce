"""The sharded path across devices: ``PartitionedDG`` x-slabs over a
``devices``-way mesh, each dispatch one ``PartitionedDG.run`` of
``steps_per_dispatch`` steps through ``ShardedStepPipeline`` (ring
``ppermute`` halo inside the compiled loop).  The state enters through
``permute_in`` and leaves through ``permute_out``, as a user's does."""

from __future__ import annotations

import numpy as np

from _solver import build_solver


class Driver:
    def __init__(self, cfg, traffic, prob, q0_ref, devices, kernel_impl):
        from repro.dg.partitioned import PartitionedDG
        from repro.launch.mesh import make_mesh

        n = int(traffic["devices"])
        if len(devices) < n:
            raise RuntimeError(f"the sharded path needs {n} devices, found {len(devices)}")
        self.devices = devices[:n]
        self.steps_per_dispatch = int(traffic["steps_per_dispatch"])
        self.dt = prob.dt
        solver = build_solver(cfg, kernel_impl)
        mesh = make_mesh((n,), ("data",), devices=self.devices)
        self.pdg = PartitionedDG(solver=solver, mesh_axes=mesh)
        self.state = self.pdg.permute_in(np.asarray(q0_ref).transpose(4, 0, 1, 2, 3))
        self.slab_devices = len({s.device for s in self.state.addressable_shards})

    def dispatch(self, q, n=None):
        return self.pdg.run(q, n or self.steps_per_dispatch, dt=self.dt)

    def to_reference(self, q) -> np.ndarray:
        return self.pdg.permute_out(q).transpose(1, 2, 3, 4, 0)

    def counters(self) -> dict:
        st = self.pdg.pipeline().stats
        return {"dispatches": st.dispatches, "steps_run": st.steps_run,
                "slab_devices": self.slab_devices}
