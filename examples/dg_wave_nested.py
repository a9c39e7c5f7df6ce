"""The paper's experiment, end to end: coupled elastic-acoustic wave
propagation on the two-material brick (Fig 6.1), executed BOTH ways:

  * flat single-array solver (the baseline ``dgae`` execution), and
  * the nested partition across 4 (fake) devices: Morton/slab level-1
    splices, per-stage ring halo exchange overlapped with interior compute.

Prints per-step timing for both and verifies they produce identical fields
(the paper's partition is a reordering, never an approximation).

Run:  PYTHONPATH=src python examples/dg_wave_nested.py
(sets 4 fake host devices before importing jax)
"""

import os

if "--_child" not in os.sys.argv:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
    )

import time

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np

from repro.dg.partitioned import PartitionedDG
from repro.dg.solver import gaussian_pulse, make_two_tree_solver
from repro.launch.mesh import make_mesh


def main():
    grid, order, steps = (16, 8, 8), 4, 30
    solver = make_two_tree_solver(grid=grid, order=order, extent=(2.0, 1.0, 1.0))
    print(f"[setup] {solver.mesh.K} elements, order {order} "
          f"({solver.mesh.K * solver.M**3 * 9 / 1e6:.2f}M dof), dt={solver.cfl_dt():.2e}")
    q0 = gaussian_pulse(solver, center=(0.5, 0.5, 0.5))

    t0 = time.perf_counter()
    qf = solver.run(q0, steps)
    jax.block_until_ready(qf)
    t_flat = time.perf_counter() - t0

    mesh = make_mesh((4,), ("data",))
    pdg = PartitionedDG(solver=solver, mesh_axes=mesh)
    qp0 = pdg.permute_in(q0)
    t0 = time.perf_counter()
    qp = pdg.run(qp0, steps)
    jax.block_until_ready(qp)
    t_nested = time.perf_counter() - t0

    err = float(jnp.abs(qf - pdg.permute_out(np.asarray(qp))).max())
    e0, e1 = solver.energy(q0), solver.energy(qf)
    print(f"[flat]   {steps} steps in {t_flat:.2f}s ({t_flat/steps*1e3:.1f} ms/step)")
    print(f"[nested] {steps} steps in {t_nested:.2f}s ({t_nested/steps*1e3:.1f} ms/step) "
          f"on 4 partitions")
    print(f"[check]  max |flat - nested| = {err:.2e}  "
          f"energy {e0:.4f} -> {e1:.4f} ({'stable' if e1 <= e0*1.0001 else 'UNSTABLE'})")
    assert err < 1e-10


if __name__ == "__main__":
    main()
