"""Differential harness for the multi-device fused pipeline.

The acceptance invariant of the sharded fused driver
(``runtime.pipeline.ShardedStepPipeline`` — ONE donated shard_map program:
step loop, stage scan, and the ring ppermute halo exchange all inside):

* **bitwise identical** (``kernel_impl='xla'``) to (a) the flat
  ``DGSolver``, (b) the eager per-step ``PartitionedDG`` loop, and (c) the
  single-device ``FusedStepPipeline`` at the rhs level — on periodic
  meshes, across slab counts;
* under ``kernel_impl='interpret'`` the Pallas bodies lower through jnp
  into the *surrounding* program, so FMA contraction may differ between
  differently-shaped programs (the repo's existing interpret tests compare
  the solver level with allclose for the same reason) — the drivers must
  agree to ~1 ulp;
* **O(1) host dispatches per run** — independent of device count, slab
  count and step horizon — counted on the actual compiled-function calls,
  so a future edit cannot silently re-Python-loop the hot path.

All tests run in a subprocess with XLA_FLAGS=--xla_force_host_platform_
device_count=4 (the conftest ``subproc`` fixture), so they pass in the
single-device tier-1 lane and the multi-device CI lane alike.
"""


DIFFERENTIAL = r"""
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np, jax.numpy as jnp
from repro.dg.mesh import make_brick
from repro.dg.solver import DGSolver
from repro.dg.partitioned import PartitionedDG
from repro.launch.mesh import make_mesh
from repro.runtime.executor import BlockedDGEngine, NestedPartitionExecutor

def periodic_solver(grid, impl, order=2, lam=1.0, mu=0.0):
    # the unit-material acoustic brick (rho=lam=1, mu=0) is the mesh family
    # the repo's bitwise invariants use (tests/test_pipeline.py): there
    # XLA's FMA contraction is identical across differently-shaped compiled
    # programs; non-unit/elastic materials are checked separately at ~1 ulp
    mesh = make_brick(grid, (1.0, 1.0, 0.5), periodic=True)
    K = mesh.K
    return DGSolver(mesh=mesh, order=order, rho=np.ones(K),
                    lam=np.full(K, lam), mu=np.full(K, mu),
                    kernel_impl=impl)

def check(a, b, what, bitwise):
    a, b = np.asarray(a), np.asarray(b)
    if bitwise:
        assert (a == b).all(), (what, np.abs(a - b).max())
    else:  # interpret: Pallas-in-jnp is not FMA-stable across program shapes
        np.testing.assert_allclose(a, b, rtol=1e-12, atol=1e-13, err_msg=what)

n_checked = 0
for impl in ("xla", "interpret"):
    bitwise = impl == "xla"
    for grid, slabs in (((4, 2, 2), 2), ((4, 2, 2), 4), ((4, 4, 2), 2)):
        solver = periodic_solver(grid, impl)
        K = solver.mesh.K
        rng = np.random.default_rng(7)
        q0 = jnp.asarray(rng.standard_normal((K, 9, solver.M, solver.M, solver.M)))
        dt = solver.cfl_dt()
        mesh = make_mesh((slabs,), ("data",))
        pdg = PartitionedDG(solver=solver, mesh_axes=mesh)
        pipe = pdg.pipeline()
        qp = pdg.permute_in(q0)

        # --- rhs level: all four paths --------------------------------
        r_flat = solver.rhs(q0)                                  # (a)
        r_eager = pdg.permute_out(np.asarray(pdg.rhs(qp)))       # (b)
        r_shard = pdg.permute_out(np.asarray(pipe.rhs(qp)))      # sharded fused
        ex = NestedPartitionExecutor(K, slabs, grid_dims=grid, bucket=4)
        eng = BlockedDGEngine(solver, ex)
        r_blk = eng.pipeline().rhs(q0)                           # (c)
        check(r_flat, r_shard, f"{impl} {grid} P={slabs}: sharded rhs vs flat", bitwise)
        check(r_eager, r_shard, f"{impl} {grid} P={slabs}: sharded rhs vs eager", bitwise)
        check(r_blk, r_shard, f"{impl} {grid} P={slabs}: sharded rhs vs blocked fused", bitwise)

        # --- run level: 3 steps through every driver ------------------
        q_flat = np.asarray(solver.run(q0, 3, dt))               # (a)
        q_shard = pdg.permute_out(np.asarray(pipe.run(qp, 3, dt=dt)))
        q_eager = pdg.permute_out(np.asarray(pdg.run(qp, 3, dt=dt, fused=False)))
        q_blk = np.asarray(eng.run(q0, 3, dt=dt))                # (c)
        check(q_flat, q_shard, f"{impl} {grid} P={slabs}: sharded run vs flat", bitwise)
        check(q_eager, q_shard, f"{impl} {grid} P={slabs}: sharded run vs eager", bitwise)
        # (c) across compiled programs: the blocked program's bucket
        # gather/scatter changes XLA's FMA choices in the lsrk update by
        # ~1 ulp per step (documented in repro/dg/rk.py) — rhs above IS
        # bitwise; the run agrees to contraction noise
        np.testing.assert_allclose(q_blk, q_shard, rtol=1e-12, atol=1e-13,
                                   err_msg=f"{impl} {grid} P={slabs}: blocked run")
        n_checked += 1
assert n_checked == 6

# coupled elastic materials: non-unit lam/mu open FMA-contraction choices
# that differ between compiled programs, so the cross-program agreement is
# ~1 ulp instead of bitwise (same as the repo's existing solver-level
# interpret tests)
solver = periodic_solver((4, 2, 2), "xla", lam=1.1, mu=0.3)
K = solver.mesh.K
rng = np.random.default_rng(7)
q0 = jnp.asarray(rng.standard_normal((K, 9, solver.M, solver.M, solver.M)))
mesh = make_mesh((2,), ("data",))
pdg = PartitionedDG(solver=solver, mesh_axes=mesh)
qp = pdg.permute_in(q0)
dt = solver.cfl_dt()
q_flat = np.asarray(solver.run(q0, 3, dt))
q_shard = pdg.permute_out(np.asarray(pdg.pipeline().run(qp, 3, dt=dt)))
np.testing.assert_allclose(q_shard, q_flat, rtol=1e-12, atol=1e-13,
                           err_msg="elastic periodic: sharded run vs flat")
print("OK", n_checked)
"""


DISPATCH = r"""
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np, jax.numpy as jnp
from repro.dg.mesh import make_brick
from repro.dg.solver import DGSolver
from repro.dg.partitioned import PartitionedDG
from repro.launch.mesh import make_mesh

mesh_b = make_brick((4, 2, 2), (1.0, 1.0, 0.5), periodic=True)
K = mesh_b.K
solver = DGSolver(mesh=mesh_b, order=2, rho=np.ones(K), lam=np.ones(K),
                  mu=np.zeros(K))
rng = np.random.default_rng(0)
q0 = jnp.asarray(rng.standard_normal((K, 9, 3, 3, 3)))
dt = solver.cfl_dt()

for slabs in (2, 4):
    mesh = make_mesh((slabs,), ("data",))
    pdg = PartitionedDG(solver=solver, mesh_axes=mesh)
    pipe = pdg.pipeline()
    qp = pdg.permute_in(q0)
    # count ACTUAL compiled-program invocations, not the self-reported stat
    calls = []
    orig = pipe._run_fn()
    pipe._run_c = lambda *a, **k: (calls.append(1), orig(*a, **k))[1]
    for n in (1, 3, 7):  # three horizons, ONE compiled program
        before = len(calls)
        d0 = pipe.stats.dispatches
        pipe.run(qp, n, dt=dt)
        assert len(calls) - before == 1, (slabs, n, len(calls) - before)
        assert pipe.stats.dispatches - d0 == 1
    # executor-segmented fused observe run: one dispatch per rebalance
    # chunk, now through the in-scan observation channel (run_observed)
    ex = pdg.bind_executor(pdg.make_executor(rebalance_every=2))
    obs_calls = []
    orig_obs = pipe._priced_run_fn()
    pipe._priced_run_c = lambda *a, **k: (obs_calls.append(1), orig_obs(*a, **k))[1]
    pdg.run(qp, 4, dt=dt, observe=True)
    assert len(obs_calls) == 2, len(obs_calls)  # 4 steps / chunks of 2
    assert pipe.stats.observe_chunks == 2
    assert ex.round >= 1  # the executor rebalanced on schedule
print("OK")
"""


def test_sharded_fused_differential(subproc):
    """Sharded fused == flat == eager slab loop == blocked fused, periodic
    meshes, >= 2 slab counts, both kernel_impl settings (see module doc)."""
    out = subproc(DIFFERENTIAL, n_devices=4)
    assert "OK 6" in out


def test_sharded_dispatch_counts(subproc):
    """1 host dispatch per run — for every horizon and device count — and
    one dispatch per rebalance chunk on the executor path, counted on the
    compiled callable itself."""
    out = subproc(DISPATCH, n_devices=4)
    assert "OK" in out


def test_sharded_run_with_heterogeneous_devices_counts(subproc):
    """The same program serves 2-of-4 and 4-of-4 device meshes in one
    process (per-decomposition compile caches are independent)."""
    out = subproc(
        r"""
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np, jax.numpy as jnp
from repro.dg.solver import make_two_tree_solver, gaussian_pulse
from repro.dg.partitioned import PartitionedDG
from repro.launch.mesh import make_mesh

solver = make_two_tree_solver(grid=(8, 4, 4), order=2, extent=(2.0, 1.0, 1.0))
q0 = gaussian_pulse(solver, center=(0.5, 0.5, 0.5))
dt = solver.cfl_dt()
q_ref = None
for slabs in (2, 4):
    mesh = make_mesh((slabs,), ("data",))
    pdg = PartitionedDG(solver=solver, mesh_axes=mesh)
    q = pdg.permute_out(np.asarray(pdg.run(pdg.permute_in(q0), 4, dt=dt)))
    if q_ref is None:
        q_ref = q
    else:
        assert (q == q_ref).all(), np.abs(q - q_ref).max()
print("OK")
""",
        n_devices=4,
    )
    assert "OK" in out
