"""Online auto-rebalancing nested-partition executor: the calibrate ->
solve -> resplice loop (paper section 5.6 closed at runtime).

The acceptance invariants:
  * after injecting a 2x slowdown on one partition, <=3 rebalance rounds
    bring the predicted makespan within 10% of the common-finish-time
    optimum;
  * the rebalanced partitioned run still matches the flat solver bitwise
    (the partition is a reordering, never an approximation);
  * respliced chunk sizes stay on bucket multiples so jit caches hit.
"""

import numpy as np
import pytest

from repro.runtime.executor import (
    NestedPartitionExecutor,
    PlanCache,
    bucket_counts,
    pad_to_bucket,
    plan_key,
)


def _linear_models(speeds):
    return [lambda k, s=s: k / s for s in speeds]


# ---------------------------------------------------------------------------
# bucketing
# ---------------------------------------------------------------------------


def test_bucket_counts_conserves_total_and_buckets():
    counts = bucket_counts([100, 200, 212], bucket=16)
    assert counts.sum() == 512
    # every partition except the tail-absorber is a bucket multiple
    off_bucket = [int(c) % 16 for c in counts]
    assert sum(1 for r in off_bucket if r) <= 1


def test_bucket_counts_tiny_total():
    counts = bucket_counts([3, 2], bucket=16)
    assert counts.sum() == 5 and counts.max() == 5


def test_pad_to_bucket():
    assert pad_to_bucket(0, 16) == 0
    assert pad_to_bucket(1, 16) == 16
    assert pad_to_bucket(16, 16) == 16
    assert pad_to_bucket(17, 16) == 32


# ---------------------------------------------------------------------------
# calibrate -> solve -> resplice convergence
# ---------------------------------------------------------------------------


def test_straggler_rebalances_within_three_rounds():
    """Acceptance: 2x straggler -> <=3 rounds -> within 10% of optimum."""
    ex = NestedPartitionExecutor(
        512, 2, grid_dims=(8, 8, 8), bucket=8, time_models=_linear_models([1.0, 1.0])
    )
    ex.calibrate(n_steps=2)
    ex.inject_straggler(0, 2.0)
    rounds = ex.run_until_balanced(rtol=0.10, max_rounds=8)
    assert rounds <= 3, rounds
    assert ex.predicted_makespan() <= 1.10 * ex.optimal_makespan()
    # work moved away from the straggler
    assert ex.counts[0] < ex.counts[1]


def test_straggler_rebalance_four_partitions():
    ex = NestedPartitionExecutor(
        512, 4, grid_dims=(8, 8, 8), bucket=8, time_models=_linear_models([1.0] * 4)
    )
    ex.calibrate(n_steps=1)
    ex.inject_straggler(2, 2.0)
    rounds = ex.run_until_balanced(rtol=0.10, max_rounds=8)
    assert rounds <= 3, rounds
    assert ex.counts[2] == min(ex.counts)


def test_heterogeneous_fleet_matches_solver_optimum():
    """With a 3x-faster accelerator partition the solved split approaches
    the 3:1 common-finish split."""
    ex = NestedPartitionExecutor(
        512, 2, grid_dims=(8, 8, 8), bucket=8, time_models=_linear_models([1.0, 3.0])
    )
    ex.calibrate(n_steps=1)
    ex.run_until_balanced(rtol=0.05, max_rounds=10)
    assert ex.counts[1] / max(1, ex.counts[0]) == pytest.approx(3.0, rel=0.25)


def test_resplice_keeps_partition_valid_and_bucketed():
    ex = NestedPartitionExecutor(512, 3, grid_dims=(8, 8, 8), bucket=16,
                                 time_models=_linear_models([1.0, 2.0, 4.0]))
    ex.calibrate(n_steps=1)
    ex.rebalance()
    ex.partition.validate()  # permutation + host/accel invariants hold
    assert int(np.diff(ex.partition.offsets).sum()) == 512
    np.testing.assert_array_equal(np.diff(ex.partition.offsets), ex.counts)
    # chunk pads are bucket multiples (jit-cache-stable shapes)
    assert all(p % 16 == 0 or p == 0 for p in ex.chunk_pads)


def test_observe_total_is_neutral_without_skew():
    """Synchronous-step attribution carries no skew: the split stays put."""
    ex = NestedPartitionExecutor(512, 2, grid_dims=(8, 8, 8), bucket=8)
    before = ex.counts.copy()
    for _ in range(3):
        ex.observe_total(0.1)
        ex.rebalance()
    np.testing.assert_array_equal(ex.counts, before)


def test_drive_step_driver_rebalances_on_schedule():
    ex = NestedPartitionExecutor(512, 2, grid_dims=(8, 8, 8), bucket=8,
                                 rebalance_every=2, smoothing=1.0)
    calls = []

    def step_fn(state):
        calls.append(state)
        return state + 1

    # per-partition attribution: p0 always twice as slow per item
    def times_fn(executor, dt):
        return executor.counts / np.array([0.5, 1.0])

    out = ex.drive(0, step_fn, 6, times_fn=times_fn)
    assert out == 6 and len(calls) == 6
    assert ex.round >= 2  # rebalanced on the every-2-steps schedule
    assert ex.counts[0] < ex.counts[1]


# ---------------------------------------------------------------------------
# plan cache (persisted via repro.checkpoint)
# ---------------------------------------------------------------------------


def test_plan_key_stable_and_weight_sensitive():
    k1 = plan_key((8, 8, 8), 512, 2, 8, 0.0, [0.5, 0.5])
    k2 = plan_key((8, 8, 8), 512, 2, 8, 0.0, [1.0, 1.0])  # same normalized
    k3 = plan_key((8, 8, 8), 512, 2, 8, 0.0, [0.4, 0.6])
    assert k1 == k2 and k1 != k3


def test_plan_cache_roundtrip(tmp_path):
    ex = NestedPartitionExecutor(512, 2, grid_dims=(8, 8, 8), bucket=8,
                                 plan_cache_dir=str(tmp_path))
    plan = ex.solve([0.4, 0.6])
    assert ex.plan_cache.misses == 1
    again = ex.solve([0.4, 0.6])
    assert ex.plan_cache.hits == 1
    np.testing.assert_array_equal(plan.counts, again.counts)

    # a fresh executor (fresh process analogue) reuses the persisted plan
    ex2 = NestedPartitionExecutor(512, 2, grid_dims=(8, 8, 8), bucket=8,
                                  plan_cache_dir=str(tmp_path))
    hits0 = ex2.plan_cache.hits
    plan2 = ex2.solve([0.4, 0.6])
    assert ex2.plan_cache.hits == hits0 + 1
    np.testing.assert_array_equal(plan.counts, plan2.counts)


def test_rebalance_every_zero_disables_schedule():
    ex = NestedPartitionExecutor(512, 2, grid_dims=(8, 8, 8), bucket=8,
                                 rebalance_every=0)
    ex.observe_total(0.1)
    assert ex.advance() is None  # no ZeroDivisionError, no rebalance
    assert ex.round == 0


def test_plan_cache_restart_resumes_calibrated_split(tmp_path):
    """A restarted executor adopts the last applied plan, not the naive
    50/50 split."""
    ex = NestedPartitionExecutor(512, 2, grid_dims=(8, 8, 8), bucket=8,
                                 smoothing=1.0, plan_cache_dir=str(tmp_path),
                                 time_models=_linear_models([1.0, 3.0]))
    ex.calibrate(n_steps=1)
    ex.rebalance()
    calibrated = ex.counts.copy()
    assert calibrated[1] > calibrated[0]

    restarted = NestedPartitionExecutor(512, 2, grid_dims=(8, 8, 8), bucket=8,
                                        plan_cache_dir=str(tmp_path))
    np.testing.assert_array_equal(restarted.counts, calibrated)


def test_plan_cache_direct(tmp_path):
    from repro.runtime.executor import Plan

    cache = PlanCache(str(tmp_path))
    p = Plan(key="abc", weights=np.array([0.25, 0.75]),
             counts=np.array([128, 384]), predicted_times=np.array([1.0, 1.0]), round=3)
    cache.put(p)
    got = cache.get("abc", 2)
    assert got is not None and got.round == 3
    np.testing.assert_array_equal(got.counts, p.counts)
    assert cache.get("missing", 2) is None


# ---------------------------------------------------------------------------
# blocked DG engine: bitwise-identical execution + jit-stable resplice
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def dg_setup():
    import jax.numpy as jnp  # noqa: F401 — ensures jax configured via conftest

    from repro.dg.solver import gaussian_pulse, make_two_tree_solver

    solver = make_two_tree_solver(grid=(6, 4, 4), order=2, extent=(2.0, 1.0, 1.0))
    q0 = gaussian_pulse(solver, center=(0.5, 0.5, 0.5))
    return solver, q0


def _flat_reference(solver, q0, n_steps, dt):
    """The flat solver stepped with the same eager LSRK loop the engine
    uses (identical update arithmetic, global single-array rhs)."""
    import jax
    import jax.numpy as jnp

    from repro.dg.rk import lsrk45_step

    rhs = jax.jit(solver.rhs)
    q, res = q0, jnp.zeros_like(q0)
    for _ in range(n_steps):
        q, res = lsrk45_step(q, res, rhs, dt)
    return q


def test_blocked_engine_matches_flat_bitwise(dg_setup):
    from repro.runtime.executor import BlockedDGEngine

    solver, q0 = dg_setup
    ex = NestedPartitionExecutor(96, 3, grid_dims=(6, 4, 4), bucket=8)
    eng = BlockedDGEngine(solver, ex)
    dt = solver.cfl_dt()

    # a single rhs evaluation is exactly bitwise identical
    r_flat = np.asarray(solver.rhs(q0))
    r_blk = np.asarray(eng.rhs(q0))
    assert (r_flat == r_blk).all(), np.abs(r_flat - r_blk).max()

    # across steps XLA may retile the per-batch-size gemms, reassociating
    # sub-noise-floor cancellations (observed ~1e-22 on O(1) fields) — the
    # repo's invariant: bitwise up to float reassociation
    q_flat = np.asarray(_flat_reference(solver, q0, 3, dt))
    q_blk = np.asarray(eng.run(q0, 3, dt=dt))
    np.testing.assert_allclose(q_blk, q_flat, rtol=1e-12, atol=1e-14)


def test_blocked_engine_bitwise_after_rebalance(dg_setup):
    """Acceptance: the REBALANCED partitioned run still matches the flat
    solver bitwise, and the resplice only uses bucketed shapes."""
    from repro.runtime.executor import BlockedDGEngine

    solver, q0 = dg_setup
    ex = NestedPartitionExecutor(96, 3, grid_dims=(6, 4, 4), bucket=8)
    eng = BlockedDGEngine(solver, ex)
    dt = solver.cfl_dt()

    # calibrate on real timings, then force a skewed rebalance
    eng.calibrate(q0, reps=1)
    ex.observe(np.array([0.02, 0.01, 0.01]))
    ex.rebalance()
    assert not np.array_equal(ex.counts, [32, 32, 32])  # the split moved

    q_flat = np.asarray(_flat_reference(solver, q0, 3, dt))
    q_blk = np.asarray(eng.run(q0, 3, dt=dt))
    np.testing.assert_allclose(q_blk, q_flat, rtol=1e-12, atol=1e-14)
    assert all(p % 8 == 0 for p in eng.pads_seen)


def test_blocked_engine_calibration_report(dg_setup):
    """Acceptance: calibrate() resolves nonzero, distinct boundary /
    interior / transfer components on the DG engine (no 'whole step is
    interior' fallback)."""
    from repro.runtime.executor import BlockedDGEngine

    solver, q0 = dg_setup
    ex = NestedPartitionExecutor(96, 2, grid_dims=(6, 4, 4), bucket=8)
    eng = BlockedDGEngine(solver, ex)
    rep = eng.calibrate(q0, reps=1)
    for comp in (rep.boundary_s, rep.interior_s, rep.transfer_s, rep.correction_s):
        assert (comp > 0).all()
    # the components are genuinely distinct measurements, not one value
    # smeared across fields
    for p in range(2):
        vals = {rep.boundary_s[p], rep.interior_s[p], rep.transfer_s[p]}
        assert len(vals) == 3, vals
    assert (rep.step_s >= rep.interior_s).all()
    assert (rep.overlapped_s <= rep.step_s).all()
    assert (rep.overlap_efficiency >= 0).all() and (rep.overlap_efficiency <= 1).all()
    assert "overlap-eff=" in rep.summary()
    assert ex._ewma is not None  # calibration seeds the measurement loop


def test_executor_calibrate_passes_reports_through(dg_setup):
    """NestedPartitionExecutor.calibrate with a phase-resolved measure_fn
    returns the component median, not an interior-only fallback — and each
    sample enters the EWMA exactly once even though the bound engine
    calibrate observes internally."""
    from repro.runtime.executor import BlockedDGEngine

    solver, q0 = dg_setup
    ex = NestedPartitionExecutor(96, 2, grid_dims=(6, 4, 4), bucket=8)
    eng = BlockedDGEngine(solver, ex)
    rep = ex.calibrate(measure_fn=lambda: eng.calibrate(q0, reps=1), n_steps=2)
    assert (rep.boundary_s > 0).all() and (rep.transfer_s > 0).all()
    assert ex._ewma is not None
    assert ex._n_obs == 2  # one observation per calibration step, not two


def test_blocked_engine_periodic_mesh_matches_flat():
    """Regression: on a periodic brick the wrap-around cross-node faces must
    enter the halo (the partition is built from the SOLVER mesh's neighbour
    table, not the default non-periodic grid table)."""
    import jax.numpy as jnp

    from repro.dg.mesh import make_brick
    from repro.dg.solver import DGSolver
    from repro.runtime.executor import BlockedDGEngine

    mesh = make_brick((4, 4, 2), (1.0, 1.0, 0.5), periodic=True)
    K = mesh.K
    solver = DGSolver(mesh=mesh, order=2, rho=np.ones(K), lam=np.ones(K), mu=np.zeros(K))
    rng = np.random.default_rng(0)
    q0 = jnp.asarray(rng.standard_normal((K, 9, solver.M, solver.M, solver.M)))
    ex = NestedPartitionExecutor(K, 2, grid_dims=(4, 4, 2), bucket=8)
    eng = BlockedDGEngine(solver, ex)
    ex.partition.validate()  # halo invariants under the periodic topology
    r_flat = np.asarray(solver.rhs(q0))
    r_blk = np.asarray(eng.rhs(q0))
    assert (r_flat == r_blk).all(), np.abs(r_flat - r_blk).max()


def test_executor_calibrate_totals_path():
    """Whole-step time models still calibrate: totals are carried as an
    unresolved report (components make no claim, step_s is the total)."""
    ex = NestedPartitionExecutor(
        512, 2, grid_dims=(8, 8, 8), bucket=8, time_models=_linear_models([1.0, 2.0])
    )
    rep = ex.calibrate(n_steps=2)
    np.testing.assert_allclose(rep.step_s, ex.simulated_times())
    np.testing.assert_allclose(rep.boundary_s, 0.0)
    np.testing.assert_allclose(rep.transfer_s, 0.0)


def test_plan_from_report_credits_hidden_transfer():
    """The overlap-aware solve gives the transfer-hiding partition at least
    as much work as the sequential solve, and a lower predicted makespan."""
    from repro.runtime.schedule import CalibrationReport

    ex = NestedPartitionExecutor(512, 2, grid_dims=(8, 8, 8), bucket=8)
    ex.observe_total(0.1)
    # p1 has a big transfer fully hideable under its interior compute
    rep = CalibrationReport(
        boundary_s=np.array([0.01, 0.01]),
        interior_s=np.array([0.10, 0.10]),
        transfer_s=np.array([0.00, 0.08]),
    )
    seq = ex.plan_from_report(rep, overlap=False, apply=False)
    ov = ex.plan_from_report(rep, overlap=True, apply=True)
    assert int(ov.counts.sum()) == 512
    assert ov.counts[1] > seq.counts[1]  # hidden transfer credited to p1
    # only the APPLIED solve counts as a round; the what-if solve does not
    assert ex.round == 1 and np.array_equal(ex.counts, ov.counts)
    ex.partition.validate()


def test_blocked_engine_run_after_overlap_plan(dg_setup):
    """A resplice driven by the overlap-aware plan still runs bitwise."""
    from repro.runtime.executor import BlockedDGEngine

    solver, q0 = dg_setup
    ex = NestedPartitionExecutor(96, 3, grid_dims=(6, 4, 4), bucket=8)
    eng = BlockedDGEngine(solver, ex)
    rep = eng.calibrate(q0, reps=1)
    ex.plan_from_report(rep)
    dt = solver.cfl_dt()
    q_flat = np.asarray(_flat_reference(solver, q0, 2, dt))
    q_blk = np.asarray(eng.run(q0, 2, dt=dt))
    np.testing.assert_allclose(q_blk, q_flat, rtol=1e-12, atol=1e-14)


def test_partitioned_dg_run_with_executor(subproc):
    """The SPMD slab path adopts the executor step-driver API."""
    subproc(
        """
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
import numpy as np
from repro.dg.partitioned import PartitionedDG
from repro.dg.solver import gaussian_pulse, make_two_tree_solver

solver = make_two_tree_solver(grid=(8, 4, 4), order=3, extent=(2.0, 1.0, 1.0))
q0 = gaussian_pulse(solver, center=(0.5, 0.5, 0.5))
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("data",))
pdg = PartitionedDG(solver=solver, mesh_axes=mesh)
ex = pdg.bind_executor(pdg.make_executor(rebalance_every=2))
qp = pdg.run(pdg.permute_in(q0), 4, observe=True)
qf = solver.run(q0, 4)
err = float(jnp.abs(qf - pdg.permute_out(np.asarray(qp))).max())
assert err < 1e-10, err
assert ex.round >= 1  # the executor rebalanced on schedule
print("OK", ex.counts.tolist())
""",
        n_devices=4,
    )
