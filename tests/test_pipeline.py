"""Fused scan-compiled pipeline vs the unfused four-phase schedule.

The acceptance invariant: ``FusedStepPipeline`` — one donated program,
``lax.scan`` over steps and stages, same-bucket blocks batched into single
kernel launches — is BITWISE identical to the per-block schedule path, on
periodic meshes, across bucket sizes, before and after an executor
resplice, and with the Pallas kernels (``kernel_impl='interpret'``) inside
the fused program.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from hypothesis_shim import given, settings, st
from repro.dg.mesh import make_brick
from repro.dg.rk import LSRK_A, LSRK_B, lsrk45_step, lsrk_coeffs
from repro.dg.solver import DGSolver, gaussian_pulse, make_two_tree_solver
from repro.launch.mesh import make_mesh
from repro.runtime.executor import BlockedDGEngine, NestedPartitionExecutor


def _periodic_solver(grid=(4, 4, 2), kernel_impl="xla", order=2):
    mesh = make_brick(grid, (1.0, 1.0, 0.5), periodic=True)
    K = mesh.K
    return DGSolver(mesh=mesh, order=order, rho=np.ones(K), lam=np.ones(K),
                    mu=np.zeros(K), kernel_impl=kernel_impl)


def _rand_state(solver, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        rng.standard_normal((solver.mesh.K, 9, solver.M, solver.M, solver.M))
    )


def _unfused_run(eng, q, n_steps, dt):
    """The unfused schedule, compiled per step: jit traces the scan stage
    loop over the per-block rhs — the same per-step program the fused
    pipeline's step loop iterates, so fused vs unfused is bitwise."""
    import jax

    step = jax.jit(lambda q, res: lsrk45_step(q, res, eng.rhs, dt))
    res = jnp.zeros_like(q)
    for _ in range(n_steps):
        q, res = step(q, res)
    return q


# ---------------------------------------------------------------------------
# rk: the scan-compiled stage loop
# ---------------------------------------------------------------------------


def test_lsrk_eager_is_exact_reference_loop():
    """Called eagerly (concrete arrays), lsrk45_step runs the historical
    Python stage loop — bitwise identical, no per-call re-trace."""
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((7, 9, 3, 3, 3)))
    res = jnp.asarray(rng.standard_normal((7, 9, 3, 3, 3)))
    rhs = lambda x: x * 1.25 - 0.5
    dt = 1e-3
    q_ref, res_ref = q, res
    for s in range(5):
        res_ref = LSRK_A[s] * res_ref + dt * rhs(q_ref)
        q_ref = q_ref + LSRK_B[s] * res_ref
    q_s, res_s = lsrk45_step(q, res, rhs, dt)
    assert (np.asarray(q_s) == np.asarray(q_ref)).all()
    assert (np.asarray(res_s) == np.asarray(res_ref)).all()


def test_lsrk_scan_under_jit_matches_loop():
    """Under a trace the stage loop is a lax.scan; it equals the eager loop
    up to FMA contraction (the compiled body may fuse a*res + dt*rhs into a
    single-rounding fma; ~1 ulp on O(1) fields)."""
    import jax

    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((7, 9, 3, 3, 3)))
    res = jnp.asarray(rng.standard_normal((7, 9, 3, 3, 3)))
    rhs = lambda x: x * 1.25 - 0.5
    dt = 1e-3
    q_ref, res_ref = lsrk45_step(q, res, rhs, dt)  # eager = reference loop
    q_s, res_s = jax.jit(lambda q, res: lsrk45_step(q, res, rhs, dt))(q, res)
    np.testing.assert_allclose(np.asarray(q_s), np.asarray(q_ref), rtol=1e-13, atol=1e-15)
    np.testing.assert_allclose(np.asarray(res_s), np.asarray(res_ref), rtol=1e-13, atol=1e-15)


def test_lsrk_coeffs_dtype_stable():
    for dt in ("float32", "float64"):
        c = lsrk_coeffs(dt)
        assert c.shape == (5, 2) and str(c.dtype) == dt
    q32 = jnp.zeros((2, 9, 3, 3, 3), jnp.float32)
    q, res = lsrk45_step(q32, jnp.zeros_like(q32), lambda x: x + 1, 1e-3)
    assert q.dtype == jnp.float32 and res.dtype == jnp.float32


# ---------------------------------------------------------------------------
# fused == unfused, bitwise
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bucket", [4, 8])
def test_fused_rhs_bitwise_on_periodic_mesh(bucket):
    """Fused rhs == unfused schedule == flat solver, on a periodic brick,
    for two bucket (padded-shape) sizes."""
    solver = _periodic_solver()
    K = solver.mesh.K
    q0 = _rand_state(solver)
    ex = NestedPartitionExecutor(K, 3, grid_dims=solver.mesh.grid, bucket=bucket)
    eng = BlockedDGEngine(solver, ex)
    pipe = eng.pipeline()
    r_flat = np.asarray(solver.rhs(q0))
    r_unfused = np.asarray(eng.rhs(q0))
    r_fused = np.asarray(pipe.rhs(q0))
    assert (r_unfused == r_flat).all()
    assert (r_fused == r_unfused).all(), np.abs(r_fused - r_unfused).max()


@pytest.mark.parametrize("bucket", [4, 8])
def test_fused_run_bitwise_before_and_after_resplice(bucket):
    """The scan-compiled donated run matches the unfused step loop bitwise,
    then still does after the executor re-splices the block split."""
    solver = _periodic_solver()
    K = solver.mesh.K
    q0 = _rand_state(solver)
    dt = solver.cfl_dt()
    ex = NestedPartitionExecutor(K, 3, grid_dims=solver.mesh.grid, bucket=bucket)
    eng = BlockedDGEngine(solver, ex)

    q_fused = np.asarray(eng.run(q0, 3, dt=dt))
    q_unfused = np.asarray(_unfused_run(eng, q0, 3, dt))
    assert (q_fused == q_unfused).all(), np.abs(q_fused - q_unfused).max()

    # resplice: move work between partitions, then compare again
    ex.observe(np.array([0.02, 0.01, 0.01]))
    ex.rebalance()
    assert eng.pipeline().invalidate in ex._resplice_hooks  # hook wired
    q_fused2 = np.asarray(eng.run(q0, 3, dt=dt))
    q_unfused2 = np.asarray(_unfused_run(eng, q0, 3, dt))
    assert (q_fused2 == q_unfused2).all()
    # the resplice genuinely moved the split AND the pipeline saw it
    assert not np.array_equal(ex.counts, np.full(3, K // 3))


def test_fused_pipeline_with_pallas_kernels_interpret():
    """kernel_impl='interpret' threads BOTH Pallas kernels (volume + flux)
    through the fused program; fused == unfused bitwise."""
    solver = _periodic_solver(kernel_impl="interpret")
    K = solver.mesh.K
    q0 = _rand_state(solver)
    ex = NestedPartitionExecutor(K, 2, grid_dims=solver.mesh.grid, bucket=8)
    eng = BlockedDGEngine(solver, ex)
    pipe = eng.pipeline()
    r_unfused = np.asarray(eng.rhs(q0))
    r_fused = np.asarray(pipe.rhs(q0))
    assert (r_fused == r_unfused).all(), np.abs(r_fused - r_unfused).max()
    dt = solver.cfl_dt()
    q_fused = np.asarray(eng.run(q0, 2, dt=dt))
    q_unfused = np.asarray(_unfused_run(eng, q0, 2, dt))
    assert (q_fused == q_unfused).all()


def test_fused_run_is_one_dispatch_and_preserves_inputs():
    """run() advances n steps in ONE host dispatch, with the caller's buffer
    left intact despite the donated carry."""
    solver = make_two_tree_solver(grid=(6, 4, 4), order=2, extent=(2.0, 1.0, 1.0))
    q0 = gaussian_pulse(solver, center=(0.5, 0.5, 0.5))
    ex = NestedPartitionExecutor(96, 3, grid_dims=(6, 4, 4), bucket=8)
    eng = BlockedDGEngine(solver, ex)
    pipe = eng.pipeline()
    d0, s0 = pipe.dispatches, pipe.steps_run
    q1 = eng.run(q0, 5)
    assert pipe.dispatches == d0 + 1  # 5 steps, one dispatch
    assert pipe.steps_run == s0 + 5
    # q0 not consumed by donation
    assert np.isfinite(np.asarray(q0)).all()
    assert q1.shape == q0.shape


def test_fused_pipeline_batches_same_bucket_blocks():
    """Same-padded-size partitions land in ONE bucket (one launch), and the
    compiled program is reused when a resplice keeps the signature."""
    solver = _periodic_solver(grid=(4, 4, 4))
    K = solver.mesh.K  # 64 -> 4 partitions of 16, bucket 16: one bucket of 4
    ex = NestedPartitionExecutor(K, 4, grid_dims=solver.mesh.grid, bucket=16)
    eng = BlockedDGEngine(solver, ex)
    pipe = eng.pipeline()
    sig = pipe.bucket_signature
    assert sum(B for (_, _, B, _) in sig) == 4
    assert len(sig) < 4  # strictly fewer launches than blocks
    # a no-op resplice keeps the signature -> compiled run fn is reused
    n_fns = len(pipe._run_fns)
    q0 = _rand_state(solver)
    eng.run(q0, 2)
    assert len(pipe._run_fns) == n_fns + 1
    ex.set_accel_counts(None)  # forces a resplice with identical counts
    eng.run(q0, 2)
    assert pipe.bucket_signature == sig
    assert len(pipe._run_fns) == n_fns + 1  # no recompilation


def test_fused_observe_path_feeds_executor():
    """run(observe=True) stays fused: the in-scan accumulator + chunked
    wall time feed the executor one CalibrationReport per rebalance chunk
    (4 steps / chunks of 2 = 2 observations), and it rebalances on
    schedule."""
    solver = make_two_tree_solver(grid=(6, 4, 4), order=2, extent=(2.0, 1.0, 1.0))
    q0 = gaussian_pulse(solver, center=(0.5, 0.5, 0.5))
    ex = NestedPartitionExecutor(96, 3, grid_dims=(6, 4, 4), bucket=8,
                                 rebalance_every=2, smoothing=1.0)
    eng = BlockedDGEngine(solver, ex)
    q1 = eng.run(q0, 4, observe=True)
    assert ex._n_obs == 2 and ex.round >= 1
    assert eng.pipeline().stats.observe_chunks == 2
    assert np.isfinite(np.asarray(q1)).all()


def test_observe_report_straggler_moves_split():
    """The acceptance loop end to end: the chunk-boundary report enters the
    executor, the injected straggler inflates partition 0's observed
    seconds (inside observe — the single injection point) and the solved
    split visibly moves work off the straggler."""
    solver = make_two_tree_solver(grid=(6, 4, 4), order=2, extent=(2.0, 1.0, 1.0))
    q0 = gaussian_pulse(solver, center=(0.5, 0.5, 0.5))
    ex = NestedPartitionExecutor(96, 3, grid_dims=(6, 4, 4), bucket=8,
                                 rebalance_every=2, smoothing=1.0)
    eng = BlockedDGEngine(solver, ex)
    c0 = int(ex.counts[0])
    ex.inject_straggler(0, 8.0)
    eng.run(q0, 4, observe=True)
    assert ex.round >= 1
    assert int(ex.counts[0]) < c0, (c0, ex.counts)
    assert int(ex.counts.sum()) == 96


def test_observe_report_straggler_applied_exactly_once():
    """run_observed's report carries UNfactored times; observe applies the
    straggler multipliers — so a measure->observe round counts them exactly
    once (the executor invariant, now under the in-scan channel)."""
    solver = make_two_tree_solver(grid=(6, 4, 4), order=2, extent=(2.0, 1.0, 1.0))
    q0 = gaussian_pulse(solver, center=(0.5, 0.5, 0.5))
    ex = NestedPartitionExecutor(96, 3, grid_dims=(6, 4, 4), bucket=8,
                                 rebalance_every=0, smoothing=1.0)
    eng = BlockedDGEngine(solver, ex)
    pipe = eng.pipeline()
    price = np.array([1e-3, 2e-3, 3e-3])
    ex.inject_straggler(0, 5.0)
    _, report = pipe.run_observed(q0, 2, price=price, attribute_wall=False)
    # the channel itself is factor-free...
    np.testing.assert_allclose(report.step_s, price, rtol=1e-6)
    ex.observe_chunk(report, 2)
    # ...and the EWMA carries the factor exactly once
    np.testing.assert_allclose(ex._ewma, price * np.array([5.0, 1.0, 1.0]),
                               rtol=1e-6)


def test_observe_true_bitwise_identical_to_observe_false():
    """observe=True (chunked priced programs, mid-run resplices) yields q
    BITWISE identical to observe=False (one plain program): the priced
    family performs the same field arithmetic, the accumulator only rides
    the carry, and resplices preserve the trajectory."""
    solver = _periodic_solver()
    K = solver.mesh.K
    q0 = _rand_state(solver)
    dt = solver.cfl_dt()
    ex_a = NestedPartitionExecutor(K, 3, grid_dims=solver.mesh.grid, bucket=8,
                                   rebalance_every=2, smoothing=1.0)
    q_plain = np.asarray(BlockedDGEngine(solver, ex_a).run(q0, 6, dt=dt))
    ex_b = NestedPartitionExecutor(K, 3, grid_dims=solver.mesh.grid, bucket=8,
                                   rebalance_every=2, smoothing=1.0)
    q_obs = np.asarray(
        BlockedDGEngine(solver, ex_b).run(q0, 6, dt=dt, observe=True)
    )
    assert (q_plain == q_obs).all(), np.abs(q_plain - q_obs).max()


def test_scatter_base_hoisted_across_calls():
    """The (K+1,...) scatter target is built once per resplice, not per rhs
    evaluation."""
    solver = make_two_tree_solver(grid=(4, 2, 2), order=2)
    ex = NestedPartitionExecutor(16, 2, grid_dims=(4, 2, 2), bucket=4)
    eng = BlockedDGEngine(solver, ex)
    q0 = gaussian_pulse(solver, center=(1.0, 0.5, 0.5))
    base1 = eng.scatter_base(q0)
    eng.rhs(q0)
    base2 = eng.scatter_base(q0)
    assert base1 is base2
    assert base1.shape == (17, 9, 3, 3, 3)


# ---------------------------------------------------------------------------
# the flux kernel reached from the solver (satellite: dg_flux wiring)
# ---------------------------------------------------------------------------


def test_flux_kernel_reachable_from_solver():
    """kernel_impl='interpret' routes surface_rhs through dg_flux_pallas;
    the solver rhs stays allclose to the jnp reference path."""
    s_x = make_two_tree_solver(grid=(4, 2, 2), order=3)
    s_i = make_two_tree_solver(grid=(4, 2, 2), order=3, kernel_impl="interpret")
    q = gaussian_pulse(s_x, center=(1.0, 0.5, 0.5))
    np.testing.assert_allclose(s_x.rhs(q), s_i.rhs(q), rtol=1e-10, atol=1e-12)


def test_surface_rhs_interpret_matches_xla_on_periodic():
    from repro.dg.operators import surface_rhs

    solver = _periodic_solver(order=2)
    q = _rand_state(solver, seed=5)
    a = surface_rhs(q, solver.neighbors, solver.lift, solver.rho_j, solver.lam_j,
                    solver.mu_j, solver.cp_j, solver.cs_j)
    b = surface_rhs(q, solver.neighbors, solver.lift, solver.rho_j, solver.lam_j,
                    solver.mu_j, solver.cp_j, solver.cs_j, kernel_impl="interpret")
    np.testing.assert_allclose(a, b, rtol=1e-10, atol=1e-12)


# ---------------------------------------------------------------------------
# dispatch-count regression: the hot path must never re-Python-loop
# ---------------------------------------------------------------------------


def _wrap_counting(cache, key, fn):
    """Replace a cached compiled callable with a call-counting wrapper;
    returns the counter list."""
    calls = []

    def wrapper(*a, **k):
        calls.append(1)
        return fn(*a, **k)

    cache[key] = wrapper
    return calls


def test_dispatch_count_fused_run_one_per_run():
    """run() is ONE invocation of ONE compiled program, for every horizon —
    counted on the compiled callable itself, so a future edit that quietly
    re-Python-loops the step driver fails here."""
    solver = _periodic_solver()
    K = solver.mesh.K
    q0 = _rand_state(solver)
    ex = NestedPartitionExecutor(K, 3, grid_dims=solver.mesh.grid, bucket=8)
    eng = BlockedDGEngine(solver, ex)
    pipe = eng.pipeline()
    sig = pipe.bucket_signature
    run_calls = _wrap_counting(pipe._run_fns, sig, pipe._run_fn(sig))
    step_calls = _wrap_counting(pipe._step_fns, sig, pipe._step_fn(sig))
    for n in (1, 4, 9):
        before = len(run_calls)
        d0 = pipe.dispatches
        eng.run(q0, n)
        assert len(run_calls) - before == 1, (n, len(run_calls) - before)
        assert len(step_calls) == 0  # never falls back to per-step stepping
        assert pipe.dispatches - d0 == 1
        # inside the one compiled program: exactly one volume + one surface
        # kernel launch per rhs evaluation (the envelope layout's invariant,
        # counted at trace time on the DispatchStats ledger)
        assert pipe.stats.kernel_launches == {"volume": 1, "surface": 1}
    assert pipe.stats.dispatches_per_step < 1.0


def test_dispatch_count_observe_path_one_per_chunk():
    """run(observe=True) costs exactly ONE dispatch of the priced compiled
    program per rebalance chunk — never one per step, never a fallback to
    per-step stepping — counted on the compiled callables themselves."""
    solver = make_two_tree_solver(grid=(6, 4, 4), order=2, extent=(2.0, 1.0, 1.0))
    q0 = gaussian_pulse(solver, center=(0.5, 0.5, 0.5))
    ex = NestedPartitionExecutor(96, 3, grid_dims=(6, 4, 4), bucket=8,
                                 rebalance_every=2, smoothing=1.0)
    eng = BlockedDGEngine(solver, ex)
    pipe = eng.pipeline()
    sig = pipe.bucket_signature
    step_calls = _wrap_counting(pipe._step_fns, sig, pipe._step_fn(sig))
    run_calls = _wrap_counting(pipe._run_fns, sig, pipe._run_fn(sig))
    priced_calls = _wrap_counting(pipe._priced_run_fns, sig,
                                  pipe._priced_run_fn(sig))
    eng.run(q0, 6, observe=True)
    assert len(priced_calls) == 3  # 6 steps / chunks of 2, by the ledger too
    assert pipe.stats.observe_chunks == 3 and pipe.stats.steps_run == 6
    assert len(step_calls) == 0 and len(run_calls) == 0
    # the priced program carries exactly one launch of each kernel
    assert pipe.stats.kernel_launches == {"volume": 1, "surface": 1}
    # rebalance_every=0 disables the schedule: the whole horizon is ONE
    # observed chunk (one dispatch, one report)
    ex2 = NestedPartitionExecutor(96, 3, grid_dims=(6, 4, 4), bucket=8,
                                  rebalance_every=0)
    eng2 = BlockedDGEngine(solver, ex2)
    pipe2 = eng2.pipeline()
    sig2 = pipe2.bucket_signature
    priced2 = _wrap_counting(pipe2._priced_run_fns, sig2,
                             pipe2._priced_run_fn(sig2))
    eng2.run(q0, 4, observe=True)
    assert len(priced2) == 1 and ex2._n_obs == 1


# ---------------------------------------------------------------------------
# hypothesis properties: random shapes, buckets, resplice sequences
# ---------------------------------------------------------------------------


def _scatter_coverage(eng):
    """The fused scatter rows must cover each element exactly once (dump row
    K excluded) — the disjointness that makes bucket batching exact."""
    K = eng.solver.mesh.K
    rows = np.concatenate(
        [np.asarray(b["scat"]) for b in eng._blocks if b is not None]
    )
    real = rows[rows < K]
    assert len(np.unique(real)) == len(real), "overlapping scatter rows"
    assert set(real.tolist()) == set(range(K)), "scatter rows miss elements"
    assert (rows[rows >= K] == K).all()  # pad rows all hit the dump row


@settings(max_examples=8, deadline=None)
@given(st.integers(2, 4), st.integers(1, 3), st.integers(1, 3),
       st.integers(1, 4), st.sampled_from([2, 4, 8, 16]))
def test_fused_pipeline_property_random_mesh_and_buckets(nx, ny, nz, P, bucket):
    """Property: for randomized mesh shapes, partition counts and bucket
    sizes, the fused pipeline stays bitwise-identical to the unfused
    schedule and its scatter rows cover the field disjointly."""
    grid = (nx, ny, nz)
    mesh = make_brick(grid, (1.0, 1.0, 0.5), periodic=True)
    K = mesh.K
    P = min(P, K)
    solver = DGSolver(mesh=mesh, order=1, rho=np.ones(K), lam=np.ones(K),
                      mu=np.zeros(K))
    ex = NestedPartitionExecutor(K, P, grid_dims=grid, bucket=bucket)
    eng = BlockedDGEngine(solver, ex)
    pipe = eng.pipeline()
    _scatter_coverage(eng)
    q0 = _rand_state(solver, seed=nx * 100 + ny * 10 + nz + P + bucket)
    r_fused = np.asarray(pipe.rhs(q0))
    r_unfused = np.asarray(eng.rhs(q0))
    assert (r_fused == r_unfused).all(), np.abs(r_fused - r_unfused).max()


@settings(max_examples=6, deadline=None)
@given(st.lists(st.lists(st.floats(0.2, 5.0), min_size=3, max_size=3),
                min_size=1, max_size=4),
       st.sampled_from([4, 8]))
def test_fused_pipeline_property_resplice_sequences(times_seq, bucket):
    """Property: any sequence of observe->rebalance resplices preserves
    fused==unfused bitwise equality and disjoint scatter coverage."""
    solver = _periodic_solver(grid=(4, 4, 2), order=1)
    K = solver.mesh.K
    ex = NestedPartitionExecutor(K, 3, grid_dims=solver.mesh.grid,
                                 bucket=bucket, smoothing=1.0)
    eng = BlockedDGEngine(solver, ex)
    pipe = eng.pipeline()
    q0 = _rand_state(solver, seed=bucket)
    for times in times_seq:
        ex.observe(np.asarray(times))
        ex.rebalance()
        _scatter_coverage(eng)
        r_fused = np.asarray(pipe.rhs(q0))
        r_unfused = np.asarray(eng.rhs(q0))
        assert (r_fused == r_unfused).all(), np.abs(r_fused - r_unfused).max()
        assert int(ex.counts.sum()) == K


def test_fused_pipeline_grouped_buckets_stay_bitwise():
    """A partition->group map splits buckets under layout="grouped"
    (same-profile cluster batching) without changing the arithmetic:
    grouped fused == envelope fused == unfused, bitwise, and the grouped
    signature separates the groups while the envelope stays one bucket."""
    solver = _periodic_solver(grid=(4, 4, 4))
    K = solver.mesh.K
    ex = NestedPartitionExecutor(K, 4, grid_dims=solver.mesh.grid, bucket=16)
    eng = BlockedDGEngine(solver, ex)
    plain = eng.pipeline()
    grouped = eng.pipeline(groups=[0, 1, 0, 1], layout="grouped")
    gids = sorted(set(g for (_, _, _, g) in grouped.bucket_signature))
    assert gids == [0, 1]
    assert len(grouped.bucket_signature) > len(plain.bucket_signature)
    q0 = _rand_state(solver)
    r_plain = np.asarray(plain.rhs(q0))
    r_grouped = np.asarray(grouped.rhs(q0))
    r_unfused = np.asarray(eng.rhs(q0))
    assert (r_plain == r_unfused).all()
    assert (r_grouped == r_unfused).all()


# ---------------------------------------------------------------------------
# envelope layout: one volume + one surface launch regardless of the split
# ---------------------------------------------------------------------------


def _uneven_engine(kernel_impl="xla", order=2, weights=(5.0, 1.0, 1.0, 1.0),
                   grid=(4, 4, 4), bucket=8):
    """An engine whose split lands in MULTIPLE buckets under the grouped
    layout (uneven weights -> distinct padded sizes)."""
    mesh = make_brick(grid, (1.0, 1.0, 1.0), periodic=True)
    K = mesh.K
    solver = DGSolver(mesh=mesh, order=order, rho=np.ones(K), lam=np.ones(K),
                      mu=np.zeros(K), kernel_impl=kernel_impl)
    ex = NestedPartitionExecutor(K, len(weights), grid_dims=grid, bucket=bucket)
    ex.apply(ex.solve(list(weights)))
    return solver, BlockedDGEngine(solver, ex)


@pytest.mark.parametrize("kernel_impl", ["xla", "interpret"])
def test_envelope_collapses_multibucket_split_to_one_launch(kernel_impl):
    """The tentpole invariant: an uneven split that the grouped layout
    batches into MULTIPLE launch pairs compiles to exactly ONE volume + ONE
    surface launch per rhs under the envelope layout — bitwise identical to
    both the grouped path and the unfused schedule."""
    order = 1 if kernel_impl == "interpret" else 2
    solver, eng = _uneven_engine(kernel_impl=kernel_impl, order=order)
    env = eng.pipeline()
    grp = eng.pipeline(layout="grouped")
    assert len(grp.bucket_signature) > 1  # the split is genuinely ragged
    assert len(env.bucket_signature) == 1
    assert sum(B for (_, _, B, _) in env.bucket_signature) == sum(
        B for (_, _, B, _) in grp.bucket_signature
    )
    q0 = _rand_state(solver)
    r_env = np.asarray(env.rhs(q0))
    r_grp = np.asarray(grp.rhs(q0))
    r_unf = np.asarray(eng.rhs(q0))
    assert (r_env == r_unf).all(), np.abs(r_env - r_unf).max()
    assert (r_grp == r_unf).all()
    assert env.stats.kernel_launches == {"volume": 1, "surface": 1}
    assert grp.stats.kernel_launches["volume"] == len(grp.bucket_signature)
    # the fused run trajectory agrees too (scan over stages, donated carry)
    dt = solver.cfl_dt()
    q_env = np.asarray(env.run(q0, 3, dt=dt))
    q_grp = np.asarray(grp.run(q0, 3, dt=dt))
    q_unf = np.asarray(_unfused_run(eng, q0, 3, dt))
    assert (q_env == q_unf).all()
    assert (q_grp == q_unf).all()
    assert env.stats.kernel_launches == {"volume": 1, "surface": 1}


@pytest.mark.parametrize("kernel_impl", ["xla", "interpret"])
@pytest.mark.parametrize("split", ["giant", "singletons"])
def test_envelope_degenerate_splits_bitwise(kernel_impl, split):
    """Degenerate extremes: one giant bucket (P=1 holds everything) and P
    singleton partitions (bucket=1 -> every block its own size class) both
    stay bitwise under the envelope layout: the single rhs vs the unfused
    path, and the multi-step trajectory vs the per-bucket-group (grouped)
    fused path.  (The trajectory reference is the grouped FUSED run, not the
    eager per-step loop: on some tiny meshes XLA fuses the compiled scan
    differently from the per-step jit — an FMA artifact shared by every
    fused layout — while envelope vs grouped is exactly the batching change
    this test pins.)"""
    grid = (2, 2, 2) if split == "singletons" else (4, 4, 2)
    mesh = make_brick(grid, (1.0, 1.0, 0.5), periodic=True)
    K = mesh.K
    solver = DGSolver(mesh=mesh, order=1, rho=np.ones(K), lam=np.ones(K),
                      mu=np.zeros(K), kernel_impl=kernel_impl)
    if split == "giant":
        ex = NestedPartitionExecutor(K, 1, grid_dims=grid, bucket=8)
    else:
        ex = NestedPartitionExecutor(K, K, grid_dims=grid, bucket=1)
    eng = BlockedDGEngine(solver, ex)
    env = eng.pipeline()
    grp = eng.pipeline(layout="grouped")
    assert len(env.bucket_signature) == 1
    q0 = _rand_state(solver, seed=7)
    r_env = np.asarray(env.rhs(q0))
    r_unf = np.asarray(eng.rhs(q0))
    assert (r_env == r_unf).all(), np.abs(r_env - r_unf).max()
    assert env.stats.kernel_launches == {"volume": 1, "surface": 1}
    dt = solver.cfl_dt()
    q_env = np.asarray(env.run(q0, 2, dt=dt))
    q_grp = np.asarray(grp.run(q0, 2, dt=dt))
    assert (q_env == q_grp).all(), np.abs(q_env - q_grp).max()
    assert env.stats.kernel_launches == {"volume": 1, "surface": 1}


@settings(max_examples=8, deadline=None)
@given(st.lists(st.floats(0.2, 6.0), min_size=2, max_size=4),
       st.sampled_from([1, 2, 4, 8]))
def test_envelope_property_random_splits_bitwise(weights, bucket):
    """Property: ANY random bucket split — whatever ragged mix of padded
    sizes the weights produce — collapses to one launch pair under the
    envelope layout and keeps the q trajectory bitwise identical to the
    grouped reference."""
    grid = (4, 4, 2)
    mesh = make_brick(grid, (1.0, 1.0, 0.5), periodic=True)
    K = mesh.K
    solver = DGSolver(mesh=mesh, order=1, rho=np.ones(K), lam=np.ones(K),
                      mu=np.zeros(K))
    ex = NestedPartitionExecutor(K, len(weights), grid_dims=grid, bucket=bucket)
    ex.apply(ex.solve(list(weights)))
    eng = BlockedDGEngine(solver, ex)
    env = eng.pipeline()
    grp = eng.pipeline(layout="grouped")
    assert len(env.bucket_signature) == 1
    q0 = _rand_state(solver, seed=int(bucket + sum(w * 10 for w in weights)) % 97)
    r_env = np.asarray(env.rhs(q0))
    r_grp = np.asarray(grp.rhs(q0))
    assert (r_env == r_grp).all(), np.abs(r_env - r_grp).max()
    assert env.stats.kernel_launches == {"volume": 1, "surface": 1}
    dt = solver.cfl_dt()
    q_env = np.asarray(env.run(q0, 2, dt=dt))
    q_grp = np.asarray(grp.run(q0, 2, dt=dt))
    assert (q_env == q_grp).all()


def test_sharded_pipeline_single_device_mesh():
    """ShardedStepPipeline on a 1-device mesh (no fake-device flags needed):
    the same shard_map program structure, bitwise vs the flat solver, one
    dispatch per run — the in-process twin of tests/test_multidevice.py."""
    import jax

    from repro.dg.partitioned import PartitionedDG

    solver = _periodic_solver()
    q0 = _rand_state(solver)
    dt = solver.cfl_dt()
    mesh = make_mesh((1,), ("data",))
    pdg = PartitionedDG(solver=solver, mesh_axes=mesh)
    pipe = pdg.pipeline()
    qp = pdg.permute_in(q0)
    r_flat = np.asarray(jax.jit(solver.rhs)(q0))
    r_shard = pdg.permute_out(np.asarray(pipe.rhs(qp)))
    assert (r_flat == r_shard).all(), np.abs(r_flat - r_shard).max()
    q_flat = np.asarray(solver.run(q0, 3, dt))
    d0 = pipe.dispatches
    q_shard = pdg.permute_out(np.asarray(pipe.run(qp, 3, dt=dt)))
    assert pipe.dispatches - d0 == 1 and pipe.steps_run >= 3
    assert (q_flat == q_shard).all(), np.abs(q_flat - q_shard).max()
    # the eager reference driver and the fused step agree with the program
    q_eager = pdg.permute_out(np.asarray(pdg.run(qp, 3, dt=dt, fused=False)))
    assert (q_eager == q_shard).all()
    # donated single fused step consumes its operands but not the original
    res = jnp.zeros_like(qp)
    q1, res1 = pipe.step(pipe._sharded_copy(qp), pipe._sharded_copy(res), dt)
    assert np.isfinite(np.asarray(q1)).all()
    assert np.isfinite(np.asarray(qp)).all()


def test_sharded_run_observed_in_scan_channel():
    """ShardedStepPipeline.run_observed on a 1-device mesh: per-shard
    accumulators psum-reduced inside the program, q bitwise vs the plain
    fused run, and the deterministic (attribute_wall=False) report carries
    the price itself; the wall-attributed report sums to positive
    seconds."""
    import jax

    from repro.dg.partitioned import PartitionedDG

    solver = _periodic_solver()
    q0 = _rand_state(solver)
    dt = solver.cfl_dt()
    mesh = make_mesh((1,), ("data",))
    pdg = PartitionedDG(solver=solver, mesh_axes=mesh)
    pipe = pdg.pipeline()
    qp = pdg.permute_in(q0)
    q_plain = np.asarray(pipe.run(qp, 3, dt=dt))
    price = np.array([2e-3])
    q_obs, report = pipe.run_observed(qp, 3, dt=dt, price=price,
                                      attribute_wall=False)
    assert (np.asarray(q_obs) == q_plain).all()
    np.testing.assert_allclose(report.step_s, price, rtol=1e-6)
    q_obs2, report2 = pipe.run_observed(qp, 3, dt=dt)
    assert (np.asarray(q_obs2) == q_plain).all()
    assert (np.asarray(report2.step_s) > 0).all()
    assert pipe.stats.observe_chunks == 2
    # observe=True through PartitionedDG.run: one report per chunk feeds
    # the bound executor
    ex = pdg.bind_executor(pdg.make_executor(rebalance_every=2))
    q3 = pdg.run(qp, 4, dt=dt, observe=True)
    assert ex._n_obs == 2 and np.isfinite(np.asarray(q3)).all()


def test_fused_run_priced_accumulates_in_scan():
    """run(price=...) returns the same field as the unpriced run plus the
    per-partition cost accumulated inside the compiled loop (price * n)."""
    solver = _periodic_solver()
    K = solver.mesh.K
    q0 = _rand_state(solver)
    dt = solver.cfl_dt()
    ex = NestedPartitionExecutor(K, 3, grid_dims=solver.mesh.grid, bucket=8)
    eng = BlockedDGEngine(solver, ex)
    pipe = eng.pipeline()
    price = np.array([1e-3, 2e-3, 3e-3])
    q_plain = np.asarray(pipe.run(q0, 4, dt=dt))
    q_priced, acc = pipe.run(q0, 4, dt=dt, price=price)
    assert (np.asarray(q_priced) == q_plain).all()
    np.testing.assert_allclose(np.asarray(acc), price * 4, rtol=1e-12)


@settings(max_examples=6, deadline=None)
@given(st.lists(st.floats(1e-4, 5e-3), min_size=3, max_size=3),
       st.lists(st.integers(1, 3), min_size=1, max_size=4))
def test_priced_accumulator_chunking_property(price, chunks):
    """Property: splitting an observed run into arbitrary rebalance chunks
    preserves the accumulated totals — the sum over chunks of each chunk's
    in-scan accumulator equals the per-step sum ``price * n`` and the
    single-run accumulator (allclose, not bitwise: float addition order
    differs across chunk boundaries)."""
    solver = _periodic_solver(grid=(4, 2, 2), order=1)
    K = solver.mesh.K
    ex = NestedPartitionExecutor(K, 3, grid_dims=solver.mesh.grid, bucket=4)
    eng = BlockedDGEngine(solver, ex)
    pipe = eng.pipeline()
    q0 = _rand_state(solver)
    dt = solver.cfl_dt()
    price = np.asarray(price)
    n = sum(chunks)
    total = np.zeros(len(price))
    q = q0
    for c in chunks:
        q, acc = pipe.run(q, c, dt=dt, price=price)
        total += np.asarray(acc)
    _, acc_single = pipe.run(q0, n, dt=dt, price=price)
    np.testing.assert_allclose(total, np.asarray(acc_single), rtol=1e-9)
    np.testing.assert_allclose(total, price * n, rtol=1e-9)
