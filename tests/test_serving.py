"""Continuous-batching serving loop + Engine-protocol conformance.

Covers the PR-6 acceptance bars:
  * a request spliced into a running loop yields the bitwise-identical
    token row it gets in a fresh one-shot batch;
  * the loop stays fused — exactly one dispatch per decode chunk
    (``DispatchStats``);
  * SLO accounting degrades (and sheds appear) under an injected straggler
    partition;
  * shed rate is monotone in offered load and zero at sub-capacity load;
  * all four engines (`DGSolver`, `PartitionedDG`, `BlockedDGEngine`,
    `SimulatedCluster`) satisfy the shared ``Engine`` protocol.
"""

import numpy as np
import pytest

from repro.runtime import Engine
from repro.runtime.schedule import CalibrationReport
from repro.runtime.serving import (
    SLO,
    ContinuousBatchingLoop,
    ServeKernels,
    build_lm,
    decode_batch,
    poisson_trace,
)

PROMPT_LEN = 8
MAX_NEW = 4


@pytest.fixture(scope="module")
def served():
    """One built model + kernel set shared by every loop test (compiles are
    the expensive part; the loop itself is cheap)."""
    cfg, lm, params, mesh = build_lm("qwen2-7b", smoke=True, seed=0)
    kernels = ServeKernels(lm, mesh, max_len=PROMPT_LEN + MAX_NEW)
    return cfg, kernels, params


def _report(p=1, prefill=0.010, decode=0.020):
    """Synthetic phase-resolved calibration: fully deterministic pricing
    (decode seconds are for calib_gen-1 = 2 steps at the calibrated
    counts)."""
    return CalibrationReport(
        boundary_s=np.full(p, prefill),
        interior_s=np.full(p, decode),
        transfer_s=np.zeros(p),
    )


def _trace(cfg, n, rate, seed=3, max_new=MAX_NEW):
    return poisson_trace(
        n, rate, prompt_len=PROMPT_LEN, vocab=cfg.vocab_size,
        max_new=max_new, seed=seed,
    )


# ---------------------------------------------------------------------------
# continuous batching
# ---------------------------------------------------------------------------


def test_splice_bitwise_and_fused(served):
    """Requests admitted mid-loop (capacity 2, 6 requests -> 4 refills)
    produce bitwise the token rows of fresh one-shot batches, and every
    decode chunk is exactly one fused dispatch."""
    cfg, kernels, params = served
    trace = _trace(cfg, 6, rate=2.0)
    loop = ContinuousBatchingLoop(
        kernels, params, capacity=2, chunk=2, calib_gen=3,
        report=_report(), slo=SLO(ttft_s=1e9, tok_s=1e9),
    )
    summary = loop.run(trace)
    assert summary.n_done == 6 and summary.n_shed == 0

    # the loop never un-fuses: 1 dispatch per decode chunk, by ledger
    assert summary.dispatches_per_chunk == 1.0
    assert loop.stats.dispatches == loop.n_chunks
    assert loop.n_chunks >= 6 * (MAX_NEW - 1) / 2 / 2  # >= total work / (chunk*capacity)

    # one-shot reference at the loop's batch width: row independence means
    # each request's row is identical whether its neighbours are other live
    # requests (loop) or any other rows (fresh batch)
    for a, b in [(0, 1), (2, 3), (4, 5)]:
        block = np.stack([trace[a].prompt, trace[b].prompt])
        ref, _, _ = decode_batch(kernels, params, block, MAX_NEW)
        assert trace[a].tokens == ref[0].tolist(), f"rid {a} diverged"
        assert trace[b].tokens == ref[1].tolist(), f"rid {b} diverged"

    # SLO ledger is complete for served requests
    for r in trace:
        assert r.state == "done"
        assert r.arrival_s <= r.admitted_s <= r.first_token_s <= r.done_s
        assert len(r.tokens) == MAX_NEW


def test_splice_mid_loop_vs_solo_batch(served):
    """The stronger form: a late request decoded alongside an in-flight one
    matches its own solo one-shot run bitwise (cross-batch-composition
    invariance of the row)."""
    cfg, kernels, params = served
    trace = _trace(cfg, 4, rate=5.0, seed=11)
    loop = ContinuousBatchingLoop(
        kernels, params, capacity=2, chunk=2, calib_gen=3,
        report=_report(), slo=SLO(ttft_s=1e9, tok_s=1e9),
    )
    loop.run(trace)
    for r in trace:
        solo, _, _ = decode_batch(kernels, params, r.prompt[None, :], MAX_NEW)
        assert r.tokens == solo[0].tolist(), f"rid {r.rid} != solo run"


def test_straggler_inflates_slo_accounting(served):
    """Injecting a straggler partition into the executor inflates the
    modeled pricing: virtual-clock TTFT/latency grow and the (tight) SLO
    starts shedding requests that the healthy fleet serves."""
    cfg, kernels, params = served

    def run_with(factor):
        loop = ContinuousBatchingLoop(
            kernels, params, capacity=2, chunk=2, partitions=2, calib_gen=3,
            report=_report(p=2), slo=SLO(ttft_s=0.5, tok_s=1e9),
        )
        loop.executor.inject_straggler(0, factor)
        trace = _trace(cfg, 8, rate=50.0, seed=7)
        summary = loop.run(trace)
        return summary

    healthy = run_with(1.0)
    slow = run_with(40.0)
    assert healthy.ttft_p50_s < slow.ttft_p50_s or healthy.n_shed < slow.n_shed
    assert slow.elapsed_s > healthy.elapsed_s  # straggler slows the virtual fleet
    # deterministic virtual clock: the healthy run is reproducible exactly
    again = run_with(1.0)
    assert again.to_dict() == healthy.to_dict()


def test_shed_rate_monotone_in_offered_load(served):
    """Same seed, rising offered load -> the same arrival pattern
    compressed -> shed rate must be monotone, and zero at sub-capacity."""
    cfg, kernels, params = served

    def shed_rate(load_rps):
        loop = ContinuousBatchingLoop(
            kernels, params, capacity=2, chunk=2, calib_gen=3,
            report=_report(), slo=SLO(ttft_s=0.2, tok_s=1e9),
        )
        trace = _trace(cfg, 10, rate=load_rps, seed=5)
        return loop.run(trace).shed_rate

    rates = [shed_rate(r) for r in (2.0, 50.0, 500.0)]
    assert rates[0] == 0.0  # sub-capacity: nothing shed
    assert rates[0] <= rates[1] <= rates[2]
    assert rates[2] > 0.0  # heavy oversubscription does shed


def test_downgrade_trims_generation(served):
    """A finite latency budget downgrades (trims) requests instead of
    shedding them outright when at least min_new tokens still fit."""
    cfg, kernels, params = served
    loop = ContinuousBatchingLoop(
        kernels, params, capacity=2, chunk=2, calib_gen=3,
        report=_report(decode=0.2),
        slo=SLO(ttft_s=10.0, tok_s=1e9, latency_s=0.5, min_new=1),
    )
    trace = _trace(cfg, 4, rate=100.0, seed=9, max_new=MAX_NEW)
    summary = loop.run(trace)
    assert summary.n_downgraded > 0
    for r in trace:
        if r.state == "done" and r.downgraded:
            assert 1 <= len(r.tokens) < r.max_new


def test_trace_records_roundtrip(served, tmp_path):
    cfg, kernels, params = served
    loop = ContinuousBatchingLoop(
        kernels, params, capacity=2, chunk=2, calib_gen=3, report=_report(),
    )
    loop.run(_trace(cfg, 3, rate=2.0))
    path = tmp_path / "trace.json"
    loop.write_trace(str(path))
    import json

    rows = json.loads(path.read_text())
    assert len(rows) == 3
    assert {"rid", "state", "ttft_s", "latency_s", "n_tokens"} <= set(rows[0])


def test_rounds_loop_bitwise_and_ledgered(served):
    """--rounds acceptance bar: the row pool sharded across heterogeneous
    node groups and re-aggregated through the multi-round merge tree yields
    token rows BITWISE identical to the plain single-aggregator loop, at
    exactly one fused dispatch per worker per chunk (DispatchStats-gated),
    deterministic under the virtual clock."""
    from repro.runtime.cluster import NodeProfile
    from repro.runtime.rounds import workers_from_profiles

    cfg, kernels, params = served
    workers = workers_from_profiles(
        [NodeProfile(name="node", speed=2.0), NodeProfile(name="node", speed=1.0)]
    )

    def run_with(rounds):
        trace = _trace(cfg, 6, rate=2.0)
        loop = ContinuousBatchingLoop(
            kernels, params, capacity=4, chunk=2, calib_gen=3,
            report=_report(), slo=SLO(ttft_s=1e9, tok_s=1e9), rounds=rounds,
        )
        return loop, loop.run(trace), trace

    loop_r, summary_r, trace_r = run_with(workers)
    loop_p, summary_p, trace_p = run_with(None)

    # pool rows apportioned by calibrated speed (4 rows over 2:1 workers)
    assert loop_r.rounds_plan.counts_by_worker(0).tolist() == [3, 1]
    assert loop_r.n_round_workers == summary_r.n_round_workers == 2
    # the ledger: one fused dispatch per WORKER per chunk, nothing hidden
    assert summary_r.dispatches_per_chunk == 2.0
    assert loop_r.stats.dispatches == 2 * loop_r.n_chunks
    assert summary_p.dispatches_per_chunk == 1.0

    # bitwise: every request's token row identical across the two paths
    assert summary_r.n_done == summary_p.n_done == 6
    for a, b in zip(trace_r, trace_p):
        assert a.state == b.state == "done"
        assert a.tokens == b.tokens, f"rid {a.rid} diverged under --rounds"

    # deterministic under VirtualClock: a rerun reproduces the summary
    _, again, _ = run_with(workers)
    assert again.to_dict() == summary_r.to_dict()


def test_fully_shed_trace_serializes_strict_json(served, tmp_path):
    """Regression: a fully-shed trace has no TTFT/latency samples, so the
    percentiles are NaN — they must serialize as null (strict JSON), never
    as the bare NaN literal that breaks downstream parsers."""
    import json

    cfg, kernels, params = served
    loop = ContinuousBatchingLoop(
        kernels, params, capacity=2, chunk=2, calib_gen=3,
        report=_report(), slo=SLO(ttft_s=1e-9, tok_s=1e9),
    )
    trace = _trace(cfg, 4, rate=1000.0, seed=11)
    summary = loop.run(trace)
    assert summary.n_done == 0 and summary.n_shed == len(trace)
    assert np.isnan(summary.ttft_p50_s)  # in-process floats stay NaN...

    d = summary.to_dict()
    assert d["ttft_p50_s"] is None and d["ttft_p99_s"] is None  # ...JSON gets null
    text = json.dumps(d, allow_nan=False)  # strict mode must not raise
    assert "NaN" not in text
    assert json.loads(text)["ttft_p50_s"] is None

    path = tmp_path / "shed_trace.json"
    loop.write_trace(str(path))  # write_trace is allow_nan=False-gated too
    rows = json.loads(path.read_text())
    assert len(rows) == 4 and all(r["state"] == "shed" for r in rows)


# ---------------------------------------------------------------------------
# Engine protocol conformance
# ---------------------------------------------------------------------------


def _engines():
    """(name, engine, state) for all four execution engines on a tiny
    brick."""
    import jax

    from repro.dg.partitioned import PartitionedDG
    from repro.dg.solver import gaussian_pulse, make_two_tree_solver
    from repro.runtime import BlockedDGEngine, NestedPartitionExecutor, SimulatedCluster
    from repro.runtime.cluster import NodeProfile

    solver = make_two_tree_solver(grid=(4, 2, 2), order=2)
    q0 = gaussian_pulse(solver, width=0.25)

    out = [("DGSolver", solver, q0)]

    ex = NestedPartitionExecutor(solver.mesh.K, 2, grid_dims=solver.mesh.grid,
                                 bucket=4, rebalance_every=0)
    eng = BlockedDGEngine(solver, ex)
    out.append(("BlockedDGEngine", eng, q0))

    cl = SimulatedCluster(solver, [NodeProfile(speed=1.0), NodeProfile(speed=2.0)])
    out.append(("SimulatedCluster", cl, q0))

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("data",), devices=jax.devices()[:1])
    pdg = PartitionedDG(solver, mesh)
    out.append(("PartitionedDG", pdg, pdg.permute_in(q0)))
    return out


def test_engine_protocol_conformance():
    """All four engines satisfy the structural protocol AND behave: run
    accepts the unified keyword set, calibrate returns a CalibrationReport,
    resplice applies a plan without breaking a subsequent run."""
    for name, eng, q in _engines():
        assert isinstance(eng, Engine), f"{name} missing protocol methods"
        out = eng.run(q, 2, fused=True, observe=False)
        assert out.shape == q.shape, name
        out2 = eng.run(q, 2, fused=False, observe=False)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(out2), rtol=1e-10, atol=1e-12,
            err_msg=f"{name}: fused != eager",
        )
        rep = eng.calibrate(q)
        assert isinstance(rep, CalibrationReport), name
        assert np.all(rep.step_s >= 0), name

        executor = getattr(eng, "executor", None) or getattr(eng, "_executor", None)
        if executor is None and hasattr(eng, "bind_executor"):
            executor = eng.bind_executor()
        if executor is not None:
            plan = executor.solve(np.ones(executor.n_partitions))
            eng.resplice(plan)
        else:
            eng.resplice(None)  # flat solver: documented no-op
        out3 = eng.run(q, 2)
        assert out3.shape == q.shape, f"{name} broken after resplice"


def test_partitioned_dg_executor_kwarg_removed():
    """The pre-protocol PartitionedDG.run(executor=...) shim expired after
    its one-release window: the kwarg is gone, and the bind_executor +
    observe=True spelling is the only one."""
    import jax

    from repro.dg.partitioned import PartitionedDG
    from repro.dg.solver import gaussian_pulse, make_two_tree_solver
    from repro.launch.mesh import make_mesh

    solver = make_two_tree_solver(grid=(4, 2, 2), order=2)
    mesh = make_mesh((1,), ("data",), devices=jax.devices()[:1])
    pdg = PartitionedDG(solver, mesh)
    ex = pdg.make_executor(bucket=4, rebalance_every=0)
    q = pdg.permute_in(gaussian_pulse(solver, width=0.25))
    with pytest.raises(TypeError, match="executor"):
        pdg.run(q, 2, executor=ex)
    pdg.bind_executor(ex)
    out = pdg.run(q, 2, observe=True)
    assert out.shape == q.shape
    assert ex._n_obs >= 1  # the in-scan channel fed the bound executor


def test_decode_batch_uses_monotonic_clock(served, monkeypatch):
    """Regression: decode_batch must time with perf_counter, not the
    non-monotonic wall clock — under a clock that steps BACKWARD (NTP
    adjustment) its prefill/decode seconds stay non-negative."""
    import repro.runtime.serving as serving_mod

    import itertools

    cfg, kernels, params = served
    ticks = itertools.count()
    monkeypatch.setattr(
        serving_mod.time, "time", lambda: 1e9 - 10.0 * next(ticks)
    )
    rows = np.stack([_trace(cfg, 1, rate=1.0)[0].prompt])
    _, t_prefill, t_decode = decode_batch(kernels, params, rows, MAX_NEW)
    assert t_prefill >= 0.0 and t_decode >= 0.0


def test_loop_observes_every_decode_chunk(served):
    """The serving loop feeds the executor one chunk-grained observation
    per decode chunk (zero extra dispatches), so the calibrate→solve→
    resplice loop keeps running under load — and the deterministic virtual
    clock keeps the observations deterministic."""
    cfg, kernels, params = served
    trace = _trace(cfg, 4, rate=2.0)
    loop = ContinuousBatchingLoop(
        kernels, params, capacity=2, chunk=2, calib_gen=3,
        report=_report(), slo=SLO(ttft_s=1e9, tok_s=1e9),
    )
    summary = loop.run(trace)
    assert summary.n_done == 4
    n0 = loop.executor._n_obs
    assert loop.stats.observe_chunks == loop.n_chunks > 0
    assert n0 >= loop.n_chunks  # calibration obs + one per chunk
    assert loop.last_chunk_report is not None
    assert np.all(np.asarray(loop.last_chunk_report.step_s) >= 0)
    # still exactly one dispatch per decode chunk — observation is free
    assert summary.dispatches_per_chunk == 1.0


def test_list_scenarios_enumerates_everything():
    """--list-scenarios output covers every registered arch and scenario
    (the benchmark/CI entry points resolve through the same registry)."""
    from repro.configs.registry import (
        format_listing,
        list_archs,
        list_scenarios,
        resolve_arch,
        resolve_scenario,
    )

    listing = format_listing()
    archs, scenarios = list_archs(), list_scenarios()
    assert archs and scenarios
    for a in archs:
        assert a in listing
        assert resolve_arch(a).arch_id == a
    for s in scenarios:
        assert s in listing
        assert resolve_scenario(s).name == s
    # the scenarios CI/benchmarks use by name must exist
    assert {"dg-two-tree", "dg-smoke", "stampede-cluster"} <= set(scenarios)
    # scenario factories actually build
    sv = resolve_scenario("dg-smoke").build()
    assert sv.mesh.K == 4 * 2 * 2


def test_decode_chunk_fault_retried_without_unfusing(served):
    """A transient fault injected at a decode-chunk boundary is retried in
    place: the probe fires BEFORE the dispatch, so service is identical to
    a clean run and the loop stays one dispatch per chunk."""
    from repro.runtime import FailureInjector

    cfg, kernels, params = served

    def run_loop(injector=None, max_retries=1):
        loop = ContinuousBatchingLoop(
            kernels, params, capacity=2, chunk=2, calib_gen=3,
            report=_report(), slo=SLO(ttft_s=1e9, tok_s=1e9),
            injector=injector, max_retries=max_retries,
        )
        return loop, loop.run(_trace(cfg, 4, rate=2.0))

    loop, faulty = run_loop(FailureInjector({1: "transient"}))
    assert loop.chunk_retries == 1
    _, clean = run_loop()
    assert faulty.to_dict() == clean.to_dict()
    assert faulty.dispatches_per_chunk == 1.0


def test_decode_chunk_fault_escalates_past_max_retries(served):
    from repro.runtime import FailureInjector
    from repro.runtime.fault_tolerance import InjectedFailure

    cfg, kernels, params = served
    loop = ContinuousBatchingLoop(
        kernels, params, capacity=2, chunk=2, calib_gen=3,
        report=_report(), slo=SLO(ttft_s=1e9, tok_s=1e9),
        injector=FailureInjector({0: "node-loss"}),
        max_retries=0,
    )
    with pytest.raises(InjectedFailure):
        loop.run(_trace(cfg, 4, rate=2.0))
