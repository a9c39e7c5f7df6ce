"""The observed nested run: chunks that end on the rebalance schedule from
any starting step, the counters of the calibrate->solve->resplice loop, and
a ragged split under an injected straggler that changes nothing of the
solution (against the benchmark's plain reference)."""

import json
import os
import sys

import numpy as np
import pytest

from repro.dg.solver import gaussian_pulse, make_two_tree_solver
from repro.runtime.executor import BlockedDGEngine, NestedPartitionExecutor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench")


def _engine(rebalance_every=10, weights=None, straggler=None, grid=(6, 4, 4)):
    solver = make_two_tree_solver(grid=grid, order=2, extent=(2.0, 1.0, 1.0))
    ex = NestedPartitionExecutor(solver.mesh.K, 3, grid_dims=grid, bucket=8,
                                 rebalance_every=rebalance_every, smoothing=1.0,
                                 initial_weights=weights)
    if straggler is not None:
        ex.inject_straggler(0, straggler)
    return BlockedDGEngine(solver, ex), gaussian_pulse(solver, center=(0.5, 0.5, 0.5))


def test_next_chunk_ends_on_the_schedule():
    ex = NestedPartitionExecutor(96, 3, grid_dims=(6, 4, 4), rebalance_every=10)
    assert ex.next_chunk(25) == 10 and ex.next_chunk(4) == 4
    ex._step = 3
    assert ex.next_chunk(25) == 7 and ex.next_chunk(5) == 5
    ex._step = 10
    assert ex.next_chunk(25) == 10
    ex.rebalance_every = 0
    assert ex.next_chunk(25) == 25


@pytest.mark.parametrize("first,then,chunks,rebalances", [
    (1, 10, 3, 1),   # 1 | 9 | 1: fires at step 10
    (3, 17, 3, 2),   # 3 | 7, 10: fires at 10 and 20
    (10, 10, 2, 2),  # aligned from the start
])
def test_observed_run_rebalances_on_schedule_from_any_start(first, then, chunks, rebalances):
    """A run that does not start on a multiple of ``rebalance_every`` still
    rebalances every ``rebalance_every`` steps: each chunk ends on the next
    boundary, so the executor's step counter lands on it."""
    eng, q0 = _engine(rebalance_every=10)
    ex, pipe = eng.executor, eng.pipeline()
    q = eng.run(q0, first, observe=True)
    q = eng.run(q, then, observe=True)
    assert ex._step == first + then
    assert pipe.stats.observe_chunks == chunks
    assert ex.rebalances == rebalances
    assert np.isfinite(np.asarray(q)).all()


def test_counters_count_what_they_name():
    """``rebalances`` counts calibration-loop rounds and ``plans_changed``
    those that moved the split; ``table_builds`` counts the pipeline's
    table builds, one per resplice a dispatch sees, and ``programs_built``
    the step-loop programs traced, one per (family, bucket signature)."""
    # from a uniform split the 8x straggler moves work once; after that the
    # observed rates reproduce the solved weights and the plan stands still
    eng, q0 = _engine(rebalance_every=2, straggler=8.0)
    ex, pipe = eng.executor, eng.pipeline()
    before = ex.counts.copy()
    q = eng.run(q0, 6, observe=True)
    assert ex.rebalances == 3 and ex.round == 3
    assert ex.plans_changed == 1 and not np.array_equal(ex.counts, before)
    assert pipe.stats.observe_chunks == 3
    # the first dispatch builds the tables, and each later chunk follows a
    # resplice; the third chunk's resplice is not yet seen
    assert pipe.stats.table_builds == 3
    # the priced family, once per signature its chunks ran
    assert pipe.stats.programs_built == len(pipe._priced_run_fns) >= 1

    # the split solved above, resumed: every rebalance keeps it
    eng, q0 = _engine(rebalance_every=2, straggler=8.0, weights=ex.weights)
    ex2, pipe2 = eng.executor, eng.pipeline()
    q = eng.run(q0, 6, observe=True)
    assert ex2.rebalances == 3 and ex2.plans_changed == 0
    np.testing.assert_array_equal(ex2.counts, ex.counts)
    assert pipe2.stats.programs_built == 1
    assert pipe2.stats.table_builds == 3
    # the plain family is a program of its own, traced once and then reused
    q = eng.run(q, 2)
    q = eng.run(q, 3)
    assert pipe2.stats.programs_built == 2
    assert pipe2.stats.table_builds == 4
    assert np.isfinite(np.asarray(q)).all()


def _bench_modules():
    for p in (BENCH, os.path.join(BENCH, "drivers")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import inputs
    from _solver import build_solver
    from reference import dgsem

    return inputs, build_solver, dgsem


def test_observed_ragged_run_equals_observe_off_and_the_reference():
    """The deployment of the ``dg-paper-skewed`` configuration at a small
    size: a ragged split under the injected straggler, run observed (one
    priced program per chunk, a re-solve and a resplice after each), equals
    the observe-off run on the same split bitwise, and agrees with the
    benchmark's plain reference within the cell's limit."""
    inputs, build_solver, dgsem = _bench_modules()
    with open(os.path.join(BENCH, "configs", "dg-paper-skewed.json")) as f:
        cfg = dict(json.load(f), order=2, grid=[16, 4, 4], extent=[4.0, 1.0, 1.0])
    with open(os.path.join(BENCH, "traffic", "observed.json")) as f:
        traffic = json.load(f)
    with open(os.path.join(BENCH, "limits", "dg-paper-skewed.observed.json")) as f:
        limit = json.load(f)["field_err"]["limit"]
    node = cfg["node"]
    prob = dgsem.Problem(cfg)
    q0_ref = inputs.initial_field(prob, 2**31 + 5, traffic)
    q0 = np.asarray(q0_ref).transpose(4, 0, 1, 2, 3)
    steps = 25  # 10 | 10 | 5: two rebalances, then a chunk cut short

    def run(observe):
        solver = build_solver(cfg, "xla")
        ex = NestedPartitionExecutor(
            solver.mesh.K, node["partitions"], grid_dims=solver.mesh.grid,
            initial_weights=node["initial_weights"], bucket=node["bucket"],
            rebalance_every=node["rebalance_every"])
        ex.inject_straggler(node["straggler"]["partition"], node["straggler"]["factor"])
        counts = ex.counts.copy()
        q = np.asarray(BlockedDGEngine(solver, ex).run(q0, steps, dt=prob.dt,
                                                       observe=observe))
        np.testing.assert_array_equal(ex.counts, counts)
        return q, ex

    q_obs, ex = run(True)
    assert ex.rebalances == 2 and ex.plans_changed == 0
    assert len(set(ex.counts.tolist())) > 1  # ragged
    q_off, _ = run(False)
    assert (q_obs == q_off).all(), np.abs(q_obs - q_off).max()
    q_ref = np.asarray(dgsem.make_run(prob)(q0_ref, steps)).transpose(4, 0, 1, 2, 3)
    err = float(np.max(np.abs(q_obs - q_ref)) / np.max(np.abs(q_ref)))
    assert err <= limit, err
