"""The ``dg-paper-skewed.observed`` cell end to end on the CPU through the
harness's functions, at a size of its own whose converged split is ragged
(order 2, 16x4x4 elements: 48/80/64/64 under the configuration's weights
and bucket), interpret-mode kernels."""

import dataclasses
import json
import time

import jax
import pytest

import harness

NAME = "dg-paper-skewed.observed"
SIZE = dict(order=2, grid=[16, 4, 4], extent=[4.0, 1.0, 1.0])


def _run(trace: bool):
    c = harness.Cell.find(harness.load_spec(), NAME)
    c = dataclasses.replace(c, config=dict(c.config, **SIZE))
    lines = []
    res = harness.run_cell(c, 2**31 + 13, 0.2, trace, t_start=time.perf_counter(),
                           devices=jax.devices(), kernel_impl="interpret", cache=False,
                           log=lines.append)
    ledger = [json.loads(line[len("[ledger] "):]) for line in lines
              if line.startswith("[ledger] ")]
    return res, ledger[0]


@pytest.fixture(scope="module")
def untraced():
    return _run(False)


def test_skewed_cell_runs_correct_with_its_end_to_end_metrics(untraced):
    res, _ = untraced
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"elem_steps_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["attempted"] >= 2 and res["failed"] == 0


def test_skewed_window_rebalances_every_chunk_and_keeps_the_converged_split(untraced):
    res, led = untraced
    # the window's counts: the warm dispatch ran one chunk of its own
    assert led["warm"]["observe_chunks"] == 1 and led["warm"]["rebalances"] == 1
    assert led["observe_chunks"] == res["attempted"] >= 2
    assert led["rebalances"] == led["observe_chunks"]
    assert led["plans_changed"] == 0 and led["programs_built"] == 0
    assert led["table_builds"] == led["observe_chunks"]
    assert led["partition_counts"] == [48, 80, 64, 64]


def test_traced_skewed_cell_reads_the_envelope_padding():
    res, led = _run(True)
    assert res["correct"]
    # the CPU has no TPU plane: the idle reader stays silent
    assert set(res["metrics"]) == {"envelope_pad_share"}
    # blocks of 80, 128, 96 and 80 rows (own and halo, bucketed) padded to 128
    # each: 128 rows more, over 256 elements
    assert led["gathered_rows_per_rhs"] - led["block_rows_per_rhs"] == 128
    assert res["metrics"]["envelope_pad_share"]["value"] == pytest.approx(50.0)
