"""Each cell end to end on the CPU through the harness's functions (the
command itself refuses the CPU), at a small size, interpret-mode kernels."""

import json
import os
import subprocess
import sys

import pytest

from small import cell, run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = ["dg-paper.nested", "dg-paper-x4.sharded"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_correct_with_its_end_to_end_metrics(name):
    res = run(cell(name))
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"elem_steps_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks" and res["attempted"] >= 2 and res["failed"] == 0


@pytest.mark.parametrize("name", CELLS)
def test_traced_cell_reports_host_counts_and_no_device_numbers_on_cpu(name):
    res = run(cell(name), trace=True)
    assert res["correct"]
    # the CPU has no TPU plane: every device-trace reader stays silent
    expect = {"padded_elem_share"} if name.endswith("nested") else set()
    assert set(res["metrics"]) == expect
    if expect:
        # each block gathers its own rows and its halo
        assert res["metrics"]["padded_elem_share"]["value"] > 0


def test_command_refuses_the_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"), "--workload",
                        "dg-paper.nested", "--seed", "1", "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 2, p.stderr
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_json_names_files_that_exist():
    spec = json.load(open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")))
    for c in spec["configs"]:
        assert os.path.exists(os.path.join(os.path.dirname(BENCH), c["file"]))
    for w in spec["workloads"]:
        assert os.path.exists(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(BENCH, "limits", w["name"] + ".json"))
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
            driver = json.load(f)["driver"]
        assert os.path.exists(os.path.join(BENCH, "drivers", driver + ".py"))
    for m in spec["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics", m["name"] + ".py"))
