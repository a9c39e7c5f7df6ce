"""Kernel block-size autotuner + measured-roofline cost-model feedback.

Covers the sweep machinery (interpret mode, tiny shapes), the JSON cache
roundtrip, block-size invariance of the kernels under ``activate``, and the
planner loop: an autotune cache entry consumed through
``CalibrationTable.from_autotune`` / ``NodeModel.from_tables`` /
``measured_launch_overhead`` must actually change planner decisions vs the
analytic model, and ``roofline_time_fn``'s 20 µs fallback must stay pinned
when no cache is present.
"""

import json

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.cost_model import (
    DEFAULT_LAUNCH_OVERHEAD,
    CalibrationTable,
    DGWorkModel,
    measured_launch_overhead,
    roofline_time_fn,
    stampede_calibration,
)
from repro.core.load_balance import NodeModel, solve_two_way
from repro.core.topology import STAMPEDE_SNB_SOCKET
from repro.kernels import autotune as at


def _entry(device_kind="test-device", order=3, be=16, bf=128,
           vol=2e-7, flux=1e-7, overhead=55e-6):
    return {
        "device_kind": device_kind,
        "order": order,
        "n_fields": 9,
        "dtype": "float32",
        "interpret": True,
        "be": be,
        "bf": bf,
        "sec_per_element": {"volume_loop": vol, "int_flux": flux},
        "launch_overhead_s": overhead,
    }


# ---------------------------------------------------------------------------
# cache roundtrip
# ---------------------------------------------------------------------------


def test_cache_save_load_lookup_roundtrip(tmp_path):
    path = str(tmp_path / "autotune.json")
    assert at.load_cache(path) == {}  # missing file -> empty, no raise
    e1 = _entry(order=2)
    e2 = _entry(order=4, be=32)
    at.save_entry(e1, path)
    at.save_entry(e2, path)
    cache = at.load_cache(path)
    assert set(cache) == {at.entry_key("test-device", 2),
                          at.entry_key("test-device", 4)}
    hit = at.lookup("test-device", 4, path=path)
    assert hit["be"] == 32
    assert at.lookup("test-device", 9, path=path) is None  # unknown order
    # order=None: any entry for the device class
    assert at.lookup("test-device", path=path)["device_kind"] == "test-device"
    assert at.best_blocks("test-device", 2, path=path) == (16, 128)
    assert at.best_blocks("absent-device", 2, path=path) == (None, None)
    # re-saving the same key overwrites, not duplicates
    at.save_entry(_entry(order=2, be=8), path)
    assert at.lookup("test-device", 2, path=path)["be"] == 8
    assert len(at.load_cache(path)) == 2


def test_cache_corrupt_file_degrades_to_empty(tmp_path):
    path = str(tmp_path / "autotune.json")
    with open(path, "w") as f:
        f.write("{not json")
    assert at.load_cache(path) == {}


# ---------------------------------------------------------------------------
# the sweep (interpret mode, tiny shapes)
# ---------------------------------------------------------------------------


def test_autotune_sweep_interpret_smoke(tmp_path):
    path = str(tmp_path / "autotune.json")
    entry = at.autotune(
        order=1,
        device_kind="ci-interpret",
        be_candidates=(8, 16),
        bf_candidates=(16, 32),
        interpret=True,
        reps=1,
        size_factor=2,
        cache_path=path,
    )
    assert entry["be"] in (8, 16) and entry["bf"] in (16, 32)
    assert set(entry["sec_per_element"]) == {"volume_loop", "int_flux"}
    assert entry["sec_per_element"]["volume_loop"] >= 0.0
    assert entry["launch_overhead_s"] >= 0.0
    assert len(entry["volume_sweep"]) == 2 and len(entry["flux_sweep"]) == 2
    # the sweep saved itself; the cache is immediately consumable
    cached = at.lookup("ci-interpret", 1, path=path)
    assert cached["be"] == entry["be"] and cached["bf"] == entry["bf"]
    tab = CalibrationTable.from_autotune(cached)
    assert tab.time_fn()(100) > 0.0


def test_activate_changes_blocks_and_results_stay_bitwise():
    """activate() installs the winners module-wide; the kernels are
    block-invariant, so any activated BE/BF reproduces the default output
    bitwise (the property the envelope pipeline's bitwise guarantee rests
    on)."""
    from repro.dg.basis import diff_matrix, lgl_nodes_weights
    from repro.kernels import dg_flux, dg_volume

    order, K, F = 1, 12, 20
    M = order + 1
    x, _ = lgl_nodes_weights(order)
    D = jnp.asarray(diff_matrix(x), jnp.float32)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((K, 9, M, M, M)), jnp.float32)
    ones = jnp.ones(K, jnp.float32)
    mu = jnp.zeros(K, jnp.float32)
    tm = jnp.asarray(rng.standard_normal((6, 6, M * M, F)), jnp.float32)
    tp = jnp.asarray(rng.standard_normal((6, 6, M * M, F)), jnp.float32)
    mat = jnp.asarray(np.abs(rng.standard_normal((6, 10, F))) + 0.5, jnp.float32)
    flux = lambda: np.asarray(dg_flux.dg_flux_pallas(tm, tp, mat, (1.0, 2.0, 3.0),
                                                     interpret=True))

    ref_v = np.asarray(dg_volume.dg_volume_pallas(
        q, D, (2.0, 2.0, 2.0), ones, ones, mu, interpret=True))
    ref_f = flux()
    try:
        at.activate(_entry(be=4, bf=8))
        assert dg_volume.block_elems() == 4 and dg_flux.block_faces() == 8
        got_v = np.asarray(dg_volume.dg_volume_pallas(
            q, D, (2.0, 2.0, 2.0), ones, ones, mu, interpret=True))
        got_f = flux()
        assert (got_v == ref_v).all()
        assert (got_f == ref_f).all()
    finally:
        at.activate(None)
    assert dg_volume.block_elems() == dg_volume.BE
    assert dg_flux.block_faces() == dg_flux.BF


# ---------------------------------------------------------------------------
# cost-model feedback
# ---------------------------------------------------------------------------


def test_from_autotune_fills_shares_and_overhead():
    entry = _entry(vol=4e-7, flux=2e-7, overhead=77e-6)
    tab = CalibrationTable.from_autotune(entry)
    assert tab.device_name == "test-device" and tab.order == 3
    assert tab.overhead == pytest.approx(77e-6)
    assert tab.sec_per_element["volume_loop"] == pytest.approx(4e-7)
    assert tab.sec_per_element["int_flux"] == pytest.approx(2e-7)
    # unmeasured kernels filled from the Fig 4.1 shares anchored to the
    # MEASURED volume_loop: rk share 0.10 vs volume share 0.40 -> 1/4 ratio
    assert tab.sec_per_element["rk"] == pytest.approx(4e-7 * 0.10 / 0.40)
    assert set(tab.sec_per_element) >= {"volume_loop", "int_flux", "rk",
                                        "lift", "interp_q"}
    bare = CalibrationTable.from_autotune(entry, fill_shares=False)
    assert set(bare.sec_per_element) == {"volume_loop", "int_flux"}


def test_roofline_overhead_fallback_pinned(tmp_path, monkeypatch):
    """With no autotune cache present the 20 µs constant survives exactly."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "does-not-exist.json"))
    assert DEFAULT_LAUNCH_OVERHEAD == pytest.approx(20e-6)
    assert measured_launch_overhead("whatever") == pytest.approx(20e-6)
    work = DGWorkModel(order=3)
    T = roofline_time_fn(work, STAMPEDE_SNB_SOCKET)
    T_explicit = roofline_time_fn(work, STAMPEDE_SNB_SOCKET, overhead=20e-6)
    assert T(0) == 0.0
    for K in (1, 64, 4096):
        assert T(K) == pytest.approx(T_explicit(K))


def test_roofline_overhead_measured_when_cache_present(tmp_path, monkeypatch):
    path = str(tmp_path / "autotune.json")
    at.save_entry(_entry(device_kind=STAMPEDE_SNB_SOCKET.name,
                         overhead=300e-6), path)
    at.save_entry(_entry(device_kind="other-device", overhead=1e-6), path)
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", path)
    # device-matched entry wins over the other device's
    assert measured_launch_overhead(STAMPEDE_SNB_SOCKET.name) == pytest.approx(300e-6)
    assert measured_launch_overhead("other-device") == pytest.approx(1e-6)
    # unmatched device falls back over all cached entries (median)
    assert measured_launch_overhead("unknown") in (pytest.approx(300e-6),
                                                   pytest.approx(1e-6))
    work = DGWorkModel(order=3)
    T = roofline_time_fn(work, STAMPEDE_SNB_SOCKET)
    T_const = roofline_time_fn(work, STAMPEDE_SNB_SOCKET, overhead=20e-6)
    assert T(64) - T_const(64) == pytest.approx(280e-6)
    # explicit path param bypasses the env var
    T_miss = roofline_time_fn(work, STAMPEDE_SNB_SOCKET,
                              autotune_path=str(tmp_path / "nope.json"))
    assert T_miss(64) == pytest.approx(T_const(64))


def test_autotuned_tables_change_planner_decision():
    """The acceptance loop: a measured autotune entry, consumed via
    CalibrationTable.from_autotune -> NodeModel.from_tables, must move the
    solve_two_way split vs the analytic (reconstructed-Stampede) model —
    planning on observed rooflines, not assumed ones."""
    order, K = 7, 8192
    tabs = stampede_calibration(order)
    analytic = NodeModel.from_tables(tabs["snb-socket"], tabs["xeon-phi"])
    base = analytic.solve(K)
    # the autotuner measured this accelerator much faster than the
    # reconstructed table assumed (and the host as reconstructed)
    host_meas = _entry(device_kind="host", order=order,
                       vol=tabs["snb-socket"].sec_per_element["volume_loop"],
                       flux=tabs["snb-socket"].sec_per_element["int_flux"],
                       overhead=tabs["snb-socket"].overhead)
    accel_meas = _entry(device_kind="accel", order=order,
                        vol=tabs["xeon-phi"].sec_per_element["volume_loop"] / 4,
                        flux=tabs["xeon-phi"].sec_per_element["int_flux"] / 4,
                        overhead=tabs["xeon-phi"].overhead)
    measured = NodeModel.from_tables(
        CalibrationTable.from_autotune(host_meas),
        CalibrationTable.from_autotune(accel_meas),
    )
    tuned = measured.solve(K)
    # a 4x faster measured accelerator absorbs strictly more elements
    assert tuned.counts[1] > base.counts[1]
    assert tuned.counts != base.counts
    assert tuned.makespan < base.makespan
    # the same tables drive solve_two_way directly
    direct = solve_two_way(
        CalibrationTable.from_autotune(host_meas).time_fn(),
        CalibrationTable.from_autotune(accel_meas).time_fn(),
        K,
    )
    assert direct.counts == tuned.counts
