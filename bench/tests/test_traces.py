"""The trace reduction on a small trace whose numbers are worked out by
hand: busy and idle unions, kernel sums by name, collective-permute
exposure, the per-layer readers and a roofline share."""

import importlib.util
import os

import pytest

import traces
import work
from harness import Context
from traces import Event, Trace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reader(name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(BENCH, "metrics",
                                                                     name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def small_trace():
    """Two devices over a window of 100 ns (from 1000 to 1100).

    dev 0: dg_volume 1000-1020, fusion 1020-1030 overlapping copy 1025-1040,
    collective-permute-start 1040-1042 ... -done 1060-1070 with dg_flux
    1045-1055 in between, idle 1070-1080, fusion 1080-1100 (clipped from
    1080-1120).
    dev 1: dg_volume 1000-1010, a synchronous collective-permute 1010-1030,
    then idle to 1100."""
    d0 = [Event("%dg_volume.2 = f32[8,4608]{1,0} custom-call(%copy.1)", 1000, 1020),
          Event("%fusion.1 = f32[8]{0} fusion(%dg_volume.2)", 1020, 1030),
          Event("copy.2", 1025, 1040), Event("collective-permute-start.3", 1040, 1042),
          Event("%dg_flux.4 = f32[6,64,8] custom-call(%fusion.1)", 1045, 1055),
          Event("collective-permute-done.3", 1060, 1070),
          Event("%fusion.1 = f32[8]{0} fusion(%dg_volume.2)", 1080, 1120)]
    d1 = [Event("dg_volume", 1000, 1010), Event("collective-permute.5", 1010, 1030)]
    host = [Event("bench.window", 1000, 1100), Event("bench.wait", 1065, 1099),
            Event("bench.dispatch", 900, 1000)]
    return Trace(devices={"/device:TPU:0": d0, "/device:TPU:1": d1}, host=host)


def test_unions_and_gaps():
    tr = small_trace()
    w = tr.window
    assert w == (1000, 1100) and tr.window_s == pytest.approx(1e-7)
    d0, d1 = tr.devices["/device:TPU:0"], tr.devices["/device:TPU:1"]
    # 1000-1042, 1045-1055, 1060-1070, 1080-1100
    assert traces.busy(d0, w) == [(1000, 1042), (1045, 1055), (1060, 1070), (1080, 1100)]
    assert traces.measure(traces.busy(d0, w)) == 82
    assert traces.idle_gaps(d0, w) == [(1042, 1045), (1055, 1060), (1070, 1080)]
    assert traces.measure(traces.busy(d1, w)) == 30


def test_kernel_sums_by_instruction_name():
    tr = small_trace()
    d0 = tr.devices["/device:TPU:0"]
    # fusion.1 reads %dg_volume.2 and is not the kernel
    assert traces.kernel_ns(d0, "dg_volume", tr.window) == (20, 1)
    assert traces.kernel_ns(d0, "dg_flux", tr.window) == (10, 1)


def test_collective_exposure():
    tr = small_trace()
    d0, d1 = tr.devices["/device:TPU:0"], tr.devices["/device:TPU:1"]
    # in flight 1040-1070; dg_flux covers 1045-1055 -> 20 exposed
    assert traces.collective_in_flight(d0) == [(1040, 1070)]
    assert traces.exposed_collective_ns(d0, tr.window) == 20
    # synchronous: all 20 exposed
    assert traces.exposed_collective_ns(d1, tr.window) == 20


def test_breakdown():
    tr = small_trace()
    ops = dict((n, s) for n, s in traces.top_ops(tr))
    assert ops["fusion.1 f32[8]"] == pytest.approx((10 + 20) * 1e-9 / 2)
    gaps = traces.top_gaps(tr)
    # the idlest device is dev 1: one gap 1030-1100, half under bench.wait
    assert gaps == [["bench.wait", pytest.approx(70e-9)]]


def test_readers():
    tr = small_trace()
    ctx = Context(trace=tr, steps=2, rhs_evals=10, elements=8192, order=7,
                  device_kind="TPU v5 lite", counters={"gathered_rows_per_rhs": 9728})
    assert reader("device_idle_share")(ctx) == pytest.approx(70.0)
    # dev 0: 82 busy - 30 kernel = 52 ns; dev 1: 30 - 10 = 20 ns -> 52 ns / 2 steps
    assert reader("nonkernel_ms_per_step")(ctx) == pytest.approx(26e-6)
    assert reader("halo_exposed_ms_per_step")(ctx) == pytest.approx(10e-6)
    assert reader("padded_elem_share")(ctx) == pytest.approx(18.75)
    least = 10 * 8192 * 36876 / 819e9
    assert reader("dg_volume_roofline")(ctx) == pytest.approx(100 * least / 30e-9)
    assert reader("dg_flux_ms_per_step")(ctx) == pytest.approx(5e-6)


def test_readers_without_a_trace_return_nothing():
    ctx = Context(trace=None, steps=2, rhs_evals=10, elements=8192, order=7,
                  device_kind="TPU v5 lite", counters={})
    for name in ("device_idle_share", "nonkernel_ms_per_step", "halo_exposed_ms_per_step",
                 "dg_volume_roofline", "dg_flux_ms_per_step", "padded_elem_share"):
        assert reader(name)(ctx) is None


def test_no_collective_no_exposure_reading():
    tr = small_trace()
    tr.devices = {"/device:TPU:0": [Event("dg_volume", 1000, 1020)]}
    ctx = Context(trace=tr, steps=1, rhs_evals=5, elements=8, order=7,
                  device_kind="TPU v5 lite", counters={})
    assert reader("halo_exposed_ms_per_step")(ctx) is None
    assert work.least_seconds("dg_flux", 7, 8, 5, "TPU v5 lite")[1] == "bytes"


def test_recorded_rhs_on_a_v5e():
    """One rhs evaluation of ``dg-paper.nested`` as the chip's profiler
    recorded it (``data/nested_rhs_excerpt.json``).  The numbers below were
    worked out from the file with a plain sweep over the op boundaries,
    leaving out the five events that contain others (the two ``while``
    loops, two transposes and a fusion that hold async ops)."""
    import json

    with open(os.path.join(BENCH, "tests", "data", "nested_rhs_excerpt.json")) as f:
        d = json.load(f)
    ev = [Event(*e) for e in d["events"]]
    w = tuple(d["window"])
    assert len(traces.leaves(ev)) == len(ev) - 5
    assert traces.measure(traces.busy(ev, w)) == 160867292
    assert w[1] - w[0] == 162103362
    assert traces.kernel_ns(ev, "dg_volume", w) == (2451340, 1)
    assert traces.kernel_ns(ev, "dg_flux", w) == (190708, 6)
    assert traces.collective_in_flight(ev) == []
    tr = Trace(devices={"/device:TPU:0": ev}, host=[Event("bench.window", *w)])
    ctx = Context(trace=tr, steps=1, rhs_evals=1, elements=8192, order=7,
                  device_kind="TPU v5 lite", counters={})
    # least time of one rhs: 8192 x 36876 B / 819e9 B/s = 0.368838 ms
    assert reader("dg_volume_roofline")(ctx) == pytest.approx(
        100 * 8192 * 36876 / 819e9 / 2451340e-9)
    assert reader("device_idle_share")(ctx) == pytest.approx(
        100 * (1 - 160867292 / 162103362))
    assert reader("nonkernel_ms_per_step")(ctx) == pytest.approx(
        (160867292 - 2451340 - 190708) * 1e-6)
