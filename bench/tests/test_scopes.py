"""The program's stage scopes and host spans, reduced by ``bench/scopes.py``:
on a small trace whose numbers are worked out by hand (scope matching on
whole path components, the innermost ``dg.*`` scope wins, union against sum,
busiest against idlest device, idle gaps clipped to ``dg.dispatch``), on a
hand-built ``.xplane.pb`` (the op's scope read from its metadata), and on a
recorded excerpt of a chip trace, read to fixed values."""

import json
import os

import pytest

import scopes
import traces
from scopes import Op, ScopedTrace
from traces import Event

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BODY = "jit(run)/while/body/closed_call/dg.lsrk"


def scoped_trace():
    """Two devices over a window of 100 ns (1000 to 1100).

    dev 0: a ``while`` around everything (a container, never charged);
    gather 1000-1010, volume kernel 1010-1030, flux fusion 1030-1040, flux
    kernel 1040-1045, an op in ``dg.fluxes`` 1045-1050, scatter 1050-1060
    overlapped by a second gather 1055-1065, the stage update 1065-1070, an
    unscoped copy 1070-1075, idle 1075-1085, a volume relayout 1085-1100
    (clipped from 1085-1120).
    dev 1: gather 1000-1005, scatter 1005-1010, then idle to 1100.
    Program spans: ``dg.dispatch`` 990-1002 (begun before the window, with
    its ``dg.copy_in``) and 1070-1090, then ``dg.sync`` 1090-1095."""
    d0 = [Op("%while.1 = (f32[8]) while(%t)", 1000, 1100),
          Op("%gather.1 = f32[8] gather(%q)", 1000, 1010, "dg.gather"),
          Op("%dg_volume.2 = f32[8,4608] custom-call(%gather.1)", 1010, 1030, "dg.volume"),
          Op("%fusion.3 = f32[8] fusion(%gather.1)", 1030, 1040, "dg.flux"),
          Op("%dg_flux.4 = f32[6,64,8] custom-call(%fusion.3)", 1040, 1045, "dg.flux"),
          Op("%add.5 = f32[8] add(%dg_flux.4)", 1045, 1050, "dg.fluxes"),
          Op("%scatter.6 = f32[9,8] scatter(%add.5)", 1050, 1060, "dg.scatter"),
          Op("%gather.7 = f32[8] gather(%q)", 1055, 1065, "dg.gather"),
          Op("%add.8 = f32[8] add(%scatter.6)", 1065, 1070, "dg.lsrk"),
          Op("%copy.9 = f32[8] copy(%add.8)", 1070, 1075),
          Op("%fusion.10 = f32[8,4608] fusion(%q)", 1085, 1120, "dg.volume")]
    d1 = [Op("%gather.1 = f32[8] gather(%q)", 1000, 1005, "dg.gather"),
          Op("%scatter.2 = f32[9,8] scatter(%q)", 1005, 1010, "dg.scatter")]
    program = [Event("dg.dispatch", 990, 1002), Event("dg.copy_in", 991, 992),
               Event("dg.dispatch", 1070, 1090), Event("dg.sync", 1090, 1095)]
    return ScopedTrace(devices={"/device:TPU:0": d0, "/device:TPU:1": d1},
                       host=[Event("bench.window", 1000, 1100)], program=program)


def test_innermost_whole_component():
    assert scopes.innermost(f"{BODY}/dg.gather/gather:") == "dg.gather"
    assert scopes.innermost(f"{BODY}/dg.flux/dg_flux/pallas_call:") == "dg.flux"
    assert scopes.innermost(f"{BODY}/mul") == "dg.lsrk"
    assert scopes.innermost(f"{BODY}/dg.fluxes/add") == "dg.fluxes"
    # a component is matched whole: a name that merely contains "dg." is none
    assert scopes.innermost("jit(run)/while/body/my_dg.flux/add") == ""
    assert scopes.innermost("jit(run)/while/body/add:dg.flux") == ""
    assert scopes.innermost("") == ""


def test_union_sum_and_unscoped():
    tr = scoped_trace()
    w = tr.window
    d0, d1 = tr.devices["/device:TPU:0"], tr.devices["/device:TPU:1"]
    # gather [1000,1010] + [1055,1065], scatter [1050,1060]: sum 30, union 25
    assert scopes.sum_ns(d0, "dg.gather", w) + scopes.sum_ns(d0, "dg.scatter", w) == 30
    assert scopes.busy_ns(d0, scopes.GATHER_SCATTER, w) == 25
    assert scopes.busy_ns(d1, scopes.GATHER_SCATTER, w) == 10
    # the while loop is a container and is charged to nothing
    assert scopes.by_scope_ns(d0, w) == {"dg.gather": 20, "dg.volume": 35, "dg.flux": 15,
                                         "dg.fluxes": 5, "dg.scatter": 10, "dg.lsrk": 5,
                                         "": 5}
    assert scopes.unscoped_ops(d0, w) == {"copy.9 f32[8]": 5}


def test_readings_on_the_hand_worked_trace():
    got = scopes.readings(scoped_trace(), 2, 7, 8192, "TPU v5 lite")
    flux_least = 10 * 8192 * (3 * 64 * 18 * 4 * 2) / 819e9  # 10 rhs evaluations
    vol_least = 10 * 8192 * 36876 / 819e9
    assert got == pytest.approx({
        # busiest device: dev 0, 25 ns over 2 steps
        "gather_scatter_ms_per_step": 12.5e-6,
        # dg.fluxes is not dg.flux: 15 ns of flux stage, all of it on dev 0
        "flux_stage_roofline": 100 * flux_least / 15e-9,
        "volume_stage_roofline": 100 * vol_least / 35e-9,
        # idlest device: dev 1 (90 ns idle), 20 of it inside the one
        # dispatch that starts in the window (1070-1090); dev 0's 10 ns of
        # idle (1075-1085) would have read 10
        "dispatch_idle_ms_per_dispatch": 20e-6,
    })


def test_readings_leave_out_what_nothing_carries():
    tr = scoped_trace()
    tr.devices = {d: [Op(e.name, e.start, e.end) for e in ev] for d, ev in tr.devices.items()}
    tr.program = []
    assert scopes.readings(tr, 2, 7, 8192, "TPU v5 lite") == {}


def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | (0x80 if n else 0)])
        if not n:
            return out


def _msg(*fields):
    """A protobuf message from (field, int or bytes or str) pairs."""
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += _varint(f << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(f << 3 | 2) + _varint(len(v)) + v
    return out


def _meta(mid, name, *stats):
    """One map entry of an XPlane's event or stat metadata."""
    return _msg((1, mid), (2, _msg((1, mid), (2, name), *((5, s) for s in stats))))


def test_load_reads_the_scope_from_the_op_metadata(tmp_path):
    """A hand-built ``.xplane.pb``: a TPU plane whose ops name their scope
    in the ``tf_op`` stat of the event metadata (one as a string, one as a
    reference to a stat metadata entry, one with none), and a host plane
    with the benchmark's window and a program span."""
    tf_op, ref = 7, 8
    tpu = _msg((2, "/device:TPU:0"),
               (3, _msg((2, "XLA Ops"), (3, 100),  # the line starts at 100 ns
                        (4, _msg((1, 1), (2, 5_000), (3, 10_000))),
                        (4, _msg((1, 2), (2, 15_999), (3, 4_000))),
                        (4, _msg((1, 3), (2, 20_000), (3, 1_000))))),
               (3, _msg((2, "XLA Modules"), (3, 100), (4, _msg((1, 1), (2, 0), (3, 1))))),
               (4, _meta(1, "%gather.1 = f32[8] gather(%q)",
                         _msg((1, tf_op), (5, f"{BODY}/dg.gather/gather:")))),
               (4, _meta(2, "%fusion.2 = f32[8] fusion(%gather.1)", _msg((1, tf_op), (7, ref)))),
               (4, _meta(3, "%copy.3 = f32[8] copy(%fusion.2)")),
               (5, _msg((1, tf_op), (2, _msg((1, tf_op), (2, "tf_op"))))),
               (5, _msg((1, ref), (2, _msg((1, ref), (2, f"{BODY}/dg.flux/mul:"))))))
    host = _msg((2, "/host:CPU"),
                (3, _msg((2, "python3"), (3, 50),
                         (4, _msg((1, 1), (2, 0), (3, 100_000))),
                         (4, _msg((1, 2), (2, 10_000), (3, 20_000))))),
                (4, _meta(1, "bench.window")), (4, _meta(2, "dg.dispatch")))
    path = tmp_path / "plugins" / "profile" / "1"
    path.mkdir(parents=True)
    (path / "h.xplane.pb").write_bytes(_msg((1, tpu), (1, host)))
    tr = scopes.load(str(tmp_path))
    # whole ns from the line's start, as jax.profiler.ProfileData gives them
    assert tr.devices == {"/device:TPU:0": [
        Op("%gather.1 = f32[8] gather(%q)", 105.0, 115.0, "dg.gather"),
        Op("%fusion.2 = f32[8] fusion(%gather.1)", 115.0, 119.0, "dg.flux"),
        Op("%copy.3 = f32[8] copy(%fusion.2)", 120.0, 121.0, "")]}
    assert tr.host == [Event("bench.window", 50.0, 150.0)]
    assert tr.program == [Event("dg.dispatch", 60.0, 80.0)]
    assert tr.window == (50.0, 150.0)


def test_recorded_scoped_excerpt_on_a_v5e():
    """The start of a ``dg-paper.nested`` window as the chip's profiler
    recorded it with the stage scopes in place
    (``data/nested_scoped_excerpt.json``): both dispatches' host spans and
    the device's first 294 ms.  The scope sums add up to the busy time (no
    two ops overlap here), and the device waits 0.921 ms at the window's
    start, inside the first ``dg.dispatch``, for the first program."""
    with open(os.path.join(BENCH, "tests", "data", "nested_scoped_excerpt.json")) as f:
        d = json.load(f)
    ops = [Op(*e) for e in d["events"]]
    w = tuple(d["window"])
    tr = ScopedTrace(devices={"/device:TPU:0": ops}, host=[Event("bench.window", *w)],
                     program=[Event(*p) for p in d["program"]])
    by = scopes.by_scope_ns(ops, w)
    assert by == {"": 68304766, "dg.volume": 3824551, "dg.flux": 151606813,
                  "dg.gather": 46144013, "dg.scatter": 21131755, "dg.lsrk": 1119570}
    assert sum(by.values()) == traces.measure(traces.busy(ops, w)) == 292131468
    assert traces.kernel_ns(ops, "dg_volume", w) == (2451565, 1)
    assert [p.name for p in tr.program].count("dg.dispatch") == 2
    got = scopes.readings(tr, 1, 7, 8192, "TPU v5 lite")
    assert got == pytest.approx({
        "gather_scatter_ms_per_step": 67.275768,
        "flux_stage_roofline": 100 * 5 * 8192 * 27648 / 819e9 / 151606813e-9,
        "volume_stage_roofline": 100 * 5 * 8192 * 36876 / 819e9 / 3824551e-9,
        "dispatch_idle_ms_per_dispatch": 0.921287 / 2,
    })
    # the stage holds the kernel: its share is under the kernel's own
    assert got["volume_stage_roofline"] < 100 * 5 * 8192 * 36876 / 819e9 / 2451565e-9
