"""The program's own names in a ``jax.profiler`` trace: the ``dg.*`` stage
scopes of the device ops and the ``dg.*`` host spans, and the four readings
built on them.

The program puts each stage of the DG step under a ``jax.named_scope``
(``dg.gather``, ``dg.halo``, ``dg.volume``, ``dg.flux``, ``dg.scatter``,
``dg.lsrk``).  The scope path reaches each HLO instruction's ``op_name``,
which the TPU profiler records as the ``tf_op`` stat of the op's event
metadata; an op is charged to the innermost ``dg.*`` component of that path
(a fusion carries its root's).  ``jax.profiler.ProfileData``, which
``traces.load`` reads, gives an event's own stats and not its metadata's, so
``load`` here reads the ``.xplane.pb`` protobuf itself.  On the host the
program opens ``jax.profiler.TraceAnnotation`` spans ``dg.dispatch``
(children ``dg.copy_in``, ``dg.enqueue``), ``dg.sync``, ``dg.rebalance``
and ``dg.tables``.

  python3 bench/scopes.py <trace_dir> --steps <n> --order <N> --elements <K>

reads a kept ``--trace 1`` trace (the directory ``jax.profiler.start_trace``
wrote; the benchmark removes its own after reducing it) and prints the four
readings, ms per step per scope on each device, the unscoped ops above 1%
of busy time, and the device idle time inside ``dg.dispatch``.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import glob
import json
import os
import struct
import sys
from typing import Dict, List, Sequence, Tuple

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import traces  # noqa: E402
import work  # noqa: E402
from traces import Interval  # noqa: E402

PREFIX = "dg."
OP_NAME_STAT = "tf_op"
GATHER_SCATTER = ("dg.gather", "dg.scatter")


@dataclasses.dataclass(frozen=True)
class Op(traces.Event):
    scope: str = ""  # the innermost dg.* component of the op's op_name


def innermost(op_name: str) -> str:
    """The innermost ``dg.*`` component of an ``op_name`` path (whole path
    components only; a ``name:type`` suffix is dropped), or ""."""
    parts = [c for c in op_name.split(":")[0].split("/") if c.startswith(PREFIX)]
    return parts[-1] if parts else ""


@dataclasses.dataclass
class ScopedTrace:
    devices: Dict[str, List[Op]]
    host: List[traces.Event]  # the benchmark's bench.* spans
    program: List[traces.Event]  # the program's dg.* spans

    @property
    def window(self) -> Interval:
        return traces.Trace(devices={}, host=self.host).window


# -- the .xplane.pb protobuf (tsl/profiler/protobuf/xplane.proto), read by hand


def _fields(buf: bytes):
    """(field number, value) of each field of one protobuf message: an int
    for varint and fixed64, bytes for length-delimited."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, kind = key >> 3, key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 1:
            value, i = struct.unpack_from("<q", buf, i)[0], i + 8
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield field, value


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _plane(buf: bytes):
    """(name, lines, {metadata id: (name, {stat id: str value or ref})},
    {stat id: name}) of one XPlane."""
    name, lines, events, stats = "", [], {}, {}
    for f, v in _fields(buf):
        if f == 2:
            name = v.decode()
        elif f == 3:
            lines.append(v)
        elif f in (4, 5):
            entry = dict(_fields(v)).get(2, b"")
            meta = list(_fields(entry))
            mid = next((x for g, x in meta if g == 1), 0)
            mname = next((x for g, x in meta if g == 2), b"").decode(errors="replace")
            if f == 5:
                stats[mid] = mname
            else:
                mstats = {}
                for g, x in meta:
                    if g == 5:
                        st = dict(_fields(x))
                        if 5 in st:
                            mstats[st.get(1, 0)] = st[5].decode(errors="replace")
                        elif 7 in st:
                            mstats[st.get(1, 0)] = ("ref", st[7])
                events[mid] = (mname, mstats)
    return name, lines, events, stats


def _line_events(buf: bytes):
    """(line name, [(metadata id, start ns, end ns)]) of one XLine."""
    name, t0, out = "", 0, []
    raw = []
    for f, v in _fields(buf):
        if f == 2:
            name = v.decode()
        elif f == 3:
            t0 = v
        elif f == 4:
            ev = dict(_fields(v))
            raw.append((ev.get(1, 0), ev.get(2, 0), ev.get(3, 0)))
    for mid, off_ps, dur_ps in raw:  # whole ns, as jax.profiler.ProfileData gives them
        start = float(t0 + off_ps // 1000)
        out.append((mid, start, start + float(dur_ps // 1000)))
    return name, out


def load(trace_dir: str) -> ScopedTrace:
    """The newest ``.xplane.pb`` under ``trace_dir``: each TPU's ``XLA Ops``
    with its scope, the ``bench.*`` and the ``dg.*`` host spans, on the
    clock ``traces.load`` uses."""
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(files[-1], "rb") as f:
        space = f.read()
    devices, host, program = {}, [], []
    for field, buf in _fields(space):
        if field != 1:
            continue
        name, lines, events, stats = _plane(buf)
        if name.startswith(traces.DEVICE_PREFIX):
            op_stat = [k for k, v in stats.items() if v == OP_NAME_STAT]

            def scope_of(mid):
                got = events.get(mid, ("", {}))[1]
                value = next((got[k] for k in op_stat if k in got), "")
                if isinstance(value, tuple):
                    value = stats.get(value[1], "")
                return innermost(value)

            for line in lines:
                lname, evs = _line_events(line)
                if lname == traces.OPS_LINE:
                    devices[name] = sorted(
                        (Op(events.get(m, ("", {}))[0], s, e, scope_of(m)) for m, s, e in evs),
                        key=lambda e: e.start)
        elif name.startswith("/host:"):
            for line in lines:
                for m, s, e in _line_events(line)[1]:
                    label = events.get(m, ("", {}))[0]
                    if label.startswith(traces.HOST_PREFIX):
                        host.append(traces.Event(label, s, e))
                    elif label.startswith(PREFIX):
                        program.append(traces.Event(label, s, e))
    return ScopedTrace(devices=devices, host=sorted(host, key=lambda e: e.start),
                       program=sorted(program, key=lambda e: e.start))


# -- reductions


def _clipped(events: Sequence[Op], window: Interval) -> List[tuple]:
    lo, hi = window
    out = []
    for e in traces.leaves(events):
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append((e, s, t))
    return out


def busy_ns(events: Sequence[Op], names: Sequence[str], window: Interval) -> float:
    """Union of the innermost op intervals charged to any of ``names``."""
    return traces.measure(traces.union(
        [(s, t) for e, s, t in _clipped(events, window) if e.scope in names], window))


def sum_ns(events: Sequence[Op], name: str, window: Interval) -> float:
    """Summed durations of the innermost ops charged to ``name``."""
    return sum(t - s for e, s, t in _clipped(events, window) if e.scope == name)


def by_scope_ns(events: Sequence[Op], window: Interval) -> Dict[str, float]:
    """Summed innermost-op time per scope ("" for unscoped)."""
    out = collections.Counter()
    for e, s, t in _clipped(events, window):
        out[e.scope] += t - s
    return dict(out)


def unscoped_ops(events: Sequence[Op], window: Interval) -> Dict[str, float]:
    """Summed time of each unscoped op, by its breakdown label."""
    out = collections.Counter()
    for e, s, t in _clipped(events, window):
        if not e.scope:
            out[e.label] += t - s
    return dict(out)


def idle_inside_ns(events: Sequence[Op], spans: Sequence[Interval], window: Interval) -> float:
    """Device idle time (outside the busy union) inside the host ``spans``."""
    return traces.measure(traces.intersect(traces.idle_gaps(events, window),
                                           traces.union(spans, window)))


def readings(tr: ScopedTrace, steps: int, order: int, elements: int,
             device_kind: str) -> Dict[str, float]:
    """The four per-layer numbers of a window of ``steps`` LSRK steps (five
    rhs evaluations each); a number is left out where nothing carries its
    scope or span.

    * ``gather_scatter_ms_per_step``: busy time of the ops in ``dg.gather``
      or ``dg.scatter``, busiest device;
    * ``flux_stage_roofline`` / ``volume_stage_roofline``: least time of the
      kernel's work (``work.least_seconds``) over the summed time of the ops
      in ``dg.flux`` / ``dg.volume``, all devices together;
    * ``dispatch_idle_ms_per_dispatch``: device idle time inside
      ``dg.dispatch`` spans on the idlest device, over the dispatches that
      start in the window."""
    window = tr.window
    devs = list(tr.devices.values())
    out = {}
    gs = [busy_ns(ev, GATHER_SCATTER, window) for ev in devs]
    if any(gs):
        out["gather_scatter_ms_per_step"] = max(gs) * 1e-6 / steps
    for metric, kernel, name in (("flux_stage_roofline", "dg_flux", "dg.flux"),
                                 ("volume_stage_roofline", "dg_volume", "dg.volume")):
        stage_s = sum(sum_ns(ev, name, window) for ev in devs) * 1e-9
        if stage_s > 0:
            least, _ = work.least_seconds(kernel, order, elements, 5 * steps, device_kind)
            out[metric] = 100.0 * least / stage_s
    disp = [(e.start, e.end) for e in tr.program if e.name == "dg.dispatch"]
    n = sum(1 for s, _ in disp if window[0] <= s <= window[1])
    if devs and n:
        idlest = max(devs, key=lambda ev: traces.measure(traces.idle_gaps(ev, window)))
        out["dispatch_idle_ms_per_dispatch"] = idle_inside_ns(idlest, disp, window) * 1e-6 / n
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--steps", type=int, required=True, help="LSRK steps in the window")
    ap.add_argument("--order", type=int, required=True)
    ap.add_argument("--elements", type=int, required=True, help="real elements of the mesh")
    ap.add_argument("--device-kind", default="TPU v5 lite")
    args = ap.parse_args(argv)
    tr = load(args.trace_dir)
    window = tr.window
    per = args.steps * 1e6
    disp = [(e.start, e.end) for e in tr.program if e.name == "dg.dispatch"]
    out = {"window_s": (window[1] - window[0]) * 1e-9, "steps": args.steps,
           "readings": readings(tr, args.steps, args.order, args.elements, args.device_kind),
           "devices": {}}
    for d, ev in sorted(tr.devices.items()):
        busy = traces.measure(traces.busy(ev, window))
        out["devices"][d] = {
            "busy_ms_per_step": busy / per,
            "scope_ms_per_step": {k or "(unscoped)": v / per
                                  for k, v in sorted(by_scope_ns(ev, window).items())},
            "unscoped_over_1pct": {k: v / per for k, v in sorted(
                unscoped_ops(ev, window).items(), key=lambda kv: -kv[1]) if v > 0.01 * busy},
            "idle_ms": traces.measure(traces.idle_gaps(ev, window)) * 1e-6,
            "idle_in_dispatch_ms": idle_inside_ns(ev, disp, window) * 1e-6,
        }
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
