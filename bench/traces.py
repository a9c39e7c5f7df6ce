"""Reduction of a ``jax.profiler`` trace to the numbers the per-layer
metrics read.

A trace is reduced to plain events: for each device plane
(``/device:TPU:<n>``) the operations of its ``XLA Ops`` line, and on the
host the benchmark's own ``bench.*`` spans, all on the profiler's clock.
The measured window is the host span ``bench.window``; every number below
is clipped to it.

* busy time: the union of a device's operation intervals (innermost
  events: a ``while`` loop's own event spans its whole body);
* kernel time: the summed durations of the events of one Pallas kernel,
  found by its HLO instruction name (``dg_volume.<n>``, ``dg_flux.<n>``);
* exposed collective time: the part of the intervals in which a
  collective-permute is in flight (``-start`` to its ``-done``, or one
  synchronous op) during which no other operation runs on that device.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]
_INSTR = re.compile(r"%?([^\s=]+) = ")
_LABEL = re.compile(r"%?([^\s=]+) = \(?(\w+\[[^\]]*\])")

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
WINDOW = "bench.window"
COLLECTIVE = "collective-permute"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str  # on a TPU: the op's HLO text, "%<instruction> = <shape> <opcode>(...)"
    start: float  # ns
    end: float  # ns

    @property
    def op(self) -> str:
        """The HLO instruction name (``dg_volume.8``, ``fusion.219``)."""
        m = _INSTR.match(self.name)
        return m.group(1) if m else self.name

    @property
    def label(self) -> str:
        """The instruction and its result's shape, for the breakdown."""
        m = _LABEL.match(self.name)
        return f"{m.group(1)} {m.group(2)}" if m else self.op


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Event]]
    host: List[Event]

    @property
    def window(self) -> Interval:
        spans = [e for e in self.host if e.name == WINDOW]
        if len(spans) != 1:
            raise ValueError(f"expected one {WINDOW} span, found {len(spans)}")
        return spans[0].start, spans[0].end

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) * 1e-9


def load(trace_dir: str) -> Trace:
    """The newest ``.xplane.pb`` under ``trace_dir``, reduced to events."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = sorted(
                        (Event(e.name, e.start_ns, e.end_ns) for e in line.events),
                        key=lambda e: e.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns, e.end_ns) for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return Trace(devices=devices, host=sorted(host, key=lambda e: e.start))


def union(intervals: Sequence[Interval], window: Interval) -> List[Interval]:
    """Merged, sorted intervals clipped to ``window``."""
    lo, hi = window
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def measure(merged: Sequence[Interval]) -> float:
    return sum(e - s for s, e in merged)


def intersect(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def is_kernel(event: Event, kernel: str) -> bool:
    """The event is a call of the Pallas kernel named ``kernel`` (its HLO
    instruction is ``<kernel>`` or ``<kernel>.<n>``), not an op that reads
    the kernel's output."""
    op = event.op
    return op == kernel or op.startswith(kernel + ".")


def leaves(events: Sequence[Event]) -> List[Event]:
    """The events that contain no other event: ops, not the ``while``
    loops around them."""
    order = sorted(events, key=lambda e: (e.start, -e.end))
    container, stack = set(), []
    for i, e in enumerate(order):
        while stack and order[stack[-1]].end <= e.start:
            stack.pop()
        if stack and e.end <= order[stack[-1]].end:
            container.add(stack[-1])
        stack.append(i)
    return [e for i, e in enumerate(order) if i not in container]


def busy(events: Sequence[Event], window: Interval) -> List[Interval]:
    """The union of the op intervals: innermost events only, so that the
    gaps between the ops of a loop body count as idle."""
    return union([(e.start, e.end) for e in leaves(events)], window)


def kernel_ns(events: Sequence[Event], kernel: str, window: Interval) -> Tuple[float, int]:
    """(summed duration inside the window, number of events) of a kernel."""
    lo, hi = window
    hits = [(max(e.start, lo), min(e.end, hi)) for e in events if is_kernel(e, kernel)]
    hits = [(s, e) for s, e in hits if e > s]
    return sum(e - s for s, e in hits), len(hits)


def collective_in_flight(events: Sequence[Event]) -> List[Interval]:
    """Intervals in which a collective-permute is in flight: an async
    ``-start`` to its ``-done`` (paired in order per op name), or one
    synchronous op."""
    pending = collections.defaultdict(collections.deque)
    out = []
    for e in events:
        op = e.op
        if not op.startswith(COLLECTIVE):
            continue
        if "-start" in op:
            pending[op.replace("-start", "")].append(e.start)
        elif "-done" in op:
            key = op.replace("-done", "")
            if pending[key]:
                out.append((pending[key].popleft(), e.end))
        else:
            out.append((e.start, e.end))
    return out


def exposed_collective_ns(events: Sequence[Event], window: Interval) -> float:
    """Time with a collective-permute in flight and no other op running."""
    flight = union(collective_in_flight(events), window)
    others = busy([e for e in events if not e.op.startswith(COLLECTIVE)], window)
    return measure(flight) - measure(intersect(flight, others))


def idle_gaps(events: Sequence[Event], window: Interval) -> List[Interval]:
    """The complement of the busy union inside the window."""
    gaps, t = [], window[0]
    for s, e in busy(events, window):
        if s > t:
            gaps.append((t, s))
        t = e
    if window[1] > t:
        gaps.append((t, window[1]))
    return gaps


def host_label(host: Sequence[Event], gap: Interval) -> str:
    """The innermost benchmark span (other than the window) that covers the
    gap's midpoint: what the host was doing while the device waited."""
    mid = 0.5 * (gap[0] + gap[1])
    covering = [e for e in host if e.name != WINDOW and e.start <= mid <= e.end]
    if not covering:
        return "host outside any bench span"
    return min(covering, key=lambda e: e.end - e.start).name


def top_ops(trace: Trace, n: int = 10) -> List[list]:
    """The ``n`` ops (innermost events) with the most device time in the
    window, in seconds averaged over the devices."""
    window = trace.window
    total = collections.Counter()
    for events in trace.devices.values():
        for e in leaves(events):
            s, t = max(e.start, window[0]), min(e.end, window[1])
            if t > s:
                total[e.label] += (t - s) * 1e-9
    nd = max(1, len(trace.devices))
    return [[name, secs / nd] for name, secs in total.most_common(n)]


def top_gaps(trace: Trace, n: int = 10) -> List[list]:
    """The ``n`` longest idle gaps of the idlest device, each named by what
    the host was doing."""
    if not trace.devices:
        return []
    window = trace.window
    idlest = max(trace.devices.values(), key=lambda ev: measure(idle_gaps(ev, window)))
    gaps = sorted(idle_gaps(idlest, window), key=lambda g: g[0] - g[1])[:n]
    return [[host_label(trace.host, g), (g[1] - g[0]) * 1e-9] for g in gaps]
