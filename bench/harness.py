"""One run of one cell: set-up, the measured window, the comparison with
the plain reference, and the per-layer readings.

Everything a cell needs is found by name from ``BENCHMARK.json``:

* ``configs/<config>.json``: the problem's sizes, materials, precision
  and the program path's kernels (``file`` of the ``configs`` entry);
* ``traffic/<traffic>.json``: the driver (``drivers/<driver>.py``) and its
  parameters, and the ranges the seed draws the initial field from;
* ``metrics/<metric>.py``: one reader per per-layer metric, ``read(ctx)``;
* ``limits/<workload>.json``: the limit of each number compared.

A later cell or metric is new files and new ``BENCHMARK.json`` entries.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def _paths() -> None:
    for p in (os.path.join(ROOT, "src"), BENCH, os.path.join(BENCH, "drivers"),
              os.path.join(BENCH, "metrics")):
        if p not in sys.path:
            sys.path.insert(0, p)


def _module(path: str):
    name = "bench_" + os.path.relpath(path, BENCH).replace(os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    workload: dict
    config: dict  # the configuration file's contents
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list

    @classmethod
    def find(cls, spec: dict, name: str) -> "Cell":
        wl = [w for w in spec["workloads"] if w["name"] == name]
        if not wl:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        wl = wl[0]
        entry = [c for c in spec["configs"] if c["name"] == wl["config"]][0]
        with open(os.path.join(ROOT, entry["file"])) as f:
            cfg = json.load(f)
        listed = lambda m: name in m.get("workloads", [name])
        return cls(workload=wl, config=cfg, traffic=_json("traffic", wl["traffic"] + ".json"),
                   limits=_json("limits", name + ".json"),
                   end_to_end=[m for m in spec["end_to_end"] if listed(m)],
                   per_layer=[m for m in spec["per_layer"] if listed(m)])


class CompileLog:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events (one listener pair per process)."""

    _instance = None

    def __init__(self):
        import jax

        self.compiles = []
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    @classmethod
    def get(cls) -> "CompileLog":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _on_duration(self, event, duration, **kwargs):
        if event == COMPILE_EVENT:
            self.compiles.append(float(duration))

    def _on_event(self, event, **kwargs):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def mark(self):
        return len(self.compiles), self.cache_hits


@dataclasses.dataclass
class Context:
    """What a per-layer reader sees."""

    trace: object  # traces.Trace or None
    steps: int  # LSRK steps in the window
    rhs_evals: int  # rhs evaluations in the window (5 per step)
    elements: int  # real elements of the mesh
    order: int
    device_kind: str
    counters: dict


def check_devices(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


def use_cache() -> str:
    """The program's persistent compilation cache (``JAX_COMPILATION_CACHE_DIR``,
    else ``<checkout>/.jax_cache``), with every program in it however quick
    to compile or small, so that a second run compiles nothing.  Returns
    its directory."""
    import jax

    _paths()
    from repro.launch.compile_cache import use_compile_cache

    path = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def passes(err: float, limit: float) -> bool:
    """The test that decides ``correct`` for one number compared."""
    return bool(math.isfinite(err) and err <= limit)


def _span(name: str, on: bool):
    import jax

    return jax.profiler.TraceAnnotation(name) if on else contextlib.nullcontext()


def measured_window(dispatch, q, seconds: float, traced: bool):
    """Dispatch with one dispatch always queued behind the one running, and
    stop queuing once the one in flight will end past ``seconds`` (judged by
    the mean time of the dispatches done so far).  The window ends when that
    last dispatch has finished.  Returns (state, dispatches, wall seconds,
    the seconds at which each dispatch was seen finished)."""
    import jax

    t0 = time.perf_counter()
    seen = []
    with _span("bench.window", traced):
        with _span("bench.dispatch", traced):
            nxt = dispatch(q)
        n = 1
        while True:
            cur = nxt
            with _span("bench.dispatch", traced):
                nxt = dispatch(cur)
            n += 1
            with _span("bench.wait", traced):
                jax.block_until_ready(cur)
            done = time.perf_counter() - t0
            seen.append(done)
            if done + done / (n - 1) >= seconds:
                break
        with _span("bench.wait", traced):
            jax.block_until_ready(nxt)
        wall = time.perf_counter() - t0
    return nxt, n, wall, seen + [wall]


def reference_sharding(devices, chips: int):
    """How the reference and the initial field lie: on one device, or with
    the element axis split over the cell's chips."""
    if chips == 1:
        return None
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(devices[:chips], ("k",))
    return NamedSharding(mesh, PartitionSpec(None, None, None, None, "k"))


def field_err(q, q_ref) -> float:
    """max |q - q_ref| / max |q_ref| over the whole state."""
    import numpy as np

    q, q_ref = np.asarray(q, np.float32), np.asarray(q_ref, np.float32)
    return float(np.max(np.abs(q - q_ref)) / np.max(np.abs(q_ref)))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *, t_start: float,
             devices=None, kernel_impl: Optional[str] = None, cache: bool = True,
             log=print, fault=None) -> dict:
    """One run; returns the result line's object.  ``devices`` and
    ``kernel_impl`` default to the chip's (``check_devices``) and the
    configuration's; ``cache=False`` (tests) leaves the persistent cache
    off; ``fault`` (tests only) wraps the driver to break the timed path."""
    import jax
    import numpy as np

    _paths()
    from reference import dgsem
    import inputs

    phases = [("imports", time.perf_counter())]
    chips = int(cell.workload["chips"])
    devices = check_devices(chips) if devices is None else devices
    cache_dir = use_cache() if cache else None
    phases.append(("devices", time.perf_counter()))
    clog = CompileLog.get()
    c0 = clog.mark()
    cfg, traffic = cell.config, cell.traffic
    prob = dgsem.Problem(cfg)
    ref_sharding = reference_sharding(devices, chips)
    q0 = jax.block_until_ready(inputs.initial_field(prob, seed, traffic, ref_sharding))
    phases.append(("field", time.perf_counter()))
    drv_mod = _module(os.path.join(BENCH, "drivers", traffic["driver"] + ".py"))
    drv = drv_mod.Driver(cfg, traffic, prob, q0, devices,
                         kernel_impl or cfg["kernels"])
    del q0
    if fault is not None:
        drv = fault(drv)
    jax.block_until_ready(drv.state)
    phases.append(("driver", time.perf_counter()))
    # the warm dispatch: the window's own program (its trip count is traced,
    # so one step loads or compiles the same program as a full dispatch)
    warm_steps = int(traffic["warm_steps"])
    q = jax.block_until_ready(drv.dispatch(drv.state, warm_steps))
    drv.state = None
    phases.append(("warm", time.perf_counter()))
    setup_s = time.perf_counter() - t_start
    c1 = clog.mark()
    phase_s = {name: t - prev for (_, prev), (name, t) in
               zip([("start", t_start)] + phases[:-1], phases)}

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    try:
        q, n_disp, wall, seen = measured_window(drv.dispatch, q, seconds, trace)
    finally:
        if trace:
            jax.profiler.stop_trace()
    c2 = clog.mark()
    window_steps = n_disp * drv.steps_per_dispatch

    used = drv.devices
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in used]
    counters = drv.counters()
    q_prog = drv.to_reference(q)
    del q, drv
    kind = used[0].device_kind

    log(f"[device] platform {used[0].platform} kind {kind} count {jax.device_count()} "
        f"used {len(used)}")
    log(f"[setup] setup_s {setup_s} programs_compiled_or_loaded {c1[0] - c0[0]} "
        f"({sum(clog.compiles[c0[0]:c1[0]])} s) persistent_cache_hits {c1[1] - c0[1]} "
        f"cache {cache_dir} warm_steps {warm_steps}")
    log(f"[setup] phases_s {json.dumps(phase_s)}")
    log(f"[window] compiles_inside_window {(c2[0] - c1[0]) + (c2[1] - c1[1])} "
        f"dispatches {n_disp} steps {window_steps} wall_s {wall} seed {seed} "
        f"finished_at_s {seen}")
    log(f"[ledger] {json.dumps(counters)}")
    log(f"[memory] peak_bytes_in_use per device {peaks}")

    # the plain reference over the same steps, after the program's state is gone
    t_ref = time.perf_counter()
    q0 = inputs.initial_field(prob, seed, traffic, ref_sharding)
    q_ref = np.asarray(dgsem.make_run(prob)(q0, warm_steps + window_steps))
    del q0
    err = field_err(q_prog, q_ref)
    limit = float(cell.limits["field_err"]["limit"])
    correct = passes(err, limit)
    log(f"[reference] steps {warm_steps + window_steps} seconds {time.perf_counter() - t_ref}")

    elements = prob.K
    metrics, extra = {}, {}
    if trace:
        import traces

        tr = traces.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = Context(trace=tr, steps=window_steps, rhs_evals=5 * window_steps,
                      elements=elements, order=prob.order, device_kind=kind,
                      counters=counters)
        for m in cell.per_layer:
            value = _module(os.path.join(BENCH, "metrics", m["name"] + ".py")).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        window = tr.window
        busy = [traces.measure(traces.busy(ev, window)) * 1e-9 for ev in tr.devices.values()]
        for k in ("dg_volume", "dg_flux"):
            counts = [traces.kernel_ns(ev, k, window)[1] for ev in tr.devices.values()]
            log(f"[trace] {k} events per device {counts} rhs_evals {5 * window_steps}")
        log(f"[trace] devices {sorted(tr.devices)} window_s {tr.window_s} busy_s {busy}")
        extra = {"busy_s": sum(busy) / max(1, len(busy)), "window_s": tr.window_s}
        breakdown = {"device_ops": traces.top_ops(tr), "idle_gaps": traces.top_gaps(tr)}
    else:
        values = {"elem_steps_per_s": elements * window_steps / wall, "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    result = {
        "correct": correct,
        "attempted": n_disp,
        "failed": 0 if correct else n_disp,
        "metrics": metrics,
        "device": {"platform": used[0].platform, "kind": kind, "count": jax.device_count(),
                   "memory_peak_bytes": max(peaks), **extra},
    }
    if trace:
        result["breakdown"] = breakdown
    result["checks"] = {"field_err": {"value": err, "limit": limit}}
    return result
