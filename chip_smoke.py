#!/usr/bin/env python3
"""Bring-up smoke of the DG main path on a TPU.

Runs the paper's problem — the ``dg-paper`` scenario: coupled
elastic–acoustic DGSEM, order 7, 32x16x16 = 8192 elements — for the
118-step horizon of ``configs/dg_wave.py`` in float32, through the library
entry points a user calls, and checks the answer against the XLA path.

  python chip_smoke.py               # one chip
  python chip_smoke.py --four-chips  # the sharded path on a four-chip host

One chip: ``BlockedDGEngine`` over a 4-way ``NestedPartitionExecutor`` with
both Pallas kernels compiled by Mosaic (``kernel_impl="pallas"``): one
fused ``run`` (twice: cold, then warm) and one ``run(observe=True)`` whose
rebalance chunks calibrate -> solve -> resplice on the chip, under an
injected 2x straggler so the resplice moves work.  The reference is the
flat ``DGSolver`` with ``kernel_impl="xla"``.

Four chips: ``PartitionedDG`` slabs over a 4-device mesh on 4 x 8192
elements (128x16x16, the same element size), run twice (cold, then warm),
against the flat XLA solver on one chip.

Everything runs in this one process, with x64 off.  Without a TPU it exits
non-zero and prints no result.  Its last line is one JSON object naming the
device; every earlier line is a measurement or a check.  Times are of a
smoke run, not a benchmark.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# Largest max|q - q_ref| / max|q_ref| accepted on the final field.  Both runs
# are float32 and do the same arithmetic in a different order (the volume
# kernel's dense M^3 x M^3 operator against XLA's tensor-product einsum; the
# MXU's fp32 mode is multi-pass bf16).  On this problem a float32 run drifts
# 4.1e-6 of max|q| from the float64 one over the 118 steps (CPU, order 7,
# element size 1/16), so two float32 runs sit within about twice that.  A
# wrong kernel or halo is off by O(1).
TOL = 5e-5

PARTITIONS = 4  # nested-partition nodes on the one chip
REBALANCE_EVERY = 40  # steps per observed chunk: 3 chunks over the 118 steps
N_DEV = 4  # devices of the sharded phase

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    """Backend compiles (program name, seconds) and persistent-cache hits,
    from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.compiles = []
        self.cache_hits = 0
        self._shown = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kwargs):
        if event == COMPILE_EVENT:
            self.compiles.append((str(kwargs.get("fun_name", "?")), float(duration)))

    def _on_event(self, event, **kwargs):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def show(self, phase: str) -> None:
        """Print the compiles since the last call: each program that took
        0.1 s or more, and the count and sum of the small eager ones."""
        new = self.compiles[self._shown:]
        self._shown = len(self.compiles)
        small = [secs for _, secs in new if secs < 0.1]
        for name, secs in new:
            if secs >= 0.1:
                print(f"[{phase}] compile_s {name}: {secs}")
        print(f"[{phase}] compile_s {len(small)} small programs: {sum(small)}")


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)
    print(f"check ok: {msg}")


def timed(fn):
    """(result, wall seconds) of ``fn()``, waited on."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def rel_diff(q, q_ref) -> float:
    import numpy as np

    q, q_ref = np.asarray(q, np.float64), np.asarray(q_ref, np.float64)
    return float(np.abs(q - q_ref).max() / np.abs(q_ref).max())


def paper_problem(kernel_impl: str, **overrides):
    """The registered ``dg-paper`` solver in float32."""
    from repro.configs.registry import resolve_scenario

    return resolve_scenario("dg-paper").build(
        dtype="float32", kernel_impl=kernel_impl, **overrides
    )


def horizon(solver):
    """(steps, dt): the horizon of ``configs/dg_wave.py`` at the solver's
    CFL step."""
    from repro.configs.dg_wave import CONFIG

    return CONFIG.n_steps, solver.cfl_dt()


def reference_run(ref, q0, n, dt):
    """The flat XLA solver with full float32 products: the MXU's default
    for an f32 dot is one bf16 pass, and the Pallas volume kernel asks for
    full precision, so both sides of the comparison compute in float32."""
    import jax

    with jax.default_matmul_precision("highest"):
        return timed(lambda: ref.run(q0, n, dt=dt))


def pulse(solver):
    """A Gaussian velocity pulse on the material interface (the x
    midplane), so the acoustic, elastic and coupled fluxes all act."""
    from repro.dg.solver import gaussian_pulse

    ex, ey, ez = solver.mesh.extent
    return gaussian_pulse(solver, center=(ex / 2, ey / 2, ez / 2))


def check_energy(tag: str, solver, q0, q) -> None:
    e0, e1 = solver.energy(q0), solver.energy(q)
    print(f"[{tag}] energy_before: {e0}")
    print(f"[{tag}] energy_after: {e1}")
    check(math.isfinite(e1) and e1 <= e0, f"{tag}: energy finite and not growing")


def peak_bytes(dev) -> None:
    stats = dev.memory_stats() or {}
    print(f"[{dev}] peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}")


def one_chip(log: CompileLog, kernel_impl: str = "pallas", **overrides) -> None:
    """The nested-partition fused pipeline against the flat XLA solver."""
    import numpy as np

    from repro.runtime import BlockedDGEngine, NestedPartitionExecutor

    solver = paper_problem(kernel_impl, **overrides)
    ref = paper_problem("xla", **overrides)
    n, dt = horizon(solver)
    K = solver.mesh.K
    print(f"[problem] dg-paper order {solver.order}, grid {solver.mesh.grid}, "
          f"K {K}, float32, {n} steps, dt {dt}, kernel_impl {kernel_impl}")
    q0 = pulse(solver)

    ex = NestedPartitionExecutor(K, PARTITIONS, grid_dims=solver.mesh.grid,
                                 rebalance_every=REBALANCE_EVERY)
    eng = BlockedDGEngine(solver, ex)
    q_cold, t_cold = timed(lambda: eng.run(q0, n, dt=dt))
    log.show("fused")
    q_f, t_warm = timed(lambda: eng.run(q0, n, dt=dt))
    print(f"[fused] first_call_s (compile + run): {t_cold}")
    print(f"[fused] warm_run_s: {t_warm}")
    print(f"[fused] steps_per_s (smoke, not a benchmark): {n / t_warm}")
    check(np.array_equal(np.asarray(q_cold), np.asarray(q_f)), "fused: warm run repeats the cold run")

    ex.inject_straggler(0, 2.0)
    counts0 = ex.counts.copy()
    q_o, t_obs = timed(lambda: eng.run(q0, n, dt=dt, observe=True))
    log.show("observe")
    chunks = -(-n // REBALANCE_EVERY)
    print(f"[observe] run_s (compiles included): {t_obs}")
    print(f"[observe] counts before: {counts0.tolist()} after: {ex.counts.tolist()} "
          f"rebalances: {ex.round}")
    check(ex.round >= 1 and ex.counts[0] < counts0[0],
          "observe: calibrate -> solve -> resplice moved work off the straggler")

    stats = eng.pipeline().stats
    print(f"[ledger] dispatches: {stats.dispatches} steps_run: {stats.steps_run} "
          f"observe_chunks: {stats.observe_chunks} kernel_launches: {stats.kernel_launches}")
    check(stats.kernel_launches == {"volume": 1, "surface": 1},
          "ledger: one volume and one surface launch per rhs")
    check(stats.dispatches == 2 + chunks and stats.observe_chunks == chunks,
          "ledger: one dispatch per fused run and per observed chunk")

    q_r, t_ref = reference_run(ref, q0, n, dt)
    log.show("xla-ref")
    print(f"[xla-ref] first_call_s (compile + run): {t_ref}")
    # what the library's default matmul precision does to the XLA path
    # (a reading, not a check)
    q_d, _ = timed(lambda: ref.run(q0, n, dt=dt))
    log.show("xla-default-precision")
    print(f"[xla-default-precision] max_abs_diff_over_max_abs_q vs xla-ref: "
          f"{rel_diff(q_d, q_r)}")
    for tag, q in (("fused", q_f), ("observe", q_o)):
        d = rel_diff(q, q_r)
        print(f"[{tag}] max_abs_diff_over_max_abs_q vs xla: {d}")
        check(d < TOL, f"{tag}: final field within {TOL} of the xla run")
        check_energy(tag, solver, q0, q)
    check_energy("xla-ref", ref, q0, q_r)


def four_chips(log: CompileLog, kernel_impl: str = "pallas",
               grid=(128, 16, 16), extent=(8.0, 1.0, 1.0)) -> None:
    """``PartitionedDG`` x-slabs over a 4-device mesh against the flat XLA
    solver on one device."""
    import jax
    import numpy as np

    from repro.dg.partitioned import PartitionedDG
    from repro.launch.mesh import make_mesh

    devs = jax.devices()
    check(len(devs) >= N_DEV, f"{N_DEV} devices visible")
    mesh = make_mesh((N_DEV,), ("data",), devices=devs[:N_DEV])
    solver = paper_problem(kernel_impl, grid=grid, extent=extent)
    ref = paper_problem("xla", grid=grid, extent=extent)
    n, dt = horizon(solver)
    print(f"[problem] dg-paper x{N_DEV} order {solver.order}, grid {solver.mesh.grid}, "
          f"K {solver.mesh.K}, float32, {n} steps, dt {dt}, kernel_impl {kernel_impl}")
    q0 = pulse(solver)
    pdg = PartitionedDG(solver=solver, mesh_axes=mesh)
    qp0 = pdg.permute_in(q0)
    in_devs = [s.device for s in qp0.addressable_shards]
    check(len(in_devs) == N_DEV and set(in_devs) == set(mesh.devices.flat),
          f"permute_in: one slab on each of {N_DEV} devices")
    qp_cold, t_cold = timed(lambda: pdg.run(qp0, n, dt=dt))
    log.show("sharded")
    qp, t_warm = timed(lambda: pdg.run(qp0, n, dt=dt))
    print(f"[sharded] first_call_s (compile + run): {t_cold}")
    print(f"[sharded] warm_run_s: {t_warm}")
    print(f"[sharded] steps_per_s (smoke, not a benchmark): {n / t_warm}")
    check(np.array_equal(np.asarray(qp_cold), np.asarray(qp)),
          "sharded: warm run repeats the cold run")
    shard_devs = {s.device for s in qp.addressable_shards}
    print(f"[sharded] shard devices: {sorted(str(d) for d in shard_devs)}")
    check(len(shard_devs) == N_DEV and shard_devs == set(mesh.devices.flat),
          f"sharded: the state spans {N_DEV} distinct devices")
    stats = pdg.pipeline().stats
    print(f"[ledger] dispatches: {stats.dispatches} steps_run: {stats.steps_run}")
    check(stats.dispatches == 2, "ledger: one dispatch per sharded run")

    q_s = pdg.permute_out(qp)
    q_r, t_ref = reference_run(ref, q0, n, dt)
    log.show("xla-ref")
    print(f"[xla-ref] first_call_s (compile + run): {t_ref}")
    d = rel_diff(q_s, q_r)
    print(f"[sharded] max_abs_diff_over_max_abs_q vs xla: {d}")
    check(d < TOL, f"sharded: final field within {TOL} of the flat xla run")
    check_energy("sharded", solver, q0, q_s)
    check_energy("xla-ref", ref, q0, q_r)
    for dev in devs[:N_DEV]:
        peak_bytes(dev)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded 4 x 8192-element path and its "
                         "flat one-chip comparison")
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_enable_x64", False)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}", file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.launch.compile_cache import use_compile_cache

    print(f"[setup] compile cache: {use_compile_cache()}")
    log = CompileLog()
    if args.four_chips:
        four_chips(log)
    else:
        one_chip(log)
        peak_bytes(devs[0])
    print(f"[setup] persistent cache hits: {log.cache_hits}")
    d = devs[0]
    print(json.dumps({"ok": True, "device": {"platform": d.platform,
                                             "kind": d.device_kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
