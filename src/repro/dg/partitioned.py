"""Nested-partition execution of the DG solver (paper section 5).

Level 1 — inter-node: elements are split into contiguous x-slabs, one per
device along the ``data`` mesh axis; the once-per-stage face exchange
between slabs is a ring ``lax.ppermute`` (`halo_exchange_1d`) of the
slab-edge *element layers*.

Level 2 — intra-node boundary/interior: the rhs is a
``repro.runtime.schedule.StepSchedule`` instantiation — the slab-edge
layers are packed and launched into the ring (boundary + exchange phases),
the volume kernel runs on the slab's own elements with no halo dependence
(interior phase), and the received layers are appended to the slab and the
full surface flux folds in last (correction phase).  XLA's scheduler
overlaps the ppermute DMA with the interior compute — the paper's Fig 5.1
expressed as dataflow.

The exchanged payload is the whole edge element layer ``q[:L]`` / ``q[-L:]``
(not just the extracted face traces): the receiving slab then evaluates
``surface_rhs`` on the *extended* block ``q[own ++ halo_lo ++ halo_hi]``
with a neighbour table that resolves cross-slab faces into the halo rows —
exactly the assemble-then-flux structure of
``repro.runtime.executor.BlockedDGEngine``, with the halo gather replaced
by a device-resident collective.  Two deliberate costs versus the old
face-trace payload schedule: ~M/2x more wire bytes per exchange, and the
surface flux (intra-slab faces included) now executes entirely in the
correction phase, so only the volume kernel overlaps the ring DMA — the
same interior=volume / correction=flux phase split ``BlockedDGEngine``
uses, which is also how ``CalibrationReport`` already attributes phase
times for the planner (``boundary_s`` is "face-flux work wherever it
executes").  What that buys is the acceptance invariant:

Correctness invariant (tested in ``tests/test_multidevice.py``): the
partitioned rhs/run equals the flat single-array solver BITWISE — every own
element's six face corrections are computed by the same ``surface_rhs``
arithmetic from the same neighbour values (halo rows carry the exact rows
of the remote elements), so the partition is a reordering, never an
approximation.  Periodic bricks wrap through the same ring (``wrap=True``
ppermute for the x direction; y/z wraps stay intra-slab).

Fused multi-device driver: ``run`` (default ``fused=True``) adopts
``repro.runtime.pipeline.ShardedStepPipeline`` — the whole time loop as ONE
donated ``shard_map`` program spanning all devices, with the ring exchange
inside the compiled step loop.  The per-step jitted driver survives as
``fused=False`` solely for calibration/reference (mirroring how
``BlockedDGEngine`` kept the four-phase path).

Online rebalancing: ``run(..., observe=True)`` adopts the step-driver API of
``repro.runtime.executor.NestedPartitionExecutor`` — each fused chunk runs
through the pipeline's in-scan observation channel
(``ShardedStepPipeline.run_observed``: per-shard accumulators psum-reduced
inside the compiled program, chunk wall time attributed by their shares)
and the bound executor (``bind_executor`` / ``make_executor``) re-solves
the nested split on schedule.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.overlap import halo_exchange_1d
from repro.dg.mesh import BrickMesh  # noqa: F401 — referenced in docs
from repro.dg.operators import surface_rhs, volume_rhs_impl
from repro.dg.rk import lsrk45_step
from repro.dg.solver import DGSolver
from repro.runtime.schedule import StepSchedule


def slab_order(grid) -> Tuple[np.ndarray, np.ndarray]:
    """(order, inv): elements reordered x-major so each x-slab is contiguous
    and each x-layer within a slab is contiguous (rows of a layer sorted by
    (iy, iz) — the ordering both ends of the ring agree on)."""
    nx, ny, nz = grid
    K = nx * ny * nz
    ix = np.arange(K) % nx
    iy = (np.arange(K) // nx) % ny
    iz = np.arange(K) // (nx * ny)
    order = np.lexsort((iz, iy, ix))  # primary key ix
    inv = np.empty(K, np.int64)
    inv[order] = np.arange(K)
    return order, inv


def build_slab_tables(neighbors: np.ndarray, grid, n_slabs: int):
    """Per-slab extended-block tables for the ring halo exchange.

    Each slab's extended block is ``[own (per) ++ halo_lo (L) ++ halo_hi
    (L)]`` where ``halo_lo``/``halo_hi`` are the previous slab's last x-layer
    and the next slab's first x-layer (what `halo_exchange_1d` delivers).
    Returns ``(order, inv, nbr_ext, ext_ids, x_wrap)``:

    * ``nbr_ext`` (P, per+2L, 6): slab-local neighbour table over the
      extended block — own rows resolve every face to an own or halo row
      (or -1 physical mirror); halo rows are -1 (their flux output is
      discarded);
    * ``ext_ids`` (P, per+2L): permuted element ids backing each extended
      row (for gathering the static material lines);
    * ``x_wrap``: whether the x direction wraps (periodic brick) — the ring
      ppermute then wraps too.

    ``neighbors`` is the SOLVER mesh's table, so periodic bricks keep their
    wrapping faces: x-wraps ride the ring, y/z wraps stay intra-slab.
    """
    nx, ny, nz = grid
    if nx % n_slabs:
        raise ValueError(f"nx={nx} not divisible by {n_slabs} slabs")
    K = nx * ny * nz
    per = K // n_slabs
    L = ny * nz
    order, inv = slab_order(grid)
    nbr = np.asarray(neighbors, dtype=np.int64)
    # permuted table: new id -> new ids of its 6 face neighbours (-1 kept)
    nbr_p = np.where(nbr[order] >= 0, inv[np.clip(nbr[order], 0, None)], -1)
    # the ring wraps iff the mesh is x-periodic (an ix=0 element — order[0]
    # is one — has a -x neighbour) AND that wrap actually crosses slabs
    x_wrap = bool(nbr_p[0, 0] >= 0) and n_slabs > 1

    ext_n = per + 2 * L
    nbr_ext = np.full((n_slabs, ext_n, 6), -1, np.int64)
    ext_ids = np.zeros((n_slabs, ext_n), np.int64)
    for d in range(n_slabs):
        own = np.arange(d * per, (d + 1) * per)
        # ring payload sources (permuted ids); at a non-wrapping global
        # boundary the ring delivers zeros and no own face references the
        # halo rows, so the id is only a dummy for finite material lines
        prev_hi = np.arange((((d - 1) % n_slabs) + 1) * per - L,
                            (((d - 1) % n_slabs) + 1) * per)
        next_lo = np.arange(((d + 1) % n_slabs) * per,
                            ((d + 1) % n_slabs) * per + L)
        ext_ids[d] = np.concatenate([own, prev_hi, next_lo])

        nn = nbr_p[own]  # (per, 6) permuted-global neighbour ids
        same = (nn >= 0) & (nn // per == d)
        out = np.where(same, nn - d * per, -1)
        cross = (nn >= 0) & ~same
        # -x cross faces live in the first layer and land on halo_lo row j
        # (layers at both ring ends are (iy, iz)-sorted, so offsets line up)
        if cross[:, 0].any():
            assert not cross[L:, 0].any(), "cross-slab -x face outside the edge layer"
            assert (nn[:L, 0][cross[:L, 0]] == prev_hi[cross[:L, 0]]).all()
            out[:L, 0] = np.where(cross[:L, 0], per + np.arange(L), out[:L, 0])
        if cross[:, 1].any():
            assert not cross[:-L, 1].any(), "cross-slab +x face outside the edge layer"
            assert (nn[-L:, 1][cross[-L:, 1]] == next_lo[cross[-L:, 1]]).all()
            out[-L:, 1] = np.where(cross[-L:, 1], per + L + np.arange(L), out[-L:, 1])
        # slabs span the full y/z extent: no other face can cross
        assert not cross[:, 2:].any(), "cross-slab y/z face (slabs must span y,z)"
        nbr_ext[d, :per] = out
    return order, inv, nbr_ext, ext_ids, x_wrap


@dataclasses.dataclass
class PartitionedDG:
    """shard_map slab execution of a DGSolver."""

    solver: DGSolver
    mesh_axes: Mesh
    axis: str = "data"

    def __post_init__(self):
        s = self.solver
        self.P = self.mesh_axes.shape[self.axis]
        nx, ny, nz = s.mesh.grid
        self.K_loc = s.mesh.K // self.P
        self.layer = ny * nz  # elements per x-layer
        self.order_perm, inv, nbr_ext, ext_ids, self.x_wrap = build_slab_tables(
            s.mesh.neighbors, s.mesh.grid, self.P
        )
        self.inv_perm = inv
        self.spec_q = P(self.axis, None, None, None, None)
        self.spec_e = P(self.axis)
        # global sharded tables: (P * ext_n, ...) with one slab's extended
        # block per device (materials are static — only q rides the ring)
        ids = self.order_perm[ext_ids.reshape(-1)]
        line = lambda v: self.place(np.asarray(v)[ids], self.spec_e)
        self.nbr_e = self.place(nbr_ext.reshape(-1, 6), P(self.axis, None))
        self.rho_e, self.lam_e, self.mu_e = line(s.rho_j), line(s.lam_j), line(s.mu_j)
        self.cp_e, self.cs_e = line(s.cp_j), line(s.cs_j)
        self._pipeline = None
        self._step_jit = None
        self._executor = None

    # ------------------------------------------------------------------
    def place(self, x, spec: P) -> jax.Array:
        """A host array as a global array sharded by ``spec`` over the
        mesh: each device receives only its own block, straight from the
        host (nothing is staged on one device)."""
        x = np.asarray(x)
        sharding = jax.sharding.NamedSharding(self.mesh_axes, spec)
        return jax.make_array_from_callback(x.shape, sharding, lambda idx: x[idx])

    def permute_in(self, q_flat) -> jax.Array:
        """The flat-order state in slab order, sharded over the mesh axis
        (``spec_q``).  The permutation runs on the host and each device
        receives only its slab: a gather of the whole state on one device
        takes a lane-padded layout many times the state's size."""
        return self.place(np.asarray(q_flat)[self.order_perm], self.spec_q)

    def permute_out(self, q_part) -> np.ndarray:
        """The slab-order state back in flat order, on the host."""
        return np.asarray(q_part)[self.inv_perm]

    # ------------------------------------------------------------------
    def _make_schedule(self) -> StepSchedule:
        """The slab rhs as the shared four-phase schedule: pack the slab-edge
        element layers -> ring exchange -> volume on own elements -> extended
        surface flux fold.  Runs inside ``shard_map`` (eagerly per stage via
        :meth:`rhs`, or inside the fused compiled loop of
        ``repro.runtime.pipeline.ShardedStepPipeline``)."""
        s = self.solver
        L = self.layer
        per = self.K_loc

        def boundary(st):
            # the pack: both slab-edge element layers (contiguous slices)
            q = st["q"]
            return {"lo": q[:L], "hi": q[-L:]}

        def exchange(send, st):
            from_prev, from_next = halo_exchange_1d(
                send["lo"], send["hi"], self.axis, wrap=self.x_wrap
            )
            return {"from_prev": from_prev, "from_next": from_next}

        def interior(st):
            # volume on own elements: no dependence on the ring payload;
            # kernel_impl threads through so the Pallas volume kernel runs
            # inside the SPMD slab path too
            return volume_rhs_impl(
                st["q"], s.D, s.metrics,
                st["rho"][:per], st["lam"][:per], st["mu"][:per],
                kernel_impl=s.kernel_impl,
            )

        def correction(out, recv, st):
            # extended block [own ++ halo_lo ++ halo_hi]: the same assemble-
            # then-flux structure as BlockedDGEngine, so every own row's six
            # face corrections are bitwise the flat solver's (halo rows'
            # output is dropped by the slice)
            q_ext = jnp.concatenate([st["q"], recv["from_prev"], recv["from_next"]])
            sur = surface_rhs(
                q_ext, st["nbr"], s.lift,
                st["rho"], st["lam"], st["mu"], st["cp"], st["cs"],
                kernel_impl=s.kernel_impl,
            )
            return out + sur[:per]

        return StepSchedule(boundary=boundary, exchange=exchange,
                            interior=interior, correction=correction, name="slab-spmd")

    def _rhs_local(self, q, nbr, rho, lam, mu, cp, cs):
        """Per-device rhs with ring halo exchange; runs inside shard_map."""
        state = {"q": q, "nbr": nbr, "rho": rho, "lam": lam, "mu": mu,
                 "cp": cp, "cs": cs}
        return self._make_schedule().rhs(state)

    def _operands(self):
        """The static sharded tables every rhs evaluation threads through."""
        return (self.nbr_e, self.rho_e, self.lam_e, self.mu_e, self.cp_e, self.cs_e)

    def _operand_specs(self):
        e = self.spec_e
        return (P(self.axis, None), e, e, e, e, e)

    # ------------------------------------------------------------------
    def rhs(self, q_part: jnp.ndarray) -> jnp.ndarray:
        """Global-view rhs on the permuted state (sharded over the axis)."""
        f = jax.shard_map(
            self._rhs_local,
            mesh=self.mesh_axes,
            in_specs=(self.spec_q,) + self._operand_specs(),
            out_specs=self.spec_q,
            check_vma=False,
        )
        return f(q_part, *self._operands())

    def make_executor(self, bucket: int = 16, **kwargs):
        """An online auto-rebalancing executor matching this decomposition
        (one partition per slab)."""
        from repro.runtime.executor import NestedPartitionExecutor

        return NestedPartitionExecutor(
            self.solver.mesh.K,
            self.P,
            grid_dims=self.solver.mesh.grid,
            bucket=bucket,
            **kwargs,
        )

    def pipeline(self):
        """The fused multi-device step pipeline bound to this decomposition:
        ONE donated shard_map program — step loop, stage scan, and the ring
        ppermute exchange all inside (built lazily, cached)."""
        if self._pipeline is None:
            from repro.runtime.pipeline import ShardedStepPipeline

            self._pipeline = ShardedStepPipeline(self)
        return self._pipeline

    def bind_executor(self, executor=None):
        """Install (or lazily create) the engine-owned executor that
        ``run(observe=True)`` feeds.  Returns it."""
        if executor is not None:
            self._executor = executor
        elif getattr(self, "_executor", None) is None:
            self._executor = self.make_executor()
        return self._executor

    def calibrate(self, q_part: jnp.ndarray, reps: int = 1,
                  dt: Optional[float] = None) -> "CalibrationReport":
        """Synchronous-step calibration: under the SPMD barrier every slab's
        step time equals the wall time, so the report attributes the same
        measured whole-step seconds to each of the P slabs
        (``observe_total`` semantics).  Per-slab skew is not separable on
        this engine — the blocked engine exists for that."""
        from repro.runtime.schedule import CalibrationReport

        dt = dt or self.solver.cfl_dt()
        if self._step_jit is None:
            self._step_jit = jax.jit(
                lambda q, res, dt: lsrk45_step(q, res, self.rhs, dt)
            )
        res = jnp.zeros_like(q_part)
        dt_j = jnp.asarray(dt, q_part.dtype)
        out = self._step_jit(q_part, res, dt_j)
        jax.block_until_ready(out)  # warmup / compile
        ts = []
        for _ in range(max(1, reps)):
            t0 = time.perf_counter()
            out = self._step_jit(q_part, res, dt_j)
            jax.block_until_ready(out)
            ts.append(time.perf_counter() - t0)
        ts.sort()
        return CalibrationReport.from_totals(np.full(self.P, ts[len(ts) // 2]))

    def resplice(self, plan) -> None:
        """Apply a solved plan to the bound executor.  Slab geometry itself
        is SPMD-fixed (equal K/P slabs inside ``shard_map``); the plan
        lands in the executor's bookkeeping/hooks, which is where blocked
        consumers of the same executor pick it up."""
        self.bind_executor().apply(plan)

    def run(
        self,
        q_part: jnp.ndarray,
        n_steps: int,
        dt: Optional[float] = None,
        *,
        observe: bool = False,
        fused: bool = True,
    ) -> jnp.ndarray:
        """Advance ``n_steps``.

        ``fused`` (default) drives the ``ShardedStepPipeline``: the whole
        time loop runs as a single donated device program spanning all
        devices — one host dispatch per run (per rebalance chunk when
        observing), independent of device count, slab count and horizon.
        ``fused=False`` is the eager per-step reference driver (one jitted
        step per host dispatch) kept for calibration and differential tests.

        With ``observe=True`` the run is segmented on the bound executor's
        (``bind_executor`` / ``make_executor``) rebalance schedule: each
        chunk is ONE fused dispatch through the pipeline's in-scan
        observation channel — per-shard cost accumulators psum-reduced
        inside the compiled program, the chunk's wall time attributed by
        their shares — and the nested split re-solved, so the
        calibrate->solve->resplice loop runs at full fused speed alongside
        the SPMD compute."""
        executor = self.bind_executor() if observe else None
        dt = dt or self.solver.cfl_dt()

        if fused:
            pipe = self.pipeline()
            if executor is None:
                return pipe.run(q_part, n_steps, dt=dt)
            done = 0
            while done < n_steps:
                chunk = n_steps - done
                if executor.rebalance_every > 0:
                    chunk = min(executor.rebalance_every, chunk)
                q_part, report = pipe.run_observed(q_part, chunk, dt=dt)
                executor.observe_chunk(report, chunk)
                done += chunk
            return q_part

        # eager reference driver: one jitted step per dispatch (shared
        # compiled step; dt is a traced operand so it compiles once)
        if self._step_jit is None:
            self._step_jit = jax.jit(
                lambda q, res, dt: lsrk45_step(q, res, self.rhs, dt)
            )
        res = jnp.zeros_like(q_part)
        dt_j = jnp.asarray(dt, q_part.dtype)
        done = 0
        while done < n_steps:
            chunk = n_steps - done
            if executor is not None and executor.rebalance_every > 0:
                chunk = min(executor.rebalance_every, chunk)
            t0 = time.perf_counter()
            for _ in range(chunk):
                q_part, res = self._step_jit(q_part, res, dt_j)
            if executor is not None:
                jax.block_until_ready(q_part)
                executor.observe_total((time.perf_counter() - t0) / chunk)
                executor.advance(chunk)
            done += chunk
        return q_part
