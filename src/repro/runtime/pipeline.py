"""Fused, donated, scan-compiled time stepping for the DG step drivers.

The paper's overlap schedule only pays off once each partition's step is a
single resident device program (cf. the fused propagate/collide kernels of
Calore et al. and the per-device kernel specialization of Borrell et al.).
``BlockedDGEngine`` historically drove LSRK4(5) from Python — 5 stages x P
blocks x ~6 separate jit calls per RHS evaluation, a fresh ``(K+1, ...)``
scatter target allocated per call, no buffer donation — so the blocked path
burned its budget on host dispatch.  ``FusedStepPipeline`` compiles the
entire blocked time loop into ONE donated program:

* **compiled step loop** — ``lax.fori_loop`` with a *traced* trip count and
  the ``(q, res)`` low-storage carry donated (``donate_argnums``), so the
  whole run is one host dispatch, the carry is updated in place, and ONE
  compiled program per bucket signature serves every horizon;
* **scan over stages** — the five LSRK4(5) stages are the inner
  ``lax.scan`` of ``repro.dg.rk.lsrk45_step``, traced once;
* **envelope batching** (default ``layout="envelope"``) — ALL blocks are
  padded to a common envelope ``(env, env_own)`` = (max ext pad, max own
  pad) and stacked, so the whole heterogeneous split becomes exactly ONE
  volume launch and ONE surface launch per rhs no matter how many bucket
  sizes or profile groups the partitioner produced.  Pad rows gather
  ``q[0]`` with unit materials, carry ``nbr = -1`` sentinels (no real row
  ever references them) and scatter to the dump row ``K``, so the masked
  tail is arithmetically inert and the result stays bitwise identical to
  the per-bucket path: the kernels are block-diagonal / per-row over the
  element axis, so real rows see the exact same operands either way.  The
  ledgered ``stats.kernel_launches`` counter (recorded at trace time)
  asserts the one-launch property;
* **bucket batching** (``layout="grouped"``, the differential reference) —
  blocks sharing a padded ``(ext, own)`` size (and profile group, see
  below) are stacked and the block RHS is batched over the stacked element
  axis, so P same-bucket partitions become ONE volume launch and ONE
  surface launch per *bucket*.  The element axis is the batch axis the
  kernels (XLA einsum and the Pallas ``dg_volume_pallas`` /
  ``dg_flux_pallas`` grids alike) already tile over, so stacking into it
  is both the fastest layout and arithmetically identical per element;
* **hoisted scatter target** — the ``(K+1, ...)`` dump-row target is built
  once per resplice (``BlockedDGEngine.rebuild``) and threaded through the
  program as an operand instead of being allocated per evaluation;
* **kernel_impl threading** — the engine's ``kernel_impl`` selects the
  Pallas volume AND flux kernels inside the fused program, exactly as on
  the flat solver path;
* **profile groups** — an optional partition -> group map keeps blocks of
  different (simulated) node classes in separate buckets, so a
  ``SimulatedCluster`` batches each same-profile node group through its own
  launches inside the one compiled program;
* **in-scan pricing / observation** — ``run(..., price=...)`` threads a
  per-partition per-step cost vector through the step loop's carry, so a
  simulated cluster's link+compute seconds accumulate inside the compiled
  scan instead of in host Python.  ``run_observed`` generalizes the same
  carry-riding accumulator into the runtime's measurement channel: one
  fused dispatch per rebalance chunk, ``block_until_ready`` ONCE at the
  chunk boundary, and the chunk's host wall time attributed across
  partitions by the accumulator shares
  (``CalibrationReport.from_chunk``) — so the online
  calibrate→solve→resplice loop runs at full fused speed and observation
  never leaves the compiled program.

``ShardedStepPipeline`` is the multi-device incarnation of the same idea
for the SPMD slab path (``repro.dg.partitioned.PartitionedDG``): the whole
time loop is ONE donated ``shard_map`` program spanning all devices — the
ring ``lax.ppermute`` face exchange of the slab ``StepSchedule`` runs
*inside* the compiled ``fori_loop``/stage-scan, so the halo DMA overlaps
the interior volume kernel across ranks with zero host involvement.  Host
dispatches per ``run()`` are O(1) independent of device count, slab count
and step horizon (asserted by ``tests/test_multidevice.py``).

Correctness invariant (tested in ``tests/test_pipeline.py`` /
``tests/test_multidevice.py``): both fused programs are bitwise identical
to their unfused reference paths and to the flat solver — the per-bucket
gather ``q[own ++ halo ++ pad]`` (or the slab's ``q[own ++ halo_lo ++
halo_hi]`` extension) reproduces the engine's assemble concatenation row
for row, the batched kernels perform the same per-element arithmetic, and
the scatter rows are disjoint across buckets.  The per-block
``StepSchedule`` path survives solely for calibration
(``BlockedDGEngine.calibrate`` / ``measure_block_times``), which needs the
four phases separable to time them.

The blocked pipeline registers itself as a resplice hook: a rebalance
invalidates the stacked tables, and the next call rebuilds them.  Compiled
programs are cached on the *bucket signature* — the tuple of
``(pad, pad_own, B, group)`` per bucket — which ``bucket_counts`` keeps
stable across rebalances, so a resplice that moves work between partitions
without changing the padded shape set reuses the compiled program with new
index tables.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.runtime.schedule import CalibrationReport, DispatchStats

__all__ = ["FusedStepPipeline", "ShardedStepPipeline"]


class FusedStepPipeline:
    """One engine's time loop as a single donated, scan-compiled program."""

    def __init__(self, engine, groups=None, layout: str = "envelope"):
        import jax

        if layout not in ("envelope", "grouped"):
            raise ValueError(
                f"layout must be 'envelope' or 'grouped', got {layout!r}"
            )
        self.engine = engine
        self.executor = engine.executor
        self.solver = engine.solver
        self.kernel_impl = engine.solver.kernel_impl
        # partition -> bucket group.  Under layout="grouped" blocks in
        # different groups are never stacked into one launch (a
        # SimulatedCluster keeps each profile class in its own batched
        # launches); the envelope layout deliberately IGNORES groups — its
        # whole point is one launch over everything, and the in-scan price
        # vector (the only per-group observable) rides the carry
        # independently of launch grouping.
        self.groups = None if groups is None else np.asarray(groups, dtype=np.int64)
        self.layout = layout
        self._jax = jax
        self._tables: Optional[List[dict]] = None
        self._sig: Optional[Tuple] = None
        # sig -> {"volume": n, "surface": n}: launch sites counted while the
        # rhs traced (feeds stats.kernel_launches after every execution)
        self._launch_sites: Dict[Tuple, Dict[str, int]] = {}
        self._rhs_fns: Dict[Tuple, object] = {}
        self._step_fns: Dict[Tuple, object] = {}
        self._run_fns: Dict[Tuple, object] = {}
        self._priced_run_fns: Dict[Tuple, object] = {}
        # introspection for benchmarks and the dispatch-count regression
        # tests: host dispatches vs steps advanced
        self.stats = DispatchStats()
        self.executor._resplice_hooks.append(self.invalidate)

    @property
    def dispatches(self) -> int:
        return self.stats.dispatches

    @property
    def steps_run(self) -> int:
        return self.stats.steps_run

    # -- tables -------------------------------------------------------------

    def invalidate(self) -> None:
        """Resplice hook: drop the stacked tables (compiled programs stay
        cached on the bucket signature and are reused when it recurs)."""
        self._tables = None
        self._sig = None

    def _build_tables(self) -> None:
        from jax.profiler import TraceAnnotation

        self.stats.table_builds += 1
        with TraceAnnotation("dg.tables"):
            if self.layout == "envelope":
                self._build_tables_envelope()
            else:
                self._build_tables_grouped()

    def _build_tables_envelope(self) -> None:
        """Pad EVERY block to the common envelope ``(env, env_own)`` = (max
        ext pad, max own pad) and stack: one table set, one volume launch,
        one surface launch per rhs regardless of the bucket split.

        The masked tail of each block is arithmetically inert by
        construction:

        * padded ext rows gather ``q[0]`` with unit materials — finite
          operands, and no real row references them because their neighbour
          sentinel is -1 and every real row's neighbour id resolves inside
          its own block's first ``pad`` rows (offsets move from ``i * pad``
          to ``i * env`` without touching the intra-block layout);
        * padded own rows gather ``q[0]`` with unit ``rho_o`` (divided by in
          the volume kernel, hence nonzero) and scatter to the dump row
          ``K``, which ``out[:K]`` discards;
        * real rows see byte-for-byte the operands of the per-bucket path —
          the kernels are block-diagonal / per-row over the element axis, so
          the trajectory stays bitwise identical (asserted by the
          envelope-vs-grouped differential tests)."""
        import jax.numpy as jnp

        blks = [b for b in self.engine._blocks if b is not None]
        if not blks:
            self._tables = []
            self._sig = ()
            return
        K = self.solver.mesh.K
        env = max(int(b["nbr_local"].shape[0]) for b in blks)
        env_own = max(int(b["own_pad"].shape[0]) for b in blks)

        def pad_idx(a, n, fill):
            a = np.asarray(a)
            if a.shape[0] < n:
                tail = np.full((n - a.shape[0],) + a.shape[1:], fill, a.dtype)
                a = np.concatenate([a, tail])
            return a

        def pad_mat(key, n):
            cols = []
            for blk in blks:
                a = np.asarray(blk[key])
                if a.shape[0] < n:
                    a = np.concatenate(
                        [a, np.ones((n - a.shape[0],) + a.shape[1:], a.dtype)]
                    )
                cols.append(a)
            return jnp.asarray(np.concatenate(cols))

        ext = np.concatenate(
            [
                pad_idx(
                    np.concatenate(
                        [np.asarray(blk["own"]), np.asarray(blk["halo"])]
                    ),
                    env,
                    0,
                )
                for blk in blks
            ]
        )
        nbr = np.concatenate(
            [
                pad_idx(
                    np.where(
                        np.asarray(blk["nbr_local"]) >= 0,
                        np.asarray(blk["nbr_local"]) + i * env,
                        np.asarray(blk["nbr_local"]),
                    ),
                    env,
                    -1,
                )
                for i, blk in enumerate(blks)
            ]
        )
        own_pad = np.concatenate(
            [pad_idx(np.asarray(blk["own_pad"]), env_own, 0) for blk in blks]
        )
        scat = np.concatenate(
            [pad_idx(np.asarray(blk["scat"]), env_own, K) for blk in blks]
        )
        self._tables = [
            {
                "ext": jnp.asarray(ext),
                "own_pad": jnp.asarray(own_pad),
                "scat": jnp.asarray(scat),
                "nbr": jnp.asarray(nbr),
                "rho": pad_mat("rho", env),
                "lam": pad_mat("lam", env),
                "mu": pad_mat("mu", env),
                "cp": pad_mat("cp", env),
                "cs": pad_mat("cs", env),
                "rho_o": pad_mat("rho_o", env_own),
                "lam_o": pad_mat("lam_o", env_own),
                "mu_o": pad_mat("mu_o", env_own),
            }
        ]
        self._sig = ((env, env_own, len(blks), 0),)

    def _build_tables_grouped(self) -> None:
        """Stack same-bucket blocks: one table set per (pad, pad_own, group)
        bucket.

        Per bucket of B blocks the tables are the engine's per-block index /
        material arrays concatenated along the element axis, with block b's
        local neighbour ids offset by ``b * pad`` (sentinels -1/-2 kept), so
        one flat surface evaluation reproduces B block evaluations row for
        row."""
        import jax.numpy as jnp

        groups: Dict[Tuple[int, int, int], List[dict]] = {}
        for p, b in enumerate(self.engine._blocks):
            if b is None:
                continue
            pad = int(b["nbr_local"].shape[0])
            pad_own = int(b["own_pad"].shape[0])
            gid = 0 if self.groups is None else int(self.groups[p])
            groups.setdefault((pad, pad_own, gid), []).append(b)

        sig = []
        tables = []
        for (pad, pad_own, gid), blks in sorted(groups.items()):
            B = len(blks)
            nbr = np.concatenate(
                [
                    np.where(
                        np.asarray(blk["nbr_local"]) >= 0,
                        np.asarray(blk["nbr_local"]) + i * pad,
                        np.asarray(blk["nbr_local"]),
                    )
                    for i, blk in enumerate(blks)
                ]
            )
            cat = lambda key: jnp.concatenate([blk[key] for blk in blks])
            tables.append(
                {
                    # q[own ++ halo ++ pad]: the engine's assemble concat as
                    # one gather (own is unpadded; halo carries the zero pad)
                    "ext": jnp.concatenate(
                        [jnp.concatenate([blk["own"], blk["halo"]]) for blk in blks]
                    ),
                    "own_pad": cat("own_pad"),
                    "scat": cat("scat"),
                    "nbr": jnp.asarray(nbr),
                    "rho": cat("rho"),
                    "lam": cat("lam"),
                    "mu": cat("mu"),
                    "cp": cat("cp"),
                    "cs": cat("cs"),
                    "rho_o": cat("rho_o"),
                    "lam_o": cat("lam_o"),
                    "mu_o": cat("mu_o"),
                }
            )
            sig.append((pad, pad_own, B, gid))
        self._tables = tables
        self._sig = tuple(sig)

    def _ensure(self) -> None:
        if self._tables is None:
            self._build_tables()

    @property
    def bucket_signature(self) -> Tuple:
        """((pad, pad_own, n_blocks, group), ...) — the compile-cache key."""
        self._ensure()
        return self._sig

    # -- program construction ----------------------------------------------

    def _make_rhs(self, sig):
        """The fused full-field rhs: per bucket one gather + one volume
        launch + one surface launch + one scatter (ONE of each total under
        the envelope layout, where sig is a single bucket).

        The ``counts`` side effects run at TRACE time only — the stage scan
        and step loop trace this body once, so the recorded numbers are the
        per-kernel launch sites baked into the compiled program per rhs
        evaluation (the quantity the dispatch-count regression tests pin)."""
        import jax

        from repro.dg.operators import surface_rhs, volume_rhs_impl

        s = self.solver
        D, metrics, lift = s.D, s.metrics, s.lift
        K = s.mesh.K
        impl = self.kernel_impl
        launch_sites = self._launch_sites

        def rhs(q, tables, base):
            counts = {"volume": 0, "surface": 0}
            out = base
            for (pad, pad_own, B, _gid), T in zip(sig, tables):
                counts["volume"] += 1
                with jax.named_scope("dg.gather"):
                    q_own = q[T["own_pad"]]
                vol = volume_rhs_impl(
                    q_own, D, metrics,
                    T["rho_o"], T["lam_o"], T["mu_o"], kernel_impl=impl,
                )
                counts["surface"] += 1
                with jax.named_scope("dg.gather"):
                    q_ext = q[T["ext"]]
                sur = surface_rhs(
                    q_ext, T["nbr"], lift,
                    T["rho"], T["lam"], T["mu"], T["cp"], T["cs"],
                    kernel_impl=impl,
                )
                with jax.named_scope("dg.scatter"):
                    # rows past each block's own count are dump rows; fold the
                    # leading pad_own surface rows of every block into its volume
                    sur_own = sur.reshape((B, pad) + sur.shape[1:])[:, :pad_own]
                    sur_own = sur_own.reshape((B * pad_own,) + sur.shape[1:])
                    out = out.at[T["scat"]].set(vol + sur_own)
            launch_sites[sig] = counts
            with jax.named_scope("dg.scatter"):
                return out[:K]

        return rhs

    def _record_launches(self) -> None:
        """Feed the trace-time launch-site counts of the active signature
        into the stats ledger (each bucket issues exactly one volume + one
        surface launch, so the sig-derived fallback covers the impossible
        not-yet-traced case)."""
        n = len(self._sig or ())
        self.stats.record_launches(
            self._launch_sites.get(self._sig) or {"volume": n, "surface": n}
        )

    def _rhs_fn(self, sig):
        import jax

        fn = self._rhs_fns.get(sig)
        if fn is None:
            fn = jax.jit(self._make_rhs(sig))
            self._rhs_fns[sig] = fn
        return fn

    def _step_fn(self, sig):
        import jax

        fn = self._step_fns.get(sig)
        if fn is None:
            from repro.dg.rk import lsrk45_step

            rhs = self._make_rhs(sig)

            def step(q, res, dt, tables, base):
                return lsrk45_step(q, res, lambda x: rhs(x, tables, base), dt)

            fn = jax.jit(step, donate_argnums=(0, 1))
            self._step_fns[sig] = fn
        return fn

    def _run_fn(self, sig):
        import jax

        fn = self._run_fns.get(sig)
        if fn is None:
            from repro.dg.rk import lsrk45_step

            rhs = self._make_rhs(sig)

            def run(q, res, dt, n, tables, base):
                # fori_loop with a TRACED trip count: one compiled program
                # per bucket signature serves every horizon (a per-n cache
                # would recompile and retain a program per distinct n)
                self.stats.programs_built += 1  # at trace time only
                def body(_, carry):
                    q, res = carry
                    return lsrk45_step(q, res, lambda x: rhs(x, tables, base), dt)

                q, res = jax.lax.fori_loop(0, n, body, (q, res))
                return q, res

            fn = jax.jit(run, donate_argnums=(0, 1))
            self._run_fns[sig] = fn
        return fn

    def _priced_run_fn(self, sig):
        import jax

        fn = self._priced_run_fns.get(sig)
        if fn is None:
            from repro.dg.rk import lsrk45_step

            rhs = self._make_rhs(sig)

            def run(q, res, acc, dt, n, tables, base, price):
                # same fused step loop, with a per-partition simulated-cost
                # accumulator riding the carry: the (link + compute) price
                # of every step is charged inside the compiled scan.  With
                # today's loop-invariant price the result equals price * n;
                # the in-carry accumulator is the hook the roadmap's
                # on-device per-step observation slots into, and a cluster
                # pipeline only ever compiles THIS family (run(price=...)
                # every call), so no program is compiled twice in practice.
                self.stats.programs_built += 1  # at trace time only
                def body(_, carry):
                    q, res, acc = carry
                    q, res = lsrk45_step(q, res, lambda x: rhs(x, tables, base), dt)
                    return q, res, acc + price

                return jax.lax.fori_loop(0, n, body, (q, res, acc))

            fn = jax.jit(run, donate_argnums=(0, 1, 2))
            self._priced_run_fns[sig] = fn
        return fn

    # -- execution ----------------------------------------------------------

    def rhs(self, q):
        """One fused full-field rhs evaluation (the unfused-equality probe)."""
        self._ensure()
        self.stats.record(1, 0)
        out = self._rhs_fn(self._sig)(q, self._tables, self.engine.scatter_base(q))
        self._record_launches()
        return out

    def step(self, q, res, dt):
        """One fused LSRK4(5) step; (q, res) are DONATED — callers must pass
        buffers they own (``run`` handles the copy)."""
        self._ensure()
        self.stats.record(1, 1)
        out = self._step_fn(self._sig)(
            q, res, dt, self._tables, self.engine.scatter_base(q)
        )
        self._record_launches()
        return out

    def run(self, q, n_steps: int, dt: Optional[float] = None, res=None,
            price=None):
        """Advance ``n_steps`` as ONE host dispatch (step loop with a traced
        trip count, scan over stages, donated carry).  The caller's ``q`` is
        copied once so donation never consumes a buffer the caller still
        holds.

        With ``price`` (a per-partition per-step seconds vector) the
        compiled loop also accumulates the simulated cost of every step and
        the call returns ``(q, accumulated_seconds)`` — how
        ``runtime.cluster.SimulatedCluster`` prices its virtual link inside
        the scan."""
        import jax.numpy as jnp
        from jax.profiler import TraceAnnotation

        # host spans: all host work of one dispatch, from entry to the
        # return of the compiled call (the operands' copies, then the call)
        with TraceAnnotation("dg.dispatch"):
            dt = dt if dt is not None else self.solver.cfl_dt()
            self._ensure()
            with TraceAnnotation("dg.copy_in"):
                q = jnp.copy(q)
                res = jnp.zeros_like(q) if res is None else jnp.copy(res)
                base = self.engine.scatter_base(q)
                if price is not None:
                    price = jnp.asarray(price, dtype=jnp.float64 if q.dtype == jnp.float64
                                        else jnp.float32)
                    acc = jnp.zeros_like(price)
            self.stats.record(1, int(n_steps))
            if price is None:
                fn = self._run_fn(self._sig)
                with TraceAnnotation("dg.enqueue"):
                    q, _ = fn(q, res, dt, int(n_steps), self._tables, base)
                self._record_launches()
                return q
            fn = self._priced_run_fn(self._sig)
            with TraceAnnotation("dg.enqueue"):
                q, _, acc = fn(q, res, acc, dt, int(n_steps),
                               self._tables, base, price)
            self._record_launches()
            return q, acc

    def run_observed(self, q, n_steps: int, dt: Optional[float] = None,
                     price=None, attribute_wall: bool = True,
                     injector=None, step: int = 0):
        """Advance ``n_steps`` as ONE fused dispatch AND observe it: the
        in-scan measurement channel of the calibrate→solve→resplice loop.

        The per-partition cost accumulator rides the scan carry (the
        ``_priced_run_fn`` family), so the relative shares of work never
        leave the compiled program; the host synchronizes exactly once per
        chunk (``block_until_ready``) and attributes the chunk's wall
        seconds across partitions by those shares
        (``CalibrationReport.from_chunk``).  ``price`` defaults to the
        executor's current element counts — the work proxy of a fused
        single-arena program, where each partition's slice of the launch
        scales with its element count.  With ``attribute_wall=False`` the
        report carries the accumulated price itself (``acc / n_steps``, no
        wall measurement) — the deterministic mode ``SimulatedCluster``
        uses for its virtual link+compute pricing.

        Returns ``(q, CalibrationReport)``; straggler factors are NOT in
        the report — ``NestedPartitionExecutor.observe`` applies them, the
        single injection point.

        ``injector`` (a ``runtime.fault_tolerance.FailureInjector``) is
        probed at ``step`` BEFORE the dispatch — the chaos hook: a raised
        failure leaves ``q``, the ledger and the executor schedule
        untouched, so a supervised retry replays the chunk exactly."""
        import jax
        from jax.profiler import TraceAnnotation

        if injector is not None:
            injector.maybe_fail(step)
        if price is None:
            price = np.maximum(
                self.executor.counts.astype(np.float64), 0.0
            )
        t0 = time.perf_counter()
        q, acc = self.run(q, n_steps, dt=dt, price=price)
        with TraceAnnotation("dg.sync"):
            jax.block_until_ready(q)
        wall = time.perf_counter() - t0
        self.stats.record_chunk()
        acc = np.asarray(acc, dtype=np.float64)
        if attribute_wall:
            report = CalibrationReport.from_chunk(wall, acc, n_steps)
        else:
            report = CalibrationReport.from_totals(acc / max(1, int(n_steps)))
        return q, report


class ShardedStepPipeline:
    """The SPMD slab time loop as ONE donated shard_map program spanning all
    devices (see module docstring).

    Bound to a ``repro.dg.partitioned.PartitionedDG``: the slab
    ``StepSchedule`` — pack edge layers, ring ``ppermute``, overlapped
    volume interior, extended surface fold — is traced INTO the compiled
    ``fori_loop`` over steps (traced trip count) and ``lax.scan`` over the
    five LSRK stages, with the ``(q, res)`` carry donated.  One compiled
    program serves every horizon and every ``dt``; host dispatches per run
    are O(1) regardless of device count."""

    def __init__(self, pdg):
        import jax

        self.pdg = pdg
        self.solver = pdg.solver
        self._jax = jax
        self._rhs_c = None
        self._step_c = None
        self._run_c = None
        self._priced_run_c = None
        self.stats = DispatchStats()

    @property
    def dispatches(self) -> int:
        return self.stats.dispatches

    @property
    def steps_run(self) -> int:
        return self.stats.steps_run

    # -- program construction ----------------------------------------------

    def _local_rhs(self):
        p = self.pdg

        def rhs(q, nbr, rho, lam, mu, cp, cs):
            return p._rhs_local(q, nbr, rho, lam, mu, cp, cs)

        return rhs

    def _shard(self, f, n_carry_out: int):
        import jax

        p = self.pdg
        qs = p.spec_q
        out = qs if n_carry_out == 1 else (qs,) * n_carry_out
        return jax.shard_map(
            f,
            mesh=p.mesh_axes,
            in_specs=(qs,) * n_carry_out
            + (self._scalar_spec(),) * (2 if n_carry_out > 1 else 0)
            + p._operand_specs(),
            out_specs=out,
            check_vma=False,
        )

    @staticmethod
    def _scalar_spec():
        from jax.sharding import PartitionSpec

        return PartitionSpec()

    def _rhs_fn(self):
        if self._rhs_c is None:
            import jax

            self._rhs_c = jax.jit(self._shard(self._local_rhs(), 1))
        return self._rhs_c

    def _step_fn(self):
        if self._step_c is None:
            import jax

            from repro.dg.rk import lsrk45_step

            local_rhs = self._local_rhs()

            def local_step(q, res, dt, n, nbr, rho, lam, mu, cp, cs):
                del n
                return lsrk45_step(
                    q, res, lambda x: local_rhs(x, nbr, rho, lam, mu, cp, cs), dt
                )

            self._step_c = jax.jit(self._shard(local_step, 2), donate_argnums=(0, 1))
        return self._step_c

    def _run_fn(self):
        if self._run_c is None:
            import jax

            from repro.dg.rk import lsrk45_step

            local_rhs = self._local_rhs()

            def local_run(q, res, dt, n, nbr, rho, lam, mu, cp, cs):
                # fori_loop with a TRACED trip count; the ring ppermute of
                # the schedule's exchange phase is traced into the loop body,
                # so the whole multi-device run is one resident program
                def body(_, carry):
                    q, res = carry
                    return lsrk45_step(
                        q, res, lambda x: local_rhs(x, nbr, rho, lam, mu, cp, cs), dt
                    )

                return jax.lax.fori_loop(0, n, body, (q, res))

            self._run_c = jax.jit(self._shard(local_run, 2), donate_argnums=(0, 1))
        return self._run_c

    def _priced_run_fn(self):
        if self._priced_run_c is None:
            import jax
            import jax.numpy as jnp
            from jax.sharding import PartitionSpec

            from repro.dg.rk import lsrk45_step

            p = self.pdg
            local_rhs = self._local_rhs()
            axis, n_shards = p.axis, p.P

            def local_run(q, res, acc, dt, n, price, nbr, rho, lam, mu, cp, cs):
                # the blocked pipeline's carry-riding accumulator, per
                # shard: each rank charges its own per-step price inside
                # the compiled loop (the ring ppermute of the exchange
                # phase is traced into the same body)
                def body(_, carry):
                    q, res, acc = carry
                    q, res = lsrk45_step(
                        q, res,
                        lambda x: local_rhs(x, nbr, rho, lam, mu, cp, cs), dt,
                    )
                    return q, res, acc + price

                q, res, acc = jax.lax.fori_loop(0, n, body, (q, res, acc))
                # collect every shard's scalar accumulator into ONE
                # replicated (P,) vector inside the compiled program —
                # one-hot placement + psum over the mesh axis — so the
                # host reads all per-shard totals from a single output
                full = (
                    jnp.zeros((n_shards,), acc.dtype)
                    .at[jax.lax.axis_index(axis)]
                    .set(acc[0])
                )
                return q, res, jax.lax.psum(full, axis)

            qs = p.spec_q
            scalar = PartitionSpec()
            vec = PartitionSpec(p.axis)
            f = jax.shard_map(
                local_run,
                mesh=p.mesh_axes,
                in_specs=(qs, qs, vec, scalar, scalar, vec) + p._operand_specs(),
                out_specs=(qs, qs, scalar),
                check_vma=False,
            )
            self._priced_run_c = jax.jit(f, donate_argnums=(0, 1, 2))
        return self._priced_run_c

    # -- execution ----------------------------------------------------------

    def _sharded_copy(self, x):
        """A fresh buffer with the pipeline's q-sharding — what the donated
        carry consumes, so the caller's array survives every call.  An
        array already so sharded is copied in place on its devices, a
        device array on another sharding is resharded, and a host array
        goes to each device as its own slab, never staged whole on one
        device."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding

        p = self.pdg
        sharding = NamedSharding(p.mesh_axes, p.spec_q)
        if not isinstance(x, jax.Array):
            return p.place(x, p.spec_q)
        if x.sharding.is_equivalent_to(sharding, x.ndim):
            return jnp.copy(x)
        return jax.device_put(x, sharding)

    def rhs(self, q):
        """One fused sharded rhs evaluation (the differential-test probe)."""
        self.stats.record(1, 0)
        return self._rhs_fn()(q, *self.pdg._operands())

    def step(self, q, res, dt):
        """One fused sharded LSRK4(5) step; (q, res) are DONATED."""
        import jax.numpy as jnp

        self.stats.record(1, 1)
        dt = jnp.asarray(dt, q.dtype)
        n = jnp.asarray(1, jnp.int32)
        return self._step_fn()(q, res, dt, n, *self.pdg._operands())

    def run(self, q, n_steps: int, dt: Optional[float] = None, res=None):
        """Advance ``n_steps`` as ONE host dispatch across all devices."""
        import jax.numpy as jnp
        from jax.profiler import TraceAnnotation

        with TraceAnnotation("dg.dispatch"):
            dt = dt if dt is not None else self.solver.cfl_dt()
            with TraceAnnotation("dg.copy_in"):
                q = self._sharded_copy(q)
                # zeros_like keeps q's sharding: a fresh buffer on every device
                res = jnp.zeros_like(q) if res is None else self._sharded_copy(res)
                dt_j = jnp.asarray(dt, q.dtype)
                n_j = jnp.asarray(int(n_steps), jnp.int32)
            fn = self._run_fn()
            self.stats.record(1, int(n_steps))
            with TraceAnnotation("dg.enqueue"):
                q, _ = fn(q, res, dt_j, n_j, *self.pdg._operands())
            return q

    def run_observed(self, q, n_steps: int, dt: Optional[float] = None,
                     price=None, attribute_wall: bool = True):
        """Advance ``n_steps`` as ONE fused multi-device dispatch AND
        observe it (the sharded twin of
        ``FusedStepPipeline.run_observed``): per-shard cost accumulators
        ride the donated carry and are reduced to one replicated vector
        with ``psum`` INSIDE the compiled program, then the chunk's host
        wall time (one ``block_until_ready``) is attributed across shards
        by those shares.  ``price`` defaults to the (equal) per-slab
        element counts; returns ``(q, CalibrationReport)``."""
        import jax
        import jax.numpy as jnp
        from jax.profiler import TraceAnnotation
        from jax.sharding import PartitionSpec

        p = self.pdg
        with TraceAnnotation("dg.dispatch"):
            dt = dt if dt is not None else self.solver.cfl_dt()
            if price is None:
                price = np.full(p.P, float(p.K_loc))
            dtype = jnp.float64 if q.dtype == jnp.float64 else jnp.float32
            with TraceAnnotation("dg.copy_in"):
                price = p.place(np.asarray(price, dtype), PartitionSpec(p.axis))
                acc = p.place(np.zeros((p.P,), dtype), PartitionSpec(p.axis))
                q = self._sharded_copy(q)
                res = jnp.zeros_like(q)
                dt_j = jnp.asarray(dt, q.dtype)
                n_j = jnp.asarray(int(n_steps), jnp.int32)
            fn = self._priced_run_fn()
            self.stats.record(1, int(n_steps))
            t0 = time.perf_counter()
            with TraceAnnotation("dg.enqueue"):
                q, _, acc = fn(q, res, acc, dt_j, n_j, price, *p._operands())
        with TraceAnnotation("dg.sync"):
            jax.block_until_ready(q)
        wall = time.perf_counter() - t0
        self.stats.record_chunk()
        acc = np.asarray(acc, dtype=np.float64)
        if attribute_wall:
            report = CalibrationReport.from_chunk(wall, acc, n_steps)
        else:
            report = CalibrationReport.from_totals(acc / max(1, int(n_steps)))
        return q, report
