"""Simulated heterogeneous cluster — the paper's *outer* partition level,
closed end to end on one machine.

The repo's executor (`runtime.executor`) closes the paper's calibration loop
for a single node's partitions.  This module lifts the same loop to the
cluster: a ``SimulatedCluster`` owns one shared ``NestedPartitionExecutor``
(the control plane: Morton splice + online re-solve) and drives one
``BlockedDGEngine`` per virtual node (the data plane: each node executes its
own Morton-contiguous block with halo gathers, bitwise-identical to the flat
solver).  Heterogeneity and the network are *simulated* on top of real
kernel timings:

* a ``NodeProfile`` per node scales measured seconds by ``1/speed`` (a node
  twice as fast observes half the time) and optionally carries calibrated
  ``t_host`` / ``t_accel`` / PCI models for the intra-node level-2 solve;
* inter-node halo exchange is priced by an alpha–beta ``LinkClass`` model on
  the partition's *exact* cross-node face cuts (``ClusterPartition``):
  ``latency * peers + bytes / bandwidth`` per node per step.

The step driver is fused by default (``run(fused=True)``): every node's
block executes inside ONE donated scan-compiled ``FusedStepPipeline``
program per rebalance chunk — same-profile node groups are batched into
their own launches (``profile_groups``), and the simulated per-node step
price (compute/speed plus the link model) is accumulated *inside* the
compiled scan (``FusedStepPipeline.run(price=...)``), so observation no
longer forces one host dispatch per step.  The eager per-step path
(``fused=False``) survives for calibration-style per-step measurement.

``resolve`` re-solves **both** levels from a per-node ``CalibrationReport``:
level 1 feeds the overlap-aware fleet report into the executor's
waterfilling solve (new node counts -> resplice), level 2 re-runs the
asymmetric two-way solve inside each node (new accelerator block sizes ->
``set_accel_counts``).  The straggler hook is the executor's own
(``inject_straggler``), so a slow node is rebalanced by exactly the paper's
equalizer.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Sequence

import numpy as np

from repro.core.cost_model import inter_node_transfer_fn, stampede_node_models
from repro.core.load_balance import NodeModel, solve_hierarchical
from repro.core.partition import ClusterPartition
from repro.core.topology import STAMPEDE_IB, LinkClass
from repro.runtime.executor import BlockedDGEngine, NestedPartitionExecutor
from repro.runtime.schedule import CalibrationReport

__all__ = [
    "NodeProfile",
    "stampede_profile",
    "SimulatedCluster",
    "format_cluster_plan",
]


@dataclasses.dataclass(frozen=True)
class NodeProfile:
    """One virtual node: a relative speed plus optional calibrated models.

    ``speed`` is a throughput multiplier applied to *measured* seconds (the
    simulation knob: speed 2.0 halves observed times, speed 0.5 is a slow
    node).  ``t_host`` / ``t_accel`` / ``transfer`` are the paper's
    T_CPU / T_MIC / PCI models for the intra-node solve; a profile without
    them is a homogeneous node (no level-2 accelerator split).
    """

    name: str = "node"
    speed: float = 1.0
    t_host: Optional[Callable[[float], float]] = None
    t_accel: Optional[Callable[[float], float]] = None
    transfer: Optional[Callable[[float], float]] = None

    def __post_init__(self):
        if self.speed <= 0:
            raise ValueError(f"node speed must be positive, got {self.speed}")

    @property
    def has_models(self) -> bool:
        return self.t_host is not None


def stampede_profile(order: int = 7, speed: float = 1.0, name: str = "stampede") -> NodeProfile:
    """The paper's node (SNB socket + MIC over PCI) as a cluster profile."""
    t_cpu, t_mic, xfer = stampede_node_models(order)
    return NodeProfile(name=name, speed=speed, t_host=t_cpu, t_accel=t_mic, transfer=xfer)


class SimulatedCluster:
    """N virtual heterogeneous nodes over one DG solver (see module docstring).

    The field execution is exact: node ``i`` evaluates block ``i`` of the
    shared nested partition through its own ``BlockedDGEngine``, and the
    assembled rhs is bitwise-identical to the flat solver's.  Only *time* is
    simulated (speed scaling + link model), which is what lets CI exercise
    cluster-level rebalancing on a single container.
    """

    def __init__(
        self,
        solver,
        profiles: Sequence[NodeProfile],
        *,
        link: LinkClass = STAMPEDE_IB,
        bucket: int = 8,
        accel_fraction: float = 0.0,
        rebalance_every: int = 0,
        plan_cache_dir: Optional[str] = None,
        sim_unit_cost: float = 50e-6,
        injector=None,
    ):
        if len(profiles) == 0:
            raise ValueError("need at least one node profile")
        self.solver = solver
        self.profiles = tuple(profiles)
        self.link = link
        # chaos hook: a runtime.fault_tolerance.FailureInjector probed once
        # per node at each fused chunk's dispatch boundary (BEFORE the
        # device program runs, so a raised failure leaves q and the
        # executor's schedule untouched and a supervised retry is exact)
        self.injector = injector
        # seconds per element (at speed 1) for the field-free deterministic
        # simulation — on the same scale as the link model, so the wire
        # genuinely enters the simulated balance
        self.sim_unit_cost = float(sim_unit_cost)
        K = solver.mesh.K
        speeds = np.array([p.speed for p in self.profiles], dtype=np.float64)
        # level-1 seed: splice the curve proportionally to nominal speeds
        self.executor = NestedPartitionExecutor(
            K,
            len(self.profiles),
            grid_dims=tuple(solver.mesh.grid),
            bucket=bucket,
            accel_fraction=accel_fraction,
            rebalance_every=rebalance_every,
            initial_weights=speeds,
            plan_cache_dir=plan_cache_dir,
        )
        # one engine per node, all bound to the shared executor/partition;
        # node i only executes block i, so its engine only builds block i's
        # tables (a resplice costs O(N) total, not O(N^2))
        self.engines: List[BlockedDGEngine] = [
            BlockedDGEngine(solver, self.executor, only_blocks=[i])
            for i in range(len(self.profiles))
        ]
        # the fused data plane (built lazily by fused_pipeline()): one full
        # engine whose FusedStepPipeline batches each same-profile node
        # group through its own launches inside ONE compiled program
        self._fused_engine: Optional[BlockedDGEngine] = None
        self.last_sim_times: Optional[np.ndarray] = None

    # -- introspection -------------------------------------------------------

    @property
    def n_nodes(self) -> int:
        return len(self.profiles)

    @property
    def counts(self) -> np.ndarray:
        return self.executor.counts

    def cluster_partition(self) -> ClusterPartition:
        """The current two-level partition with cluster-level metadata."""
        counts = np.maximum(self.executor.counts.astype(np.float64), 0.0)
        total = counts.sum()
        weights = counts / total if total > 0 else np.full(self.n_nodes, 1.0 / self.n_nodes)
        return ClusterPartition(node_weights=weights, nested=self.executor.partition)

    # -- the simulated network ----------------------------------------------

    def comm_times(self) -> np.ndarray:
        """Per-node inter-node halo exchange seconds under the alpha-beta
        link model, priced on the partition's exact cross-node face cuts."""
        part = self.cluster_partition()
        dtype_bytes = int(np.dtype(self.solver.dtype).itemsize)
        nbytes = part.halo_bytes(self.solver.order, n_fields=9, dtype_bytes=dtype_bytes)
        peers = part.halo_peers()
        return np.array(
            [self.link.time(float(nbytes[i]), n_messages=int(peers[i]))
             for i in range(self.n_nodes)]
        )

    def inter_transfer_fn(self) -> Callable[[float], float]:
        """Plan-time surface model of the same exchange: a Morton-compact
        chunk of k elements exposes ~6*k^(2/3) faces (paper section 5.5)."""
        return inter_node_transfer_fn(
            self.solver.order, link=self.link,
            dtype_bytes=int(np.dtype(self.solver.dtype).itemsize),
        )

    # -- execution (exact) ---------------------------------------------------

    def rhs(self, q):
        """Global rhs assembled from per-node engine evaluations — the same
        arithmetic as one BlockedDGEngine, so it matches the flat solver
        bitwise."""
        K = self.solver.mesh.K
        # the hoisted (K+1)-row scatter target (engines share one solver)
        out = self.engines[0].scatter_base(q)
        for i, eng in enumerate(self.engines):
            b = eng._blocks[i]
            if b is None:
                continue
            out = out.at[b["scat"]].set(eng.block_rhs(q, b))
        return out[:K]

    def profile_groups(self) -> np.ndarray:
        """Node -> bucket-group ids: nodes sharing a profile class
        ``(name, speed)`` share a group, so the fused pipeline batches each
        same-profile group through its own launches."""
        keys: dict = {}
        out = np.zeros(self.n_nodes, dtype=np.int64)
        for i, p in enumerate(self.profiles):
            out[i] = keys.setdefault((p.name, p.speed), len(keys))
        return out

    def fused_pipeline(self, layout: str = "envelope"):
        """The cluster's fused step driver: ONE donated scan-compiled
        program covering every node's block, rebuilt across resplices via
        the usual hooks.  The default envelope layout collapses ALL profile
        groups into one volume + one surface launch per rhs (the per-node
        simulated price rides the scan carry, independent of launch
        grouping); ``layout="grouped"`` keeps one launch pair per profile
        class (the differential reference)."""
        if self._fused_engine is None:
            self._fused_engine = BlockedDGEngine(self.solver, self.executor)
        return self._fused_engine.pipeline(groups=self.profile_groups(),
                                           layout=layout)

    def resplice(self, plan) -> None:
        """Apply a solved plan: every node engine rebuilds its own block
        through the executor's resplice hooks."""
        self.executor.apply(plan)

    def run(self, q, n_steps: int, dt: Optional[float] = None, observe: bool = False,
            fused: bool = True):
        """LSRK4(5) on the cluster rhs.

        ``fused`` (default) drives the grouped ``FusedStepPipeline``: the
        whole horizon is one donated device program per rebalance chunk,
        with the simulated per-node step price (compute/speed + the
        alpha-beta link on the exact face cuts) accumulated INSIDE the
        compiled scan via the in-scan observation channel
        (``run_observed(..., attribute_wall=False)`` — the report carries
        the virtual price itself, keeping the simulation deterministic);
        with ``observe`` each chunk's report feeds
        ``executor.observe_chunk`` and it rebalances on its schedule.
        ``fused=False`` is the eager per-step reference path (kept for
        calibration-style per-step observation)."""
        from repro.dg.rk import lsrk45_step

        import jax.numpy as jnp

        dt = dt or self.solver.cfl_dt()
        if fused:
            done = 0
            while done < n_steps:
                chunk = n_steps - done
                if observe:
                    chunk = self.executor.next_chunk(chunk)
                if self.injector is not None:
                    # probe every node's dispatch at the global step this
                    # chunk starts from (the executor's step counter —
                    # monotone across supervised per-chunk calls)
                    base = self.executor._step
                    for node in range(self.n_nodes):
                        self.injector.maybe_fail(base, node=node)
                pipe = self.fused_pipeline()  # after a resplice: new tables
                q, report = pipe.run_observed(
                    q, chunk, dt=dt,
                    price=self.step_times(),  # deterministic: counts + link
                    attribute_wall=False,
                )
                self.last_sim_times = np.asarray(report.step_s)
                if observe:
                    self.executor.observe_chunk(report, chunk)
                done += chunk
            return q
        res = jnp.zeros_like(q)
        for _ in range(n_steps):
            if observe:
                self.executor.observe(self.step_times(q))
                self.executor.advance()
            q, res = lsrk45_step(q, res, self.rhs, dt)
        return q

    # -- measurement (simulated time on real kernels) ------------------------

    def step_times(self, q=None, reps: int = 1) -> np.ndarray:
        """Per-node simulated step seconds: measured block time (or, without
        a field, a deterministic counts/speed model) scaled by ``1/speed``,
        plus the modeled inter-node exchange.  Straggler factors are NOT
        applied here — ``executor.observe`` applies them, the single
        injection point."""
        comm = self.comm_times()
        speeds = np.array([p.speed for p in self.profiles])
        if q is None:
            # deterministic simulation: sim_unit_cost seconds per element —
            # real-seconds scale, so the link term is commensurate and a
            # comm-heavy node genuinely reads as slower
            compute = self.executor.counts.astype(np.float64) * self.sim_unit_cost / speeds
        else:
            measured = np.zeros(self.n_nodes)
            for i, eng in enumerate(self.engines):
                b = eng._blocks[i]
                if b is None:
                    continue
                measured[i], _ = eng._time(eng.block_rhs, q, b, reps=reps)
            compute = measured / speeds
        return compute + comm

    def calibrate(self, q, reps: int = 1) -> CalibrationReport:
        """Per-node phase-resolved calibration: each node's engine times its
        OWN block, compute phases are scaled by the node's speed, and the
        transfer phase gains the modeled inter-node wire time on top of the
        measured local pack/gather.  Observes the executor once."""
        P = self.n_nodes
        boundary = np.zeros(P)
        interior = np.zeros(P)
        transfer = np.zeros(P)
        correction = np.zeros(P)
        comm = self.comm_times()
        for i, (prof, eng) in enumerate(zip(self.profiles, self.engines)):
            rep = eng.calibrate(q, reps=reps, blocks=[i], observe=False)
            boundary[i] = rep.boundary_s[i] / prof.speed
            interior[i] = rep.interior_s[i] / prof.speed
            correction[i] = rep.correction_s[i] / prof.speed
            transfer[i] = rep.transfer_s[i] / prof.speed + comm[i]
        report = CalibrationReport(boundary_s=boundary, interior_s=interior,
                                   transfer_s=transfer, correction_s=correction)
        self.executor.observe(report.step_s)
        return report

    # -- the two-level re-solve ----------------------------------------------

    @staticmethod
    def _node_model(profile: NodeProfile, inter=None) -> NodeModel:
        """The single speed-scaling convention profile -> NodeModel (both the
        offline hierarchical solve and the online level-2 re-solve use it)."""
        if not profile.has_models:
            raise RuntimeError(
                f"profile {profile.name!r} has no t_host model; "
                "model-based solves need calibrated profiles"
            )
        s = profile.speed
        return NodeModel(
            t_host=lambda k, f=profile.t_host, s=s: f(k) / s,
            t_accel=None if profile.t_accel is None
            else (lambda k, f=profile.t_accel, s=s: f(k) / s),
            transfer=profile.transfer,
            inter_transfer=inter,
        )

    def node_models(self) -> List[NodeModel]:
        """Per-node ``NodeModel``s from the profiles (speed-scaled), with the
        cluster link's surface model as each node's inter-node transfer."""
        inter = self.inter_transfer_fn()
        return [self._node_model(p, inter=inter) for p in self.profiles]

    def solve_hierarchical(self, overlap: bool = False):
        """The offline two-level solve on the profiles' calibrated models
        (level 1 waterfilling over best-achievable node times, level 2
        two-way splits) — the plan the online loop should converge to."""
        return solve_hierarchical(self.node_models(), self.solver.mesh.K, overlap=overlap)

    def resolve(self, report: Optional[CalibrationReport] = None, overlap: bool = True):
        """Re-solve both levels and resplice.

        Level 1: the fleet ``CalibrationReport`` (pass one from
        ``calibrate``, or the executor's last observation is used) feeds the
        overlap-aware waterfilling solve — new node counts.  Level 2: each
        node with intra-node models re-runs the asymmetric two-way solve at
        its new count — new accelerator block sizes via
        ``set_accel_counts``.  Returns the applied level-1 plan.
        """
        if report is not None:
            plan = self.executor.plan_from_report(report, overlap=overlap)
        else:
            plan = self.executor.rebalance()
        if any(p.has_models and p.t_accel is not None for p in self.profiles):
            accel = []
            for i, p in enumerate(self.profiles):
                k = int(self.executor.counts[i])
                if p.has_models and p.t_accel is not None:
                    res = self._node_model(p).solve(k, overlap=overlap)
                    accel.append(int(res.counts[1]))
                else:
                    accel.append(0)
            self.executor.set_accel_counts(accel)
        return plan

    # -- hooks ----------------------------------------------------------------

    def inject_straggler(self, node: int, factor: float) -> None:
        """The existing straggler hook, at cluster level: multiply node's
        observed times by ``factor``."""
        self.executor.inject_straggler(node, factor)

    def clear_stragglers(self) -> None:
        self.executor.clear_stragglers()

    # -- elastic membership ---------------------------------------------------

    def _rebuild_membership(self, profiles: Sequence[NodeProfile],
                            weights: np.ndarray) -> None:
        """Swap the control plane for a new fleet: a fresh executor seeded
        from ``weights`` (spliced through the shared plan cache, so a
        membership the cache has seen resumes its calibrated split), one
        ``only_blocks`` engine per node, and a lazily rebuilt fused data
        plane.  The solver — and with it the jitted kernel bundle and every
        compiled program keyed on a recurring bucket signature — is shared,
        so joins/leaves recompile nothing at the kernel level."""
        old = self.executor
        cache_root = old.plan_cache.root if old.plan_cache is not None else None
        self.profiles = tuple(profiles)
        self.executor = NestedPartitionExecutor(
            self.solver.mesh.K,
            len(self.profiles),
            grid_dims=tuple(self.solver.mesh.grid),
            bucket=old.bucket,
            accel_fraction=old.accel_fraction,
            rebalance_every=old.rebalance_every,
            initial_weights=np.asarray(weights, dtype=np.float64),
            plan_cache_dir=cache_root,
        )
        self.engines = [
            BlockedDGEngine(self.solver, self.executor, only_blocks=[i])
            for i in range(len(self.profiles))
        ]
        self._fused_engine = None
        self.last_sim_times = None

    def add_node(self, profile: NodeProfile, weight: Optional[float] = None) -> int:
        """A node joins between chunks: re-splice the mesh over N+1 nodes.
        The joiner's seed weight defaults to its nominal speed on the same
        scale as the survivors' current counts (so the splice hands it a
        proportional share immediately; the observe loop refines from
        there).  Returns the new node's index."""
        counts = self.executor.counts.astype(np.float64)
        speeds = np.array([p.speed for p in self.profiles], dtype=np.float64)
        survivors = np.maximum(counts, 1e-9) if counts.sum() else speeds
        per_speed = survivors.sum() / max(speeds.sum(), 1e-30)
        w_new = float(weight) if weight is not None else profile.speed * per_speed
        self._rebuild_membership(
            self.profiles + (profile,), np.concatenate([survivors, [w_new]])
        )
        return self.n_nodes - 1

    def remove_node(self, index: int) -> None:
        """A node leaves (preemption, decommission) between chunks: its
        elements are re-spliced across the survivors, who keep their
        relative calibrated shares."""
        index = int(index)
        if not (0 <= index < self.n_nodes):
            raise ValueError(f"node {index} out of range")
        if self.n_nodes == 1:
            raise RuntimeError("cannot remove the last node")
        counts = self.executor.counts.astype(np.float64)
        speeds = np.array([p.speed for p in self.profiles], dtype=np.float64)
        survivors = np.maximum(counts, 1e-9) if counts.sum() else speeds
        keep = [i for i in range(self.n_nodes) if i != index]
        self._rebuild_membership(
            tuple(self.profiles[i] for i in keep), survivors[keep]
        )

    def run_until_balanced(self, rtol: float = 0.10, max_rounds: int = 8) -> int:
        """Deterministic convergence driver: observe simulated step times
        (speed model + link) and rebalance until within ``rtol`` of the
        common-finish-time optimum."""
        return self.executor.run_until_balanced(
            measure_fn=lambda: self.step_times(), rtol=rtol, max_rounds=max_rounds
        )

    def summary(self) -> str:
        part = self.cluster_partition()
        lines = [
            f"cluster: {self.n_nodes} nodes, K={self.solver.mesh.K}, "
            f"link={self.link.name} ({self.link.bandwidth / 1e9:.1f} GB/s, "
            f"{self.link.latency * 1e6:.1f} us)"
        ]
        comm = self.comm_times()
        for i, p in enumerate(self.profiles):
            npart = part.nodes[i]
            lines.append(
                f"  {p.name}[{i}]: speed={p.speed:g} elements={npart.n_elements} "
                f"boundary={len(npart.boundary)} accel={len(npart.accel)} "
                f"halo={0 if npart.halo is None else len(npart.halo)} "
                f"comm={comm[i] * 1e6:.1f}us"
            )
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# The two-level plan, printable (launch.dryrun --cluster-plan)
# ---------------------------------------------------------------------------


def format_cluster_plan(
    grid: tuple,
    n_nodes: int,
    order: int = 7,
    speeds: Optional[Sequence[float]] = None,
    link: LinkClass = STAMPEDE_IB,
    overlap: bool = True,
) -> str:
    """Solve and render the two-level plan for ``n_nodes`` Stampede-profile
    nodes over a ``grid`` mesh: the level-1 Morton splice (counts, cut
    faces, link time) and each node's level-2 boundary/interior/accelerator
    split with predicted times.  ``speeds`` introduces heterogeneity."""
    from repro.core.partition import build_cluster_partition

    K = int(np.prod(grid))
    speeds = np.ones(n_nodes) if speeds is None else np.asarray(speeds, dtype=np.float64)
    if len(speeds) != n_nodes:
        raise ValueError(f"need {n_nodes} speeds, got {len(speeds)}")
    t_cpu, t_mic, xfer = stampede_node_models(order)
    inter = inter_node_transfer_fn(order, link=link)
    models = [
        NodeModel(
            t_host=lambda k, s=s: t_cpu(k) / s,
            t_accel=lambda k, s=s: t_mic(k) / s,
            transfer=xfer,
            inter_transfer=inter if n_nodes > 1 else None,
        )
        for s in speeds
    ]
    split = solve_hierarchical(models, K, overlap=overlap)
    part = build_cluster_partition(
        grid,
        node_weights=np.maximum(split.node_counts, 0)
        if sum(split.node_counts) else None,
        n_nodes=n_nodes,
        accel_counts=split.accel_counts,
    )
    part.validate()
    cuts = part.face_cuts()
    lines = [
        f"two-level plan: grid={grid} K={K} nodes={n_nodes} order={order} "
        f"link={link.name} overlap={'on' if overlap else 'off'}",
        f"level 0 (Morton inter-node splice): counts={list(split.node_counts)} "
        f"cut_faces={int(cuts.sum())} makespan={split.makespan * 1e3:.2f}ms "
        f"imbalance={split.imbalance:.3f}",
    ]
    for i, npart in enumerate(part.nodes):
        res = split.node_splits[i]
        lines.append(
            f"  node{i} (speed {speeds[i]:g}): {npart.n_elements} elements -> "
            f"boundary={len(npart.boundary)} host_interior={len(npart.host_interior)} "
            f"accel={len(npart.accel)} (K_acc/K_host={res.ratio:.2f}) "
            f"halo={0 if npart.halo is None else len(npart.halo)} "
            f"t={split.times[i] * 1e3:.2f}ms"
        )
    return "\n".join(lines)
