"""Online auto-rebalancing nested-partition executor — paper section 5.6
closed at *runtime*.

The paper's payoff is not a static split but a calibrated one: it solves

    T_acc(K_acc) = T_host(K - K_acc) + Transfer(K_acc)

from *measured* kernel times so that neither side idles.  This module wires
the repo's existing pieces (``core.load_balance``, ``core.partition``,
``runtime.schedule``) into the measure -> re-solve -> re-splice loop that
makes a heterogeneous run track hardware reality:

1. **calibrate** — a short phase that times the four ``StepSchedule``
   phases (boundary face flux / interior volume / halo transfer / halo
   fold) per partition.  ``BlockedDGEngine.calibrate`` resolves all four on
   the DG workload; injected ``time_models`` give whole-step totals for
   simulated fleets (``CalibrationReport.from_totals``);
2. **solve** — measured step times feed ``rebalance_from_measurements`` /
   ``solve_multiway``; a component-resolved report additionally enables the
   overlap-aware solve ``plan_from_report``, whose time model
   ``t_p(k) = boundary + max(interior, transfer) + correction`` credits a
   partition for transfer hidden under interior compute (paper Fig 5.1);
3. **resplice** — the ``NestedPartition`` index arrays are rebuilt and the
   device assignment re-spliced *without recompiling the interior kernels*:
   per-partition chunk sizes are padded to ``bucket`` multiples, so the jit
   cache is keyed on a small set of padded shapes that survive rebalances;
4. **drive** — a step-driver API (``drive`` / ``observe`` /
   ``maybe_rebalance``) adopted by ``repro.dg.partitioned``,
   ``repro.launch.train`` and ``repro.launch.serve``.

``BlockedDGEngine`` executes each partition's block as a thin instantiation
of the shared ``StepSchedule`` (the same object ``dg.partitioned`` builds
its SPMD rhs from): the exchange phase gathers the halo, the interior phase
runs the volume kernel on the block's own elements, and the correction
phase computes the face flux and folds it in.  Solved splits are cached
(hash of mesh/topology/weights -> counts) and persisted through
``repro.checkpoint``, so a restarted job starts from the last calibrated
split instead of the naive one.  A straggler-injection hook
(``inject_straggler``) multiplies observed times for one partition, which is
how tests exercise convergence: a 2x straggler must be rebalanced to within
10% of the common-finish-time optimum in a few rounds.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time
from typing import Callable, List, Optional, Sequence

import numpy as np

from repro.core.load_balance import (
    rebalance_from_measurements,
    solve_multiway,
)
from repro.core.partition import NestedPartition, build_nested_partition, splice
from repro.runtime.schedule import CalibrationReport, StepSchedule

__all__ = [
    "Plan",
    "PlanCache",
    "CalibrationReport",
    "StepSchedule",
    "NestedPartitionExecutor",
    "BlockedDGEngine",
    "bucket_counts",
]


# ---------------------------------------------------------------------------
# Bucketed counts — jit-cache-friendly chunk sizes
# ---------------------------------------------------------------------------


def bucket_counts(counts: Sequence[int], bucket: int) -> np.ndarray:
    """Round per-partition counts to multiples of ``bucket`` while conserving
    the total (largest-remainder on bucket units).  The sub-bucket tail goes
    to the largest partition; its padded shape is unchanged, so the set of
    compiled chunk shapes stays small across rebalances."""
    counts = np.asarray(counts, dtype=np.int64)
    total = int(counts.sum())
    if bucket <= 1 or total == 0:
        return counts.copy()
    units = total // bucket
    if units == 0:
        out = np.zeros_like(counts)
        out[int(np.argmax(counts))] = total
        return out
    ideal = units * counts / total
    base = np.floor(ideal).astype(np.int64)
    rem = units - int(base.sum())
    order = np.argsort(-(ideal - base), kind="stable")
    base[order[:rem]] += 1
    out = base * bucket
    out[int(np.argmax(counts))] += total - int(out.sum())
    assert out.sum() == total and (out >= 0).all()
    return out


def pad_to_bucket(n: int, bucket: int) -> int:
    """Padded (compiled) size for a chunk of ``n`` items."""
    if bucket <= 1 or n == 0:
        return n
    return int(-(-n // bucket) * bucket)


# ---------------------------------------------------------------------------
# Plans and the persistent plan cache
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Plan:
    """A solved split: normalized work weights and bucketed counts."""

    key: str
    weights: np.ndarray  # (P,) normalized
    counts: np.ndarray  # (P,) integer, bucketed, sums to K
    predicted_times: np.ndarray  # (P,) seconds under the current belief
    round: int = 0

    @property
    def makespan(self) -> float:
        return float(self.predicted_times.max()) if len(self.predicted_times) else 0.0


def plan_key(
    grid_dims: Optional[tuple],
    n_items: int,
    n_partitions: int,
    bucket: int,
    accel_fraction: float,
    weights: Sequence[float],
) -> str:
    """Stable hash of mesh/topology/weights identifying a solved split."""
    w = np.asarray(weights, dtype=np.float64)
    w = w / w.sum()
    payload = json.dumps(
        {
            "grid": list(grid_dims) if grid_dims else None,
            "K": int(n_items),
            "P": int(n_partitions),
            "bucket": int(bucket),
            "accel_fraction": round(float(accel_fraction), 6),
            "weights": [round(float(x), 6) for x in w],
        },
        sort_keys=True,
    )
    return hashlib.sha256(payload.encode()).hexdigest()[:16]


class PlanCache:
    """hash(mesh/topology/weights) -> solved split, persisted atomically via
    ``repro.checkpoint`` (one checkpoint directory per key, pruned to
    ``keep``).  A ``plan_latest`` marker records the last applied key so a
    restarted executor resumes from the calibrated split, not the naive
    one."""

    def __init__(self, root: str, keep: int = 8):
        self.root = root
        self.keep = keep
        os.makedirs(root, exist_ok=True)
        self.hits = 0
        self.misses = 0

    def _dir(self, key: str) -> str:
        return os.path.join(self.root, f"plan_{key}")

    def _marker(self) -> str:
        return os.path.join(self.root, "plan_latest")

    def mark_latest(self, key: str) -> None:
        tmp = self._marker() + ".tmp"
        with open(tmp, "w") as f:
            f.write(key)
        os.replace(tmp, self._marker())

    def get_latest(self, n_partitions: int) -> Optional[Plan]:
        try:
            with open(self._marker()) as f:
                key = f.read().strip()
        except FileNotFoundError:
            return None
        return self.get(key, n_partitions) if key else None

    def _prune(self) -> None:
        dirs = [
            os.path.join(self.root, d)
            for d in os.listdir(self.root)
            if d.startswith("plan_") and os.path.isdir(os.path.join(self.root, d))
        ]
        if len(dirs) <= self.keep:
            return
        dirs.sort(key=os.path.getmtime)
        import shutil

        for d in dirs[: len(dirs) - self.keep]:
            shutil.rmtree(d, ignore_errors=True)

    def get(self, key: str, n_partitions: int) -> Optional[Plan]:
        from repro.checkpoint import latest_step, restore

        d = self._dir(key)
        if latest_step(d) is None:
            self.misses += 1
            return None
        template = {
            "weights": np.zeros(n_partitions),
            "counts": np.zeros(n_partitions, dtype=np.int64),
            "predicted_times": np.zeros(n_partitions),
        }
        tree, manifest = restore(d, template)
        self.hits += 1
        return Plan(
            key=key,
            weights=np.asarray(tree["weights"], dtype=np.float64),
            counts=np.asarray(tree["counts"], dtype=np.int64),
            predicted_times=np.asarray(tree["predicted_times"], dtype=np.float64),
            round=int(manifest["extra"].get("round", 0)),
        )

    def put(self, plan: Plan) -> None:
        from repro.checkpoint import save

        tree = {
            "weights": plan.weights,
            "counts": plan.counts,
            "predicted_times": plan.predicted_times,
        }
        save(self._dir(plan.key), 0, tree, extra_meta={"key": plan.key, "round": plan.round})
        self._prune()


# ---------------------------------------------------------------------------
# The executor
# ---------------------------------------------------------------------------


class NestedPartitionExecutor:
    """Closes the paper's calibration loop at runtime.

    Two operating modes share the same solve/resplice machinery:

    * **measured** — ``observe`` is fed real per-partition step seconds (from
      ``BlockedDGEngine`` timing, or a synchronous driver attributing wall
      time);
    * **modeled** — ``time_models[p]`` is a callable ``T_p(k) -> seconds``
      (e.g. from ``repro.core.cost_model``); ``simulated_times`` evaluates it
      on the current counts.  This is how virtual heterogeneous fleets and
      CI-sized convergence tests run on a homogeneous container.

    ``inject_straggler(p, factor)`` multiplies partition ``p``'s *observed*
    times — the test hook for convergence: the executor must re-splice work
    away from the straggler until the predicted makespan is within ``rtol``
    of the common-finish-time optimum.
    """

    def __init__(
        self,
        n_items: int,
        n_partitions: int,
        *,
        grid_dims: Optional[tuple] = None,
        bucket: int = 16,
        smoothing: float = 0.5,
        ewma_alpha: float = 1.0,
        rebalance_every: int = 10,
        time_models: Optional[Sequence[Callable[[float], float]]] = None,
        plan_cache_dir: Optional[str] = None,
        initial_weights: Optional[Sequence[float]] = None,
        accel_fraction: float = 0.0,
        neighbors: Optional[np.ndarray] = None,
    ):
        if grid_dims is not None:
            expected = int(np.prod(grid_dims))
            if n_items != expected:
                raise ValueError(f"n_items={n_items} != prod(grid_dims)={expected}")
        self.n_items = int(n_items)
        self.n_partitions = int(n_partitions)
        self.grid_dims = tuple(grid_dims) if grid_dims is not None else None
        self.bucket = int(bucket)
        self.smoothing = float(smoothing)
        self.ewma_alpha = float(ewma_alpha)
        self.rebalance_every = int(rebalance_every)
        self.time_models = list(time_models) if time_models is not None else None
        if self.time_models is not None and len(self.time_models) != n_partitions:
            raise ValueError("need one time model per partition")
        self.plan_cache = PlanCache(plan_cache_dir) if plan_cache_dir else None
        self.accel_fraction = float(accel_fraction)
        # per-partition accelerator element counts (level-2 solve output);
        # overrides accel_fraction when set — see set_accel_counts()
        self.accel_counts: Optional[np.ndarray] = None
        # face-neighbour table the nested partition is built from; engines
        # whose mesh topology differs from the default non-periodic grid
        # (periodic bricks) install their own via set_neighbors()
        self.neighbors = None if neighbors is None else np.asarray(neighbors, dtype=np.int64)

        self._factors = np.ones(self.n_partitions)
        # ejected partitions are pinned at zero weight by every solve until
        # readmitted — the fault-tolerance layer's weight->0 ejection
        self.ejected: set = set()
        self._ewma: Optional[np.ndarray] = None
        self._obs_counts: Optional[np.ndarray] = None
        self._n_obs = 0
        self._step = 0
        self.round = 0
        self.partition: Optional[NestedPartition] = None
        self.offsets: Optional[np.ndarray] = None
        self._resplice_hooks: List[Callable[[], None]] = []
        self.history: List[Plan] = []
        # calibration-loop rounds run, and those whose solved counts differ
        # from the split before them (a converged split rebalances without
        # changing the plan)
        self.rebalances = 0
        self.plans_changed = 0

        w0 = np.asarray(
            initial_weights if initial_weights is not None else np.ones(n_partitions),
            dtype=np.float64,
        )
        self.weights = w0 / w0.sum()
        self.counts = bucket_counts(np.diff(splice(self.n_items, self.weights)), self.bucket)
        if self.plan_cache is not None:
            if initial_weights is None:
                # restart path: resume the last calibrated split, not naive
                latest = self.plan_cache.get_latest(self.n_partitions)
                if latest is not None and int(latest.counts.sum()) == self.n_items:
                    self.weights = latest.weights
                    self.counts = latest.counts.copy()
            else:
                # elastic-membership path: a fleet the cache has seen (same
                # seed weights, same P) resumes its solved splice directly
                key = plan_key(self.grid_dims, self.n_items, self.n_partitions,
                               self.bucket, self.accel_fraction, self.weights)
                cached = self.plan_cache.get(key, self.n_partitions)
                if cached is not None and int(cached.counts.sum()) == self.n_items:
                    self.weights = cached.weights
                    self.counts = cached.counts.copy()
        self._resplice()

    # -- introspection ------------------------------------------------------

    @property
    def chunk_pads(self) -> tuple:
        """Padded (compiled) chunk sizes — the jit-cache key set."""
        return tuple(pad_to_bucket(int(c), self.bucket) for c in self.counts)

    def rates(self) -> np.ndarray:
        """items/s per partition under the current belief (measured EWMA if
        available, else the time models, else uniform)."""
        if self._ewma is not None:
            with np.errstate(divide="ignore", invalid="ignore"):
                r = self._obs_counts / self._ewma
            good = np.isfinite(r) & (r > 0)
            if not good.any():
                return np.ones(self.n_partitions)
            r = np.where(good, r, r[good].mean())
            return r
        if self.time_models is not None:
            k = max(1, self.n_items // self.n_partitions)
            t = np.array([max(f(k), 1e-30) for f in self.time_models])
            return k / t
        return np.ones(self.n_partitions)

    def predicted_makespan(self) -> float:
        """max_p T_p(counts_p) under the current belief."""
        with np.errstate(divide="ignore", invalid="ignore"):
            t = self.counts / self.rates()
        return float(np.nanmax(np.where(self.counts > 0, t, 0.0)))

    def optimal_makespan(self) -> float:
        """Common-finish-time optimum for the current belief (continuous
        relaxation of ``solve_multiway``)."""
        rates = self.rates()
        fns = [lambda k, r=r: k / r for r in rates]
        res = solve_multiway(fns, self.n_items, integer=False)
        return res.makespan

    # -- test / simulation hooks -------------------------------------------

    @property
    def straggler_factors(self) -> np.ndarray:
        """Current per-partition straggler multipliers (a copy; see
        ``inject_straggler``).  Consumers pricing decisions off a
        calibration report — e.g. the serving loop's admission control —
        read these so an injected straggler reprices immediately."""
        return self._factors.copy()

    def inject_straggler(self, partition: int, factor: float) -> None:
        """Multiply partition's observed times by ``factor`` (test hook)."""
        self._factors[partition] = float(factor)

    def clear_stragglers(self) -> None:
        self._factors[:] = 1.0

    def simulated_times(self, counts: Optional[Sequence[int]] = None) -> np.ndarray:
        """Evaluate the time models on ``counts`` (default: current split).
        Straggler factors are NOT applied here — ``observe`` applies them, so
        a simulated measure->observe round counts them exactly once."""
        if self.time_models is None:
            raise RuntimeError("no time models configured")
        counts = self.counts if counts is None else np.asarray(counts)
        return np.array([self.time_models[p](int(counts[p])) for p in range(self.n_partitions)])

    # -- calibration / measurement -----------------------------------------

    def calibrate(
        self,
        measure_fn: Optional[Callable[[], "np.ndarray | CalibrationReport"]] = None,
        n_steps: int = 3,
    ) -> CalibrationReport:
        """Short calibration phase: run ``n_steps`` measurements and seed the
        EWMA.  ``measure_fn`` returns either a ``CalibrationReport`` (phase-
        resolved — e.g. a bound ``BlockedDGEngine.calibrate``) or plain
        per-partition step seconds (``BlockedDGEngine.measure_block_times``,
        the whole-step ``time_models`` default), which are carried as an
        unresolved ``CalibrationReport.from_totals``."""
        reports = []
        for _ in range(max(1, n_steps)):
            before = self._n_obs
            r = measure_fn() if measure_fn is not None else self.simulated_times()
            if not isinstance(r, CalibrationReport):
                r = CalibrationReport.from_totals(np.asarray(r))
            if self._n_obs == before:
                # only observe if the measure_fn didn't already feed us
                # (a bound BlockedDGEngine.calibrate observes internally)
                self.observe(r.step_s)
            reports.append(r)
        return CalibrationReport.median(reports)

    def observe(self, times: Sequence[float]) -> None:
        """Record measured per-partition step seconds (straggler factors are
        applied here — the single injection point)."""
        t = np.asarray(times, dtype=np.float64) * self._factors
        self._n_obs += 1
        if self._ewma is None or self.ewma_alpha >= 1.0:
            self._ewma = t.copy()
        else:
            self._ewma = self.ewma_alpha * t + (1.0 - self.ewma_alpha) * self._ewma
        # throughput must be computed against the counts these times were
        # measured under, not the counts a later resplice installs
        self._obs_counts = self.counts.astype(np.float64)

    def observe_total(self, dt: float) -> None:
        """Synchronous-step attribution: under a barrier every partition's
        step time equals the wall time (SPMD semantics).  Gives no skew
        signal by itself — stragglers enter via injection or per-partition
        measurement."""
        self.observe(np.full(self.n_partitions, float(dt)))

    def observe_chunk(self, report: "CalibrationReport", n_steps: int):
        """In-scan observation entry point: record one fused chunk's
        per-partition step seconds (a ``run_observed`` report — straggler
        factors are applied here, inside ``observe``, exactly once) and
        advance the rebalance schedule by the chunk's steps.  Returns the
        applied ``Plan`` when the schedule fired, else ``None``."""
        from jax.profiler import TraceAnnotation

        # host span: observe, then (when the schedule fires) solve and resplice
        with TraceAnnotation("dg.rebalance"):
            self.observe(np.asarray(report.step_s))
            return self.advance(int(n_steps))

    # -- solve / resplice ---------------------------------------------------

    def solve(self, weights: Sequence[float]) -> Plan:
        """Weights -> bucketed counts (plan-cache aware).  Ejected
        partitions are pinned at zero weight — the equalizer can never
        hand work back to a node the fault-tolerance layer removed."""
        w = np.asarray(weights, dtype=np.float64).copy()
        if self.ejected:
            w[sorted(self.ejected)] = 0.0
        if w.sum() <= 0:
            raise RuntimeError("no live partitions left to solve over")
        w = w / w.sum()
        key = plan_key(
            self.grid_dims, self.n_items, self.n_partitions, self.bucket,
            self.accel_fraction, w,
        )
        if self.plan_cache is not None:
            cached = self.plan_cache.get(key, self.n_partitions)
            if cached is not None and int(cached.counts.sum()) == self.n_items:
                return cached
        counts = bucket_counts(np.diff(splice(self.n_items, w)), self.bucket)
        with np.errstate(divide="ignore", invalid="ignore"):
            predicted = np.where(counts > 0, counts / self.rates(), 0.0)
        plan = Plan(key=key, weights=w, counts=counts, predicted_times=predicted, round=self.round)
        if self.plan_cache is not None:
            self.plan_cache.put(plan)
        return plan

    def set_neighbors(self, neighbors: np.ndarray) -> None:
        """Install the true mesh topology (e.g. a periodic brick's wrapping
        neighbour table) and re-splice so boundary/halo sets match it."""
        self.neighbors = np.asarray(neighbors, dtype=np.int64)
        self._resplice()

    def set_accel_counts(self, accel_counts: Optional[Sequence[int]]) -> None:
        """Install per-partition accelerator element counts (the hierarchical
        level-2 solve output) and re-splice.  ``None`` reverts to the static
        ``accel_fraction``.  Counts are clamped per node to the available
        interior by the partition build, so a stale count after a level-1
        resplice shrinks gracefully instead of erroring."""
        if accel_counts is None:
            self.accel_counts = None
        else:
            ac = np.asarray(accel_counts, dtype=np.int64)
            if len(ac) != self.n_partitions:
                raise ValueError(f"need {self.n_partitions} accel counts, got {len(ac)}")
            if (ac < 0).any():
                raise ValueError(f"accel counts must be non-negative, got {ac}")
            self.accel_counts = ac
        self._resplice()

    def _resplice(self) -> None:
        """Rebuild index arrays for the current counts.  Interior kernels are
        NOT recompiled: consumers key their jit caches on ``chunk_pads``."""
        if self.grid_dims is not None:
            self.partition = build_nested_partition(
                self.grid_dims,
                self.n_partitions,
                accel_fraction=self.accel_fraction,
                node_weights=np.maximum(self.counts, 0) if self.counts.sum() else None,
                accel_counts=self.accel_counts,
                neighbors=self.neighbors,
            )
            self.offsets = self.partition.offsets
        else:
            self.offsets = splice(self.n_items, np.maximum(self.counts, 1e-9))
        for hook in self._resplice_hooks:
            hook()

    def apply(self, plan: Plan) -> None:
        self.weights = plan.weights
        self.counts = plan.counts.copy()
        self.history.append(plan)
        if self.plan_cache is not None:
            self.plan_cache.mark_latest(plan.key)
        self._resplice()

    def rebalance(self) -> Plan:
        """One calibration-loop round: measured EWMA -> equalizer -> new
        bucketed split -> resplice."""
        if self._ewma is None:
            raise RuntimeError("rebalance before any observation; run calibrate() first")
        w = rebalance_from_measurements(
            np.maximum(self._obs_counts, 0),
            np.maximum(self._ewma, 1e-30),
            smoothing=self.smoothing,
            prev_weights=self.weights,
        )
        self.round += 1
        plan = dataclasses.replace(self.solve(w), round=self.round)
        changed = not np.array_equal(plan.counts, self.counts)
        self.apply(plan)
        self.rebalances += 1
        self.plans_changed += int(changed)
        return plan

    def plan_from_report(
        self,
        report: CalibrationReport,
        overlap: bool = True,
        apply: bool = True,
    ) -> Plan:
        """Overlap-aware solve from a phase-resolved calibration.

        Feeds ``t_p(k) = boundary + max(interior, transfer) + correction``
        (``report.time_models``) into ``solve_multiway``, so the planner
        credits a partition for transfer time hidden under its interior
        compute — the paper's Fig 5.1 schedule entering the balance
        equation.  With ``overlap=False`` the phases are charged
        back-to-back (the sequential strawman)."""
        fns = report.time_models(self.counts, overlap=overlap)
        res = solve_multiway(fns, self.n_items)
        w = np.maximum(np.asarray(res.counts, dtype=np.float64), 1e-9)
        plan = self.solve(w / w.sum())
        if apply:
            # the round counter tracks APPLIED resplices; a what-if solve
            # (apply=False) must not inflate it
            self.round += 1
            plan = dataclasses.replace(plan, round=self.round)
            self.apply(plan)
        return plan

    # -- ejection / elastic state -------------------------------------------

    def eject(self, partition: int) -> Plan:
        """Weight -> 0 for ``partition`` and re-splice the survivors — the
        straggler-ejection primitive.  Every subsequent solve keeps the
        ejected partition at zero until :meth:`readmit`; the engine side is
        automatic (a zero-count block builds no tables and joins no
        launches, so the fused loop stays one dispatch per chunk)."""
        p = int(partition)
        if not (0 <= p < self.n_partitions):
            raise ValueError(f"partition {p} out of range")
        live = self.n_partitions - len(self.ejected)
        if p not in self.ejected and live <= 1:
            raise RuntimeError("cannot eject the last live partition")
        self.ejected.add(p)
        self.round += 1
        plan = dataclasses.replace(self.solve(self.weights), round=self.round)
        self.apply(plan)
        return plan

    def readmit(self, partition: int, weight: Optional[float] = None) -> Plan:
        """Re-splice an ejected partition back in at ``weight`` (default:
        the live fleet's mean weight) — ejection is not sticky."""
        p = int(partition)
        self.ejected.discard(p)
        w = self.weights.copy()
        live = w > 0
        w[p] = float(weight) if weight is not None else (
            float(w[live].mean()) if live.any() else 1.0
        )
        self.round += 1
        plan = dataclasses.replace(self.solve(w), round=self.round)
        self.apply(plan)
        return plan

    def snapshot_state(self) -> dict:
        """The plan/belief state a checkpointed resplice needs to resume:
        everything the fault-tolerance layer saves next to ``q``."""
        return {
            "weights": self.weights.copy(),
            "counts": self.counts.copy(),
            "round": int(self.round),
            "exec_step": int(self._step),
            "ejected": sorted(self.ejected),
            "ewma": None if self._ewma is None else self._ewma.copy(),
            "obs_counts": None if self._obs_counts is None else self._obs_counts.copy(),
            "factors": self._factors.copy(),
        }

    def restore_state(self, state: dict) -> None:
        """Reinstall a :meth:`snapshot_state` (or the JSON-roundtripped
        subset a checkpoint manifest carries) and re-splice to its counts."""
        self.weights = np.asarray(state["weights"], dtype=np.float64)
        self.counts = np.asarray(state["counts"], dtype=np.int64).copy()
        self.round = int(state.get("round", self.round))
        self._step = int(state.get("exec_step", self._step))
        self.ejected = set(int(p) for p in state.get("ejected", ()))
        if state.get("ewma") is not None:
            self._ewma = np.asarray(state["ewma"], dtype=np.float64)
            self._obs_counts = (
                np.asarray(state["obs_counts"], dtype=np.float64)
                if state.get("obs_counts") is not None
                else self.counts.astype(np.float64)
            )
        if state.get("factors") is not None:
            self._factors = np.asarray(state["factors"], dtype=np.float64)
        self._resplice()

    def maybe_rebalance(self, step: Optional[int] = None) -> Optional[Plan]:
        """Step-driver hook: rebalance every ``rebalance_every`` steps
        (``rebalance_every <= 0`` disables the schedule)."""
        step = self._step if step is None else step
        if self.rebalance_every <= 0 or self._ewma is None or step == 0:
            return None
        if step % self.rebalance_every:
            return None
        return self.rebalance()

    def next_chunk(self, remaining: int) -> int:
        """Steps a chunked step driver runs before its next observation:
        ``remaining``, cut so the chunk ends on the next rebalance boundary
        whatever step the run starts from (all of it with the schedule off)."""
        every = self.rebalance_every
        if every <= 0:
            return int(remaining)
        return min(int(remaining), every - self._step % every)

    def advance(self, n_steps: int = 1) -> Optional[Plan]:
        """Advance the step counter by ``n_steps`` and rebalance if the
        schedule fires — the one protocol external step drivers use."""
        self._step += int(n_steps)
        return self.maybe_rebalance(self._step)

    def run_until_balanced(
        self,
        measure_fn: Optional[Callable[[], np.ndarray]] = None,
        rtol: float = 0.10,
        max_rounds: int = 8,
    ) -> int:
        """Measure -> rebalance until the predicted makespan is within
        ``rtol`` of the common-finish-time optimum; returns rounds used."""
        for r in range(1, max_rounds + 1):
            t = np.asarray(measure_fn() if measure_fn is not None else self.simulated_times())
            self.observe(t)
            self.rebalance()
            if self.predicted_makespan() <= (1.0 + rtol) * self.optimal_makespan():
                return r
        return max_rounds

    # -- step driver --------------------------------------------------------

    def drive(
        self,
        state,
        step_fn: Callable,
        n_steps: int,
        times_fn: Optional[Callable[["NestedPartitionExecutor", float], np.ndarray]] = None,
    ):
        """Run ``n_steps`` of ``step_fn(state) -> state``, observing wall time
        (or ``times_fn(self, dt)`` per-partition seconds) and rebalancing on
        schedule.  This is the API ``launch.train`` / ``launch.serve`` adopt."""
        for _ in range(n_steps):
            t0 = time.perf_counter()
            state = step_fn(state)
            dt = time.perf_counter() - t0
            if times_fn is not None:
                self.observe(np.asarray(times_fn(self, dt)))
            else:
                self.observe_total(dt)
            self.advance()
        return state


# ---------------------------------------------------------------------------
# Blocked DG engine — per-partition execution with halos
# ---------------------------------------------------------------------------


class BlockedDGEngine:
    """Executes a ``DGSolver`` rhs as per-partition element blocks with halo
    gathers — the executor's heterogeneous execution engine, a thin
    instantiation of the shared ``StepSchedule``.

    Per block, the four phases are: *boundary* packs the halo request (the
    index set that crosses the link), *exchange* gathers those remote
    elements, *interior* runs the volume kernel on the block's own elements
    (no halo dependence — the work that hides the transfer), and
    *correction* computes the face flux on the assembled block and folds it
    into the volume result.  ``calibrate`` times the phases separately
    (face-flux time is attributed to ``boundary_s`` — it is boundary-face
    work even though it executes inside the correction phase here).

    Each block's index tables are padded to ``bucket`` multiples, so after
    a resplice the per-block jit cache is hit whenever the padded sizes have
    been seen before; the full-field arrays never change shape.  The rhs is
    mathematically the flat solver's rhs restricted to each block (identical
    per-element arithmetic), so the partitioned run matches the flat run
    bitwise — the partition is a reordering, never an approximation.
    """

    def __init__(self, solver, executor: NestedPartitionExecutor,
                 only_blocks: Optional[Sequence[int]] = None):
        import jax

        if executor.grid_dims is None:
            raise ValueError("BlockedDGEngine needs a grid-backed executor")
        if tuple(executor.grid_dims) != tuple(solver.mesh.grid):
            raise ValueError(
                f"executor grid {executor.grid_dims} != solver grid {solver.mesh.grid}"
            )
        self.solver = solver
        self.executor = executor
        # chaos hook: a runtime.fault_tolerance.FailureInjector probed at
        # each observed chunk's dispatch (inside run_observed, before the
        # device program runs) — settable after construction
        self.injector = None
        # restrict this engine to a subset of partitions (a cluster node's
        # engine only ever executes its own block): other entries stay None,
        # so a resplice builds O(1) tables per engine instead of O(P)
        self.only_blocks = None if only_blocks is None else set(int(p) for p in only_blocks)
        self.pads_seen: set = set()
        self._blocks: list = []
        self._jax = jax
        self._build_jitted()
        self.schedule = self._make_schedule()
        # the partition's boundary/halo sets must reflect the SOLVER mesh's
        # topology (a periodic brick wraps; the default grid table does not)
        mesh_nbr = np.asarray(solver.mesh.neighbors, dtype=np.int64)
        current = executor.partition.neighbors if executor.partition is not None else executor.neighbors
        if current is None or not np.array_equal(current, mesh_nbr):
            executor.set_neighbors(mesh_nbr)
        else:
            executor.neighbors = mesh_nbr  # same table: no resplice needed
        self.rebuild()
        executor._resplice_hooks.append(self.rebuild)

    # -- jitted kernels (compiled once per padded block size) ---------------

    def _build_jitted(self):
        import jax
        import jax.numpy as jnp

        from repro.dg.operators import surface_rhs, volume_rhs_impl

        s = self.solver
        # one jitted bundle per solver, shared by every engine bound to it —
        # a SimulatedCluster's N engines would otherwise recompile the same
        # five kernels N times (jit caches live on the wrappers)
        bundle = getattr(s, "_blocked_jit_bundle", None)
        if bundle is None:
            D, metrics, lift = s.D, s.metrics, s.lift
            impl = s.kernel_impl  # Pallas volume AND flux kernels thread through

            def gather(q, idx):
                return q[idx]

            def assemble(q, own_idx, q_halo):
                # own gather is node-local; concatenated with the exchanged
                # halo this reproduces the extended block q[own ++ halo ++ pad]
                return jnp.concatenate([q[own_idx], q_halo], axis=0)

            def interior(q, own_idx, rho, lam, mu):
                return volume_rhs_impl(q[own_idx], D, metrics, rho, lam, mu,
                                       kernel_impl=impl)

            def boundary(qb, nbr_local, rho, lam, mu, cp, cs):
                return surface_rhs(qb, nbr_local, lift, rho, lam, mu, cp, cs,
                                   kernel_impl=impl)

            def fold(vol, sur):
                # rows past the block's own count are dump rows (scattered to
                # the sentinel); only the leading own rows must line up
                return vol + sur[: vol.shape[0]]

            bundle = tuple(jax.jit(f) for f in (gather, assemble, interior, boundary, fold))
            s._blocked_jit_bundle = bundle
        self._gather, self._assemble, self._interior, self._boundary, self._fold = bundle

    def _make_schedule(self) -> StepSchedule:
        """The block rhs as the shared four-phase schedule; ``state`` is
        ``(q, block)`` so one schedule (and one jit cache keyed on padded
        shapes) serves every block."""

        def boundary(state):
            _, b = state
            return b["halo"]  # the pack: which remote elements cross the link

        def exchange(send, state):
            q, _ = state
            return self._gather(q, send)

        def interior(state):
            q, b = state
            return self._interior(q, b["own_pad"], b["rho_o"], b["lam_o"], b["mu_o"])

        def correction(part, recv, state):
            q, b = state
            qb = self._assemble(q, b["own"], recv)
            sur = self._boundary(qb, b["nbr_local"], b["rho"], b["lam"],
                                 b["mu"], b["cp"], b["cs"])
            return self._fold(part, sur)

        return StepSchedule(boundary=boundary, exchange=exchange,
                            interior=interior, correction=correction, name="blocked-dg")

    # -- block tables -------------------------------------------------------

    def rebuild(self) -> None:
        """Re-splice: rebuild per-partition index tables from the executor's
        current ``NestedPartition`` (which carries each node's boundary/
        interior/halo index sets).  No kernel recompiles unless a brand-new
        padded size appears."""
        import jax.numpy as jnp
        from jax.profiler import TraceAnnotation

        with TraceAnnotation("dg.tables"):
            s = self.solver
            part = self.executor.partition
            K = s.mesh.K
            nbr = s.mesh.neighbors
            bucket = self.executor.bucket
            dt = jnp.dtype(s.dtype)
            # the (K+1)-row scatter target (row K is the dump row for padded
            # block rows) is shape-invariant across resplices — hoisted here,
            # and shared per solver (a SimulatedCluster's N engines reuse one
            # buffer), so rhs() never allocates a fresh zeros per evaluation
            if getattr(s, "_scatter_base", None) is None:
                s._scatter_base = jnp.zeros((K + 1, 9, s.M, s.M, s.M), dt)
            self._scatter_base = s._scatter_base
            blocks = []
            for p, node in enumerate(part.nodes):
                own = np.asarray(node.elements, dtype=np.int64)
                if len(own) == 0 or (self.only_blocks is not None and p not in self.only_blocks):
                    blocks.append(None)
                    continue
                halo = np.asarray(node.halo, dtype=np.int64)
                ext = np.concatenate([own, halo])
                pad = pad_to_bucket(len(ext), bucket)
                pad_own = pad_to_bucket(len(own), bucket)
                self.pads_seen.update((pad, pad_own))
                ext_pad = np.concatenate([ext, np.zeros(pad - len(ext), dtype=np.int64)])
                own_pad = np.concatenate([own, np.zeros(pad_own - len(own), dtype=np.int64)])
                halo_pad = ext_pad[len(own):]  # halo ++ zero-pad: concat target
                lut = np.full(K, -1, dtype=np.int64)
                lut[ext] = np.arange(len(ext))
                nbr_ext = nbr[ext_pad]
                # own rows: every real neighbour is in ext by construction, so
                # lut resolves it; -1 (physical boundary) is preserved.  halo and
                # pad rows may point outside ext -> -1; their output is dumped.
                nbr_local = np.where(nbr_ext >= 0, lut[np.clip(nbr_ext, 0, None)], -1)
                scat = np.concatenate([own, np.full(pad_own - len(own), K, dtype=np.int64)])
                blocks.append(
                    {
                        "own": jnp.asarray(own),
                        "own_pad": jnp.asarray(own_pad),
                        "halo": jnp.asarray(halo_pad),
                        "nbr_local": jnp.asarray(nbr_local),
                        "scat": jnp.asarray(scat),
                        "rho": jnp.asarray(s.rho[ext_pad], dt),
                        "lam": jnp.asarray(s.lam[ext_pad], dt),
                        "mu": jnp.asarray(s.mu[ext_pad], dt),
                        "cp": jnp.asarray(np.sqrt((s.lam + 2 * s.mu) / s.rho)[ext_pad], dt),
                        "cs": jnp.asarray(np.sqrt(s.mu / s.rho)[ext_pad], dt),
                        "rho_o": jnp.asarray(s.rho[own_pad], dt),
                        "lam_o": jnp.asarray(s.lam[own_pad], dt),
                        "mu_o": jnp.asarray(s.mu[own_pad], dt),
                        "n_own": len(own),
                    }
                )
            self._blocks = blocks

    # -- execution ----------------------------------------------------------

    def block_rhs(self, q, b):
        """One partition's rhs rows via the four-phase schedule."""
        return self.schedule.rhs((q, b))

    def scatter_base(self, q):
        """The hoisted (K+1)-row scatter target (falls back to a fresh zeros
        only when the caller's field dtype/shape differs from the solver's)."""
        import jax.numpy as jnp

        base = self._scatter_base
        if base.dtype != q.dtype or base.shape[1:] != tuple(q.shape[1:]):
            K = self.solver.mesh.K
            base = jnp.zeros((K + 1,) + tuple(q.shape[1:]), q.dtype)
        return base

    def rhs(self, q):
        """Full rhs assembled from per-partition block evaluations.

        Composition is phase-major (``StepSchedule.rhs_many``): every halo
        gather is issued before any interior kernel, so an async backend
        overlaps all transfers with all interiors — the same issue order the
        fused pipeline compiles into one program."""
        K = self.solver.mesh.K
        blocks = [b for b in self._blocks if b is not None]
        outs = self.schedule.rhs_many([(q, b) for b in blocks])
        out = self.scatter_base(q)
        for b, r in zip(blocks, outs):
            out = out.at[b["scat"]].set(r)
        return out[:K]

    def pipeline(self, groups=None, layout: str = "envelope"):
        """The fused scan-compiled step pipeline bound to this engine
        (built lazily, invalidated and rebuilt across resplices).

        The default ``layout="envelope"`` pads every block to a common
        envelope so each rhs is exactly ONE volume + ONE surface kernel
        launch regardless of the bucket split; ``layout="grouped"`` keeps
        the per-bucket launch batching (the bitwise differential reference,
        and the layout under which ``groups`` separates launches).

        ``groups`` (optional partition -> bucket-group map) keeps blocks of
        different groups out of each other's batched launches under the
        grouped layout — how a ``SimulatedCluster`` fuses each same-profile
        node group separately; the envelope layout batches across groups by
        design (its in-scan pricing is launch-grouping independent).  One
        pipeline is cached per distinct (grouping, layout)."""
        key = (
            None if groups is None else tuple(int(g) for g in groups),
            str(layout),
        )
        cache = getattr(self, "_pipelines", None)
        if cache is None:
            cache = self._pipelines = {}
        if key not in cache:
            from repro.runtime.pipeline import FusedStepPipeline

            cache[key] = FusedStepPipeline(self, groups=groups, layout=layout)
        return cache[key]

    def resplice(self, plan) -> None:
        """Apply a solved plan: the executor installs the new counts and the
        resplice hooks rebuild this engine's block tables (jit caches are
        hit whenever the padded block sizes have been seen before)."""
        self.executor.apply(plan)

    def run(self, q, n_steps: int, dt: Optional[float] = None, observe: bool = False,
            fused: bool = True):
        """Step driver: LSRK4(5) on the blocked rhs.

        ``fused`` (default) drives the ``FusedStepPipeline``: the whole time
        loop — ``lax.scan`` over steps, scan over the five LSRK stages,
        same-bucket blocks batched into one kernel launch — runs as a single
        donated device program, so host dispatches drop from
        O(stages x blocks) to O(1) per run.  With ``observe`` the run is
        segmented on the executor's rebalance schedule and each chunk is
        ONE fused dispatch through ``FusedStepPipeline.run_observed``: the
        per-partition cost accumulator rides the scan carry, the host
        synchronizes once per chunk, and the wall-attributed
        ``CalibrationReport`` feeds ``executor.observe_chunk`` — so
        observation never un-fuses the hot path and q stays bitwise
        identical to the unobserved run (the priced and plain programs
        perform the same field arithmetic).  ``fused=False`` is the eager
        per-block reference path; with ``observe`` it wall-times each step
        (one sync per step) and attributes it by the current counts."""
        import jax
        import jax.numpy as jnp

        from repro.dg.rk import lsrk45_step
        from repro.runtime.schedule import CalibrationReport

        dt = dt or self.solver.cfl_dt()
        if fused and not observe:
            return self.pipeline().run(q, n_steps, dt=dt)
        if fused:
            done = 0
            while done < n_steps:
                chunk = self.executor.next_chunk(n_steps - done)
                # after a resplice the pipeline rebuilds its tables; the
                # compiled program is reused while the bucket signature
                # (stable under bucketed counts) recurs
                q, report = self.pipeline().run_observed(
                    q, chunk, dt=dt,
                    injector=self.injector, step=self.executor._step,
                )
                self.executor.observe_chunk(report, chunk)
                done += chunk
            return q
        res = jnp.zeros_like(q)
        shares = np.maximum(self.executor.counts.astype(np.float64), 0.0)
        for _ in range(n_steps):
            if observe:
                t0 = time.perf_counter()
                q, res = lsrk45_step(q, res, self.rhs, dt)
                jax.block_until_ready(q)
                report = CalibrationReport.from_chunk(
                    time.perf_counter() - t0, shares, 1
                )
                self.executor.observe_chunk(report, 1)
                shares = np.maximum(self.executor.counts.astype(np.float64), 0.0)
            else:
                q, res = lsrk45_step(q, res, self.rhs, dt)
        return q

    # -- measurement --------------------------------------------------------

    def _time(self, fn, *args, reps: int = 1):
        """(median seconds, last result) — returning the result lets
        calibrate reuse each phase's output as the next phase's input
        instead of re-running kernels it already timed."""
        jax = self._jax
        out = fn(*args)
        jax.block_until_ready(out)  # warmup / compile
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            out = fn(*args)
            jax.block_until_ready(out)
            ts.append(time.perf_counter() - t0)
        ts.sort()
        return ts[len(ts) // 2], out

    def measure_block_times(self, q, reps: int = 1) -> np.ndarray:
        """Per-partition seconds for one rhs evaluation of each block
        (the full four-phase schedule, end to end)."""
        out = np.zeros(len(self._blocks))
        for p, b in enumerate(self._blocks):
            if b is None:
                continue
            out[p], _ = self._time(self.block_rhs, q, b, reps=reps)
        return out

    def calibrate(self, q, reps: int = 2, blocks: Optional[Sequence[int]] = None,
                  observe: Optional[bool] = None) -> CalibrationReport:
        """The executor's phase (1): time the four schedule phases per
        partition — boundary (face flux), interior (volume), transfer (halo
        gather) and correction (halo fold) — so the planner can run the
        overlap-aware solve (``NestedPartitionExecutor.plan_from_report``).

        ``blocks`` restricts the measurement to those partition indices (a
        cluster node calibrating only its own block); rows not measured stay
        zero.  ``observe`` defaults to full-fleet calibrations only: a
        partial report must NOT enter the executor's EWMA (the unmeasured
        partitions' 0.0s would read as infinitely fast and the equalizer
        would dump all work on them), so requesting observe=True together
        with a blocks subset is rejected — the caller (e.g.
        ``SimulatedCluster``) assembles a fleet report first and observes
        once."""
        if observe is None:
            observe = blocks is None
        elif observe and blocks is not None:
            raise ValueError(
                "cannot observe a partial calibration (blocks subset): "
                "unmeasured partitions would enter the EWMA as 0.0s"
            )
        P = len(self._blocks)
        boundary = np.zeros(P)
        interior = np.zeros(P)
        transfer = np.zeros(P)
        correction = np.zeros(P)
        picked = set(range(P)) if blocks is None else set(int(p) for p in blocks)
        for p, b in enumerate(self._blocks):
            if b is None or p not in picked:
                continue
            # each timed phase's output feeds the next phase, exactly like
            # the composed schedule — no kernel runs twice
            transfer[p], q_halo = self._time(self._gather, q, b["halo"], reps=reps)
            interior[p], vol = self._time(
                self._interior, q, b["own_pad"], b["rho_o"], b["lam_o"], b["mu_o"],
                reps=reps,
            )
            t_asm, qb = self._time(self._assemble, q, b["own"], q_halo, reps=reps)
            boundary[p], sur = self._time(
                self._boundary, qb, b["nbr_local"], b["rho"], b["lam"], b["mu"],
                b["cp"], b["cs"], reps=reps,
            )
            t_fold, _ = self._time(self._fold, vol, sur, reps=reps)
            # correction = everything the correction phase does besides the
            # face flux itself: assemble the block, fold the flux in
            correction[p] = t_asm + t_fold
        report = CalibrationReport(boundary_s=boundary, interior_s=interior,
                                   transfer_s=transfer, correction_s=correction)
        if observe:
            self.executor.observe(report.step_s)
        return report
