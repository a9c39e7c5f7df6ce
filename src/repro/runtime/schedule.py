"""The boundary/interior step schedule — paper Fig 5.1 as a four-phase object.

The paper's level-2 idea is a *schedule*, not just an element split: compute
the boundary faces first, launch the (slow-link) halo exchange, compute the
interior volume work while the exchange is in flight, and fold the received
halo back in once per step.  ``StepSchedule`` makes that four-phase
decomposition of one RHS evaluation explicit:

    1. **boundary**   — boundary-face compute / pack: produce the payload
                        that must cross a link (packed faces on the SPMD
                        slab path; the halo index set on the blocked engine);
    2. **exchange**   — the async halo exchange, *issued before* interior
                        work so the scheduler can overlap the two;
    3. **interior**   — volume compute with no halo dependence (this is
                        what hides the transfer);
    4. **correction** — fold the received halo into the partial result.

``rhs`` composes the phases in that order; because phase 3 has no data
dependence on phase 2's output, XLA's latency-hiding scheduler (or an async
backend) overlaps them — the dataflow form of the paper's CPU/MIC timeline.

Both DG execution engines are thin instantiations of this object:
``repro.dg.partitioned.PartitionedDG`` (SPMD slabs, ring ppermute exchange)
and ``repro.runtime.executor.BlockedDGEngine`` (per-partition blocks, halo
gather exchange).  ``CalibrationReport`` is the measurement side of the same
decomposition: per-partition seconds for each phase, plus the overlap-aware
step model ``t = boundary + max(interior, transfer) + correction`` that the
load-balance planner consumes (so a partition that hides its transfer under
interior compute is credited for it, paper section 5.6).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Optional, Sequence

import numpy as np

__all__ = ["StepSchedule", "CalibrationReport", "DispatchStats"]


@dataclasses.dataclass
class DispatchStats:
    """Host dispatches vs device steps advanced — the shared introspection
    ledger of every fused step driver (``runtime.pipeline.FusedStepPipeline``
    and ``ShardedStepPipeline`` both embed one).

    The fused drivers' whole point is O(1) dispatches per ``run()``
    regardless of step horizon, slab count and device count; the
    dispatch-count regression tests (``tests/test_pipeline.py``,
    ``tests/test_multidevice.py``) assert on this ledger so a future edit
    cannot silently re-Python-loop the hot path."""

    dispatches: int = 0
    steps_run: int = 0
    # fused chunks observed in-scan (run_observed calls): together with
    # ``dispatches`` this pins the observe-path invariant — observe=True
    # costs exactly ONE dispatch per rebalance chunk, never one per step
    observe_chunks: int = 0
    # stacked-table builds (one per resplice that a later dispatch sees)
    # and step-loop programs traced, plain and priced: a resplice that keeps
    # the bucket signature rebuilds tables and traces nothing.  Counted by
    # ``FusedStepPipeline``, whose programs are keyed on that signature
    table_builds: int = 0
    programs_built: int = 0
    # per-kernel launch sites per RHS evaluation inside the most recently
    # used compiled program, recorded at TRACE time (the stage scan traces
    # its body once, so launch sites per rhs = launches per stage = launches
    # per step up to the constant 5 LSRK stages).  The envelope-layout fused
    # pipeline must read {"volume": 1, "surface": 1} here regardless of the
    # bucket split — the per-kernel half of the dispatch-count regression.
    kernel_launches: dict = dataclasses.field(default_factory=dict)

    def record(self, dispatches: int, steps: int) -> None:
        self.dispatches += int(dispatches)
        self.steps_run += int(steps)

    def record_chunk(self, n: int = 1) -> None:
        """Ledger one observed fused chunk (an in-scan ``run_observed``)."""
        self.observe_chunks += int(n)

    def record_launches(self, counts: dict) -> None:
        """Install the per-kernel launch-site counts of the program that
        just ran (replaces, not accumulates: the counts describe ONE
        compiled program, not a running total)."""
        self.kernel_launches = {str(k): int(v) for k, v in counts.items()}

    @property
    def dispatches_per_step(self) -> float:
        return self.dispatches / max(1, self.steps_run)


@dataclasses.dataclass
class StepSchedule:
    """One RHS evaluation as four named phases (see module docstring).

    The callables share an opaque ``state`` (whatever the instantiating
    engine carries — field arrays, index tables):

      * ``boundary(state) -> send``              (phase 1: compute + pack)
      * ``exchange(send, state) -> recv``        (phase 2: async halo exchange)
      * ``interior(state) -> partial``           (phase 3: overlapped compute)
      * ``correction(partial, recv, state) -> out``   (phase 4: fold halo in)
    """

    boundary: Callable[[Any], Any]
    exchange: Callable[[Any, Any], Any]
    interior: Callable[[Any], Any]
    correction: Callable[[Any, Any, Any], Any]
    name: str = "step"

    PHASES = ("boundary", "exchange", "interior", "correction")

    def rhs(self, state):
        """Composed evaluation, exchange issued before interior.

        Trace order is the overlap order: the exchange enters the program
        before the (independent) interior compute, which is exactly what
        lets the scheduler run the two concurrently.
        """
        send = self.boundary(state)
        recv = self.exchange(send, state)
        part = self.interior(state)
        return self.correction(part, recv, state)

    def rhs_many(self, states):
        """Phase-major composition over many independent per-block states:
        every boundary pack and exchange is issued before any interior
        compute, so an async backend can overlap ALL of a step's transfers
        with ALL of its interior work instead of only block-local pairs.

        The blocks are independent (their phases never read each other's
        results), so the returned list is element-wise identical to mapping
        :meth:`rhs` over ``states`` — only the issue order changes.  This is
        the dispatch order the fused pipeline (``runtime.pipeline``) bakes
        into its single compiled program; here it is available to the
        eager per-block engine as well.
        """
        sends = [self.boundary(st) for st in states]
        recvs = [self.exchange(send, st) for send, st in zip(sends, states)]
        parts = [self.interior(st) for st in states]
        return [
            self.correction(part, recv, st)
            for part, recv, st in zip(parts, recvs, states)
        ]


def _zeros_like(a: np.ndarray) -> np.ndarray:
    return np.zeros_like(np.asarray(a, dtype=np.float64))


@dataclasses.dataclass(frozen=True)
class CalibrationReport:
    """Per-partition seconds for the four schedule phases (paper sec. 5.6).

    ``boundary_s`` is face-flux work wherever it executes (on the blocked
    engine the face flux runs inside the correction phase, but it is still
    boundary-face work and is attributed here); ``correction_s`` is the
    residual fold/assemble cost.  ``transfer_s`` is the slow-link halo
    exchange — the component the overlap schedule can hide.
    """

    boundary_s: np.ndarray  # face-flux work (the host keeps the network)
    interior_s: np.ndarray  # volume work (what the accelerator absorbs)
    transfer_s: np.ndarray  # slow-link exchange of the halo / shared faces
    correction_s: Optional[np.ndarray] = None  # halo fold-in (defaults to 0)

    def __post_init__(self):
        if self.correction_s is None:
            object.__setattr__(self, "correction_s", _zeros_like(self.boundary_s))

    # -- derived step models ------------------------------------------------

    @property
    def step_s(self) -> np.ndarray:
        """Sequential step: every phase back-to-back (no overlap)."""
        return self.boundary_s + self.interior_s + self.transfer_s + self.correction_s

    @property
    def overlapped_s(self) -> np.ndarray:
        """Overlap-aware step: interior hides the transfer (Fig 5.1)."""
        return (
            self.boundary_s
            + np.maximum(self.interior_s, self.transfer_s)
            + self.correction_s
        )

    @property
    def hidden_s(self) -> np.ndarray:
        """Transfer seconds hidden under interior compute per step."""
        return np.minimum(self.interior_s, self.transfer_s)

    @property
    def overlap_efficiency(self) -> np.ndarray:
        """hidden transfer / total transfer in [0, 1] (1.0 = fully hidden;
        defined as 1.0 where there is no transfer at all)."""
        t = np.asarray(self.transfer_s, dtype=np.float64)
        with np.errstate(divide="ignore", invalid="ignore"):
            eff = np.where(t > 0, self.hidden_s / np.where(t > 0, t, 1.0), 1.0)
        return eff

    # -- constructors -------------------------------------------------------

    @staticmethod
    def from_totals(step_s: Sequence[float]) -> "CalibrationReport":
        """A report from component-UNresolved per-partition step seconds
        (wall-clock attribution, whole-step time models).  The total lands
        in ``interior_s`` purely as a carrier; such a report makes no claim
        about phase composition and its ``overlap_efficiency`` is trivially
        1.0 everywhere."""
        t = np.asarray(step_s, dtype=np.float64)
        z = np.zeros_like(t)
        return CalibrationReport(boundary_s=z, interior_s=t, transfer_s=z.copy(),
                                 correction_s=z.copy())

    @staticmethod
    def from_chunk(
        wall_s: float, shares: Sequence[float], n_steps: int
    ) -> "CalibrationReport":
        """A report from ONE fused chunk: total host wall seconds for the
        chunk (``block_until_ready`` once per chunk), attributed across
        partitions by the in-scan accumulator ``shares`` — the carry-riding
        per-partition cost totals the fused scan accumulated on device.

        The chunk's per-step wall time is split proportionally to the
        shares (degenerate all-zero shares fall back to uniform), so the
        sum of the per-partition step seconds equals ``wall_s / n_steps``
        and the executor's throughput model sees real elapsed time at
        chunk granularity without a single extra dispatch.  Like
        ``from_totals`` the result is component-unresolved."""
        s = np.asarray(shares, dtype=np.float64)
        if s.ndim != 1 or len(s) == 0:
            raise ValueError(f"shares must be a non-empty vector, got shape {s.shape}")
        s = np.maximum(s, 0.0)
        tot = s.sum()
        s = s / tot if tot > 0 else np.full(len(s), 1.0 / len(s))
        per_step = float(wall_s) / max(1, int(n_steps))
        return CalibrationReport.from_totals(per_step * s)

    @staticmethod
    def median(reports: Sequence["CalibrationReport"]) -> "CalibrationReport":
        """Component-wise median over repeated calibration steps."""
        # materialize first: a lazily-consumed iterable (generator) would
        # slip past the emptiness check and surface as numpy's opaque
        # "need at least one array to stack" from np.stack below
        reports = list(reports)
        if not reports:
            raise ValueError(
                "CalibrationReport.median needs at least one report "
                "(got an empty sequence — did calibration run zero steps?)"
            )
        return CalibrationReport(
            boundary_s=np.median(np.stack([r.boundary_s for r in reports]), axis=0),
            interior_s=np.median(np.stack([r.interior_s for r in reports]), axis=0),
            transfer_s=np.median(np.stack([r.transfer_s for r in reports]), axis=0),
            correction_s=np.median(np.stack([r.correction_s for r in reports]), axis=0),
        )

    # -- planner interface --------------------------------------------------

    def time_models(
        self,
        counts: Sequence[int],
        overlap: bool = True,
        transfer_exponent: float = 2.0 / 3.0,
    ) -> List[Callable[[float], float]]:
        """Per-partition ``t_p(k)`` callables for the load-balance solvers.

        Compute phases scale linearly from the calibrated element counts;
        transfer scales with ``k**(2/3)`` (Morton-compact surface area,
        paper section 5.5).  With ``overlap=True`` the model is the paper's
        ``t = boundary + max(interior, transfer) + correction``, so the
        planner credits a partition for transfer hidden under interior work.

        A partition with no calibrated work at all (every phase 0.0 — e.g.
        its count was 0 when the engine measured) gets the fleet-mean phase
        times as a prior, mirroring ``rebalance_from_measurements``:
        otherwise its model would be identically zero and the waterfilling
        solve would dump the whole workload on it.
        """
        counts = np.asarray(counts, dtype=np.float64)
        P = len(counts)
        phases = np.stack([np.asarray(self.boundary_s, dtype=np.float64),
                           np.asarray(self.interior_s, dtype=np.float64),
                           np.asarray(self.transfer_s, dtype=np.float64),
                           np.asarray(self.correction_s, dtype=np.float64)])
        alive = phases.sum(axis=0) > 0
        if alive.any() and not alive.all():
            prior = phases[:, alive].mean(axis=1)
            c_prior = max(1.0, float(counts[alive].mean()))
            phases = phases.copy()
            phases[:, ~alive] = prior[:, None]
            counts = np.where(alive, counts, c_prior)
        fns: List[Callable[[float], float]] = []
        for p in range(P):
            c = max(1.0, float(counts[p]))
            b, i = float(phases[0, p]), float(phases[1, p])
            x, co = float(phases[2, p]), float(phases[3, p])

            def t(k: float, b=b, i=i, x=x, co=co, c=c) -> float:
                k = float(k)
                if k <= 0:
                    return 0.0
                scale = k / c
                xfer = x * scale**transfer_exponent
                compute = i * scale
                hot = max(compute, xfer) if overlap else compute + xfer
                return b * scale + hot + co * scale

            fns.append(t)
        return fns

    # -- reporting ----------------------------------------------------------

    def summary(self) -> str:
        rows = []
        eff = self.overlap_efficiency
        for p in range(len(self.boundary_s)):
            rows.append(
                f"p{p}: boundary={self.boundary_s[p] * 1e3:.2f}ms "
                f"interior={self.interior_s[p] * 1e3:.2f}ms "
                f"transfer={self.transfer_s[p] * 1e3:.2f}ms "
                f"correction={self.correction_s[p] * 1e3:.2f}ms "
                f"overlapped={self.overlapped_s[p] * 1e3:.2f}ms "
                f"overlap-eff={eff[p] * 100:.0f}%"
            )
        return "\n".join(rows)
