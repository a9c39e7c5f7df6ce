"""Boundary/interior overlap primitives — the paper's level-2 idea on TPU.

The paper hides the slow intra-node link by computing *interior* elements
while *boundary* faces are in flight, synchronizing once per step.  On TPU
the same dependency structure is expressed by decomposing a collective +
matmul into a ring of (local matmul on the chunk you hold) || (ppermute of
the next chunk): XLA's latency-hiding scheduler overlaps the DMA with MXU
work because the two have no data dependence — exactly "interior compute
over boundary communication".

These run inside ``jax.shard_map``.  ``overlap_map`` is the shared
compute-over-communication pipeline: round ``i`` computes on the data in
hand while the communication for round ``i+1`` is issued.  The two matmul
collectives are thin instantiations of it — ``ring_allgather_matmul``
replaces ``all_gather -> matmul`` (activation gathering for column-parallel
layers); ``matmul_ring_reducescatter`` replaces ``matmul -> reduce_scatter``
(row-parallel layers).  Both are exact (tested against the fused forms).
``halo_exchange_1d`` is the one-round case consumed by the DG
``StepSchedule`` (``repro.runtime.schedule``): the exchange is issued, the
interior phase computes, the correction phase consumes the received halo.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax


def _perm_shift(axis_size: int, shift: int = 1):
    return [(i, (i + shift) % axis_size) for i in range(axis_size)]


def overlap_map(
    n_rounds: int,
    compute: Callable[[int, Any], Any],
    communicate: Callable[[int, Any], Any],
    carry: Any,
) -> Any:
    """Generic interior-over-boundary pipeline (the paper's Fig 5.1 loop).

    Runs ``carry = communicate(i, compute(i, carry))`` for rounds
    ``0 .. n_rounds-2`` and a final ``compute(n_rounds-1, carry)`` with
    nothing left to send.  Each round's communication carries the data the
    NEXT round's compute needs, so the two have no data dependence and the
    scheduler overlaps the DMA with the compute.

    The loop is unrolled in Python (``n_rounds`` is the — always concrete —
    ring size), which lets the latency-hiding scheduler see the whole
    pipeline and keeps per-round ``compute`` free to use round-specific
    constants.
    """
    if n_rounds < 1:
        raise ValueError(f"need at least one round, got {n_rounds}")
    for i in range(n_rounds - 1):
        carry = communicate(i, compute(i, carry))
    return compute(n_rounds - 1, carry)


def ring_allgather_matmul(
    x_shard: jnp.ndarray,
    w: jnp.ndarray,
    axis_name: str,
    reverse: bool = False,
) -> jnp.ndarray:
    """Compute ``all_gather(x_shard, axis) @ w`` without materializing the
    gather ahead of the matmul.

    x_shard: (m_local, k) — this member's chunk of the gathered dimension.
    w:       (k, n)       — local (already sharded on n outside, if at all).
    Returns (m_local * P, n), identical to the fused form.

    Each ring step multiplies the chunk currently held (interior work) while
    the next chunk is in flight via ppermute (boundary exchange).
    """
    P = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    m_local, _ = x_shard.shape
    n = w.shape[1]
    shift = -1 if reverse else 1
    perm = _perm_shift(P, shift)

    out0 = jnp.zeros((m_local * P, n), dtype=jnp.result_type(x_shard.dtype, w.dtype))

    def compute(i, carry):  # interior: multiply the chunk currently held
        out, chunk = carry
        src = (idx - i * shift) % P  # owner of the chunk we currently hold
        out = lax.dynamic_update_slice(out, (chunk @ w).astype(out.dtype), (src * m_local, 0))
        return out, chunk

    def communicate(i, carry):  # boundary: next chunk in flight
        out, chunk = carry
        return out, lax.ppermute(chunk, axis_name, perm)

    out, _ = overlap_map(P, compute, communicate, (out0, x_shard))
    return out


def matmul_ring_reducescatter(
    x: jnp.ndarray,
    w_shard: jnp.ndarray,
    axis_name: str,
) -> jnp.ndarray:
    """Compute ``reduce_scatter(x @ w, axis, dim=0)`` chunk-by-chunk.

    x:       (m, k_local)  — activations, sharded on the contraction dim.
    w_shard: (k_local, n)  — weights, sharded on the contraction dim.
    Returns (m / P, n): this member's scattered shard of the summed product.

    Ring accumulation: at each step, add your partial product for the chunk
    you are about to pass on (interior), then rotate the accumulator
    (boundary).  Requires m % P == 0.
    """
    P = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    m = x.shape[0]
    if m % P:
        raise ValueError(f"rows {m} not divisible by axis size {P}")
    mc = m // P
    perm = _perm_shift(P, 1)

    def partial_for(slot: jnp.ndarray) -> jnp.ndarray:
        xs = lax.dynamic_slice(x, (slot * mc, 0), (mc, x.shape[1]))
        return xs @ w_shard

    def compute(i, acc):
        # chunk destined for member (idx + P - 1 - i): add the local partial
        # to the rotating accumulator (the final round lands on slot = idx).
        slot = (idx + (P - 1) - i) % P
        return acc + partial_for(slot)

    def communicate(i, acc):  # pass the accumulator along the ring
        return lax.ppermute(acc, axis_name, perm)

    acc0 = jnp.zeros((mc, w_shard.shape[1]), dtype=jnp.result_type(x.dtype, w_shard.dtype))
    return overlap_map(P, compute, communicate, acc0)


def halo_exchange_1d(
    edge_lo: jnp.ndarray,
    edge_hi: jnp.ndarray,
    axis_name: str,
    wrap: bool = False,
):
    """Exchange 1-D halos with ring neighbours (the DG face exchange and the
    SSM chunk-state handoff both reduce to this).

    Sends ``edge_hi`` to the next member and ``edge_lo`` to the previous one;
    returns (recv_from_prev, recv_from_next).  With ``wrap=False`` the ends
    receive zeros (physical boundary).
    """
    P = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    fwd = _perm_shift(P, 1) if wrap else [(i, i + 1) for i in range(P - 1)]
    bwd = _perm_shift(P, -1) if wrap else [(i + 1, i) for i in range(P - 1)]
    from_prev = lax.ppermute(edge_hi, axis_name, fwd)
    from_next = lax.ppermute(edge_lo, axis_name, bwd)
    if not wrap:
        from_prev = jnp.where(idx == 0, jnp.zeros_like(from_prev), from_prev)
        from_next = jnp.where(idx == P - 1, jnp.zeros_like(from_next), from_next)
    return from_prev, from_next
