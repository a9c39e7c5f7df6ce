"""Pure-jnp oracles for every Pallas kernel (the ``ref`` side of the
kernel == ref allclose sweeps in tests/test_kernels.py)."""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.dg.operators import face_corrections, volume_rhs
from repro.models.attention import naive_attention


def dg_volume_ref(
    q: jnp.ndarray,  # (K, 9, M, M, M)
    D: jnp.ndarray,
    metrics: Tuple[float, float, float],
    rho: jnp.ndarray,
    lam: jnp.ndarray,
    mu: jnp.ndarray,
) -> jnp.ndarray:
    return volume_rhs(q, D, metrics, rho, lam, mu)


def dg_flux_ref(
    tm: jnp.ndarray,  # (6, 6, MM, R) minus-side traces
    tp: jnp.ndarray,  # (6, 6, MM, R) plus side
    mat: jnp.ndarray,  # (6, 10, R) per-face material and flag rows
    scale: Tuple[float, float, float],
) -> jnp.ndarray:
    return face_corrections(tm, tp, mat, scale)


def flash_attention_ref(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    return naive_attention(q, k, v, causal=causal, window=window, scale=scale)
