"""jit'd wrappers with implementation selection for every kernel.

impl:
  * "xla"       — pure-jnp reference path (CPU, and the 512-device dry-run:
                  Mosaic does not lower on the CPU backend);
  * "interpret" — the Pallas kernel body executed by the interpreter
                  (correctness tests on CPU);
  * "pallas"    — the Mosaic-compiled TPU kernel (deployment target).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.dg_flux import dg_flux_pallas
from repro.kernels.dg_volume import dg_volume_pallas
from repro.kernels.flash_attention import flash_attention_pallas


def dg_volume(q, D, metrics, rho, lam, mu, impl: str = "xla"):
    if impl == "xla":
        return ref.dg_volume_ref(q, D, metrics, rho, lam, mu)
    return dg_volume_pallas(q, D, metrics, rho, lam, mu, interpret=(impl == "interpret"))


def dg_flux(tm, tp, mat, scale, impl: str = "xla"):
    if impl == "xla":
        return ref.dg_flux_ref(tm, tp, mat, scale)
    return dg_flux_pallas(tm, tp, mat, scale, interpret=(impl == "interpret"))


def flash_attention_op(
    q, k, v, *, causal: bool = True, window: Optional[int] = None,
    scale: Optional[float] = None, impl: str = "xla",
):
    if impl == "xla":
        from repro.models.attention import flash_attention

        return flash_attention(q, k, v, causal=causal, window=window, scale=scale)
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, scale=scale,
        interpret=(impl == "interpret"),
    )
