"""Hierarchy-aware collectives: the nested partition applied to communication.

The paper's rule — keep slow-link traffic at the surface-to-volume minimum
and synchronize once per step — becomes, on a multi-pod TPU mesh:

* gradients are reduce-scattered along the *fast* intra-pod axes, summed
  across pods over the *slow* DCN axis at 1/P of the bytes, then
  all-gathered back along the fast axes (`hierarchical_psum`);
* the slow hop can additionally be int8-compressed with per-chunk scales
  (`compressed_psum`); error feedback lives in the optimizer.

All functions are written for use *inside* ``jax.shard_map`` with the mesh
axes named as in ``launch/mesh.py`` (("pod",) "data", "model").
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def _pad_to_multiple(x: jnp.ndarray, mult: int) -> Tuple[jnp.ndarray, int]:
    n = x.shape[0]
    pad = (-n) % mult
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)
    return x, pad


def hierarchical_psum(x: jnp.ndarray, fast_axis, slow_axis: Optional[str] = None) -> jnp.ndarray:
    """psum over (fast_axis x slow_axis) that sends only 1/|fast| of the
    bytes over the slow link: RS(fast) -> psum(slow) -> AG(fast).

    ``fast_axis`` may be a tuple of axis names.  Works on any-shaped x
    (flattened internally, padded to the fast-axis multiple).
    """
    shape = x.shape
    flat = x.reshape(-1)
    fsize = lax.axis_size(fast_axis)
    flat, pad = _pad_to_multiple(flat, fsize)
    shard = lax.psum_scatter(flat, fast_axis, scatter_dimension=0, tiled=True)
    if slow_axis is not None:
        shard = lax.psum(shard, slow_axis)
    full = lax.all_gather(shard, fast_axis, axis=0, tiled=True)
    if pad:
        full = full[: flat.shape[0] - pad]
    return full.reshape(shape)


def quantize_int8(x: jnp.ndarray, block: int = 256) -> Tuple[jnp.ndarray, jnp.ndarray, int]:
    """Blockwise symmetric int8 quantization. Returns (q, scales, pad)."""
    flat = x.reshape(-1)
    flat, pad = _pad_to_multiple(flat, block)
    blocks = flat.reshape(-1, block)
    amax = jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
    return q, scale, pad


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray, pad: int, shape, dtype) -> jnp.ndarray:
    flat = (q.astype(jnp.float32) * scale).reshape(-1)
    if pad:
        flat = flat[:-pad]
    return flat.reshape(shape).astype(dtype)


def compressed_psum(x: jnp.ndarray, slow_axis: str, block: int = 256) -> jnp.ndarray:
    """psum along the slow axis with int8 payloads (4x fewer slow-link bytes
    than a bf16 ring).  Each member quantizes its shard, all-gathers the int8
    blocks + fp32 scales, and sums the dequantized copies locally.  Exact for
    the scales; quantization error is handled by error feedback in the
    optimizer (optim/grad_compress.py).
    """
    q, scale, pad = quantize_int8(x, block)
    qg = lax.all_gather(q, slow_axis, axis=0)  # (P, nblk, block) int8
    sg = lax.all_gather(scale, slow_axis, axis=0)  # (P, nblk, 1) f32
    total = jnp.sum(qg.astype(jnp.float32) * sg, axis=0)
    flat = total.reshape(-1)
    if pad:
        flat = flat[:-pad]
    return flat.reshape(x.shape).astype(x.dtype)


def hierarchical_psum_compressed(
    x: jnp.ndarray, fast_axis, slow_axis: Optional[str], block: int = 256
) -> jnp.ndarray:
    """RS(fast) -> compressed psum(slow) -> AG(fast)."""
    shape = x.shape
    flat = x.reshape(-1)
    fsize = lax.axis_size(fast_axis)
    flat, pad = _pad_to_multiple(flat, fsize)
    shard = lax.psum_scatter(flat, fast_axis, scatter_dimension=0, tiled=True)
    if slow_axis is not None:
        shard = compressed_psum(shard, slow_axis, block=block)
    full = lax.all_gather(shard, fast_axis, axis=0, tiled=True)
    if pad:
        full = full[: flat.shape[0] - pad]
    return full.reshape(shape)


def collective_bytes_psum(n_elements: int, dtype_bytes: int, axis_sizes: Sequence[int]) -> float:
    """Napkin-math wire bytes for a ring all-reduce over the given axes."""
    total = 1
    for s in axis_sizes:
        total *= s
    return 2.0 * (total - 1) / total * n_elements * dtype_bytes
