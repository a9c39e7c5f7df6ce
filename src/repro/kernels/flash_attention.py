"""Pallas TPU flash attention (causal / sliding-window / encoder).

Standard TPU flash structure: grid (batch*heads, n_q_blocks, n_kv_blocks)
with the kv axis iterated minor-most (sequential on TPU), online-softmax
statistics (m, l, acc) living in VMEM scratch across kv steps, and the
output written once on the last visited kv block.

Unlike the lax fallback (models/attention.py), above-diagonal kv blocks are
SKIPPED via ``pl.when`` — causal attention costs the causal minimum here,
which is the kernel's main advantage besides fusion (the gap is visible in
the roofline useful-FLOPs ratio of the dry-run, which uses the lax path).

Blocks: q (Bq x D), k/v (Bk x D) — D = head_dim (80..160 for the zoo),
Bq = Bk = 128 by default: ~4 x 128 x 128 x 4 B ~= 0.26 MiB of VMEM scratch.

Validated against ``ref.flash_attention_ref`` in interpret mode across
shapes, dtypes, causal/SWA/encoder modes (tests/test_kernels.py).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: Optional[int],
                  block_q: int, block_k: int, n_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    # visit the block only if it can contribute
    visit = True
    if causal:
        visit = jnp.asarray(ki * block_k <= qi * block_q + block_q - 1)
    if window is not None:
        visit = jnp.logical_and(
            visit, jnp.asarray((ki + 1) * block_k - 1 > qi * block_q - window)
        )
    if isinstance(visit, bool):
        visit = jnp.asarray(visit)

    @pl.when(visit)
    def _body():
        q = q_ref[0].astype(jnp.float32)  # (block_q, D)
        k = k_ref[0].astype(jnp.float32)  # (block_k, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window is not None:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ki == n_kv - 1)
    def _finish():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jnp.ndarray,  # (B, H, S, D)
    k: jnp.ndarray,  # (B, H, S, D) — expand GQA before calling
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool,
) -> jnp.ndarray:
    """``interpret=False`` compiles the kernel with Mosaic (TPU only);
    ``interpret=True`` runs it in the Pallas interpreter (the CPU test
    path).  There is no default."""
    B, H, Sq, D = q.shape
    Skv = k.shape[2]
    scale = scale or 1.0 / math.sqrt(D)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    while Sq % block_q:
        block_q -= 1
    while Skv % block_k:
        block_k -= 1
    nq, nk = Sq // block_q, Skv // block_k

    qf = q.reshape(B * H, Sq, D)
    kf = k.reshape(B * H, Skv, D)
    vf = v.reshape(B * H, Skv, D)

    from jax.experimental.pallas import tpu as pltpu

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, causal=causal, window=window,
            block_q=block_q, block_k=block_k, n_kv=nk,
        ),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),  # m
            pltpu.VMEM((block_q, 1), jnp.float32),  # l
            pltpu.VMEM((block_q, D), jnp.float32),  # acc
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, D)
