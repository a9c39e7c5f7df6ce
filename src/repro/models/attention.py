"""Attention: blocked online-softmax (flash) in pure lax, SWA, GQA, decode.

Three implementations share one signature:
  * ``naive``   — O(S^2) materialized scores; the oracle for tests.
  * ``xla``     — blocked online softmax via ``lax.scan`` (memory O(S*Bk));
                  used on CPU and by the 512-device dry-run.
  * ``pallas``  — kernels/flash_attention.py (TPU target, same blocking).

Sliding-window attention slices only the needed KV range per q block
(static slice size ~``window + block_q``), so long-context SWA costs
O(S * window) instead of O(S^2) — this is what makes the 524k-token cells
lowerable for mixtral/hymba.

Note on causal full attention: the lax path sweeps every KV block and masks,
so compiled FLOPs are ~2x the causal minimum (visible in the roofline
useful-FLOPs ratio).  The Pallas kernel skips above-diagonal blocks via its
grid; see kernels/flash_attention.py.

Layouts: q (B, Hq, Sq, D); k, v (B, Hkv, Skv, D).  GQA is computed grouped
(fold kv-head into batch, q-per-kv into the head slot) when Hq % Hkv == 0,
otherwise via a per-q-head kv index map (replicated-kv plan, e.g. hymba).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def pick_block(n: int, target: int) -> int:
    """Largest divisor of n that is <= target (block sizes must tile seq)."""
    b = min(n, target)
    while n % b:
        b -= 1
    return b


def _expand_kv(k: jnp.ndarray, kv_map) -> jnp.ndarray:
    """Expand kv heads to one per q head using an index map."""
    idx = jnp.asarray(kv_map, jnp.int32)
    return jnp.take(k, idx, axis=1)


def naive_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    kv_map=None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Reference implementation (tests / tiny shapes only)."""
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    if kv_map is not None:
        k = _expand_kv(k, kv_map)
        v = _expand_kv(v, kv_map)
    elif Hq != Hkv:
        k = jnp.repeat(k, Hq // Hkv, axis=1)
        v = jnp.repeat(v, Hq // Hkv, axis=1)
    scale = scale or 1.0 / math.sqrt(D)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    qpos = q_offset + jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


# ---------------------------------------------------------------------------
# Blocked flash in lax
# ---------------------------------------------------------------------------


def _flash_qblock(
    qb: jnp.ndarray,  # (B, G, g, Bq, D) — one q block, GQA-grouped 5-D
    k: jnp.ndarray,  # (B, G, W, D)
    v: jnp.ndarray,
    qpos: jnp.ndarray,  # (Bq,) global positions of this q block
    kpos0,  # scalar: global position of k[..., 0, :]
    *,
    causal: bool,
    window: Optional[int],
    block_k: int,
    scale: float,
) -> jnp.ndarray:
    """Online softmax over kv blocks for one q block.

    The GQA group structure is kept as separate (G, g) dims — collapsing
    (batch, kv-head) into one dim merges two mesh axes and makes GSPMD
    replicate kv heads across the model axis (observed: 16x attention FLOPs
    at micro>1; EXPERIMENTS.md §Perf mixtral iteration 1)."""
    B, G, g, Bq, D = qb.shape
    W = k.shape[2]
    nk = W // block_k
    kb = k.reshape(B, G, nk, block_k, D).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(B, G, nk, block_k, D).transpose(2, 0, 1, 3, 4)

    def body(carry, kv):
        m, l, acc, j = carry
        kj, vj = kv  # (B, G, block_k, D)
        kpos = kpos0 + j * block_k + jnp.arange(block_k)
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qb, kj).astype(jnp.float32) * scale
        mask = jnp.ones((Bq, block_k), bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p, vj.astype(jnp.float32)
        )
        return (m_new, l, acc, j + 1), None

    m0 = jnp.full((B, G, g, Bq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, G, g, Bq), jnp.float32)
    acc0 = jnp.zeros((B, G, g, Bq, D), jnp.float32)
    (m, l, acc, _), _ = lax.scan(body, (m0, l0, acc0, jnp.int32(0)), (kb, vb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(qb.dtype)


def _flash_qblock_skip(
    qb: jnp.ndarray,  # (B, G, g, Bq, D) — GQA-grouped 5-D (see _flash_qblock)
    k: jnp.ndarray,  # (B, G, Skv, D)
    v: jnp.ndarray,
    qpos: jnp.ndarray,
    q_end_hint,  # traced scalar: global start of this q block
    *,
    block_k: int,
    scale: float,
) -> jnp.ndarray:
    """Causal online softmax sweeping ONLY kv blocks at/below the diagonal
    (dynamic fori bound) — inference paths only."""
    B, G, g, Bq, D = qb.shape
    Skv = k.shape[2]
    n_blocks = (q_end_hint + Bq + block_k - 1) // block_k
    n_blocks = jnp.minimum(n_blocks, Skv // block_k).astype(jnp.int32)

    def body(j, carry):
        m, l, acc = carry
        z = jnp.zeros((), jnp.int32)
        kj = lax.dynamic_slice(k, (z, z, j * block_k, z), (B, G, block_k, D))
        vj = lax.dynamic_slice(v, (z, z, j * block_k, z), (B, G, block_k, D))
        kpos = j * block_k + jnp.arange(block_k)
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qb, kj).astype(jnp.float32) * scale
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p, vj.astype(jnp.float32)
        )
        return m_new, l, acc

    m0 = jnp.full((B, G, g, Bq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, G, g, Bq), jnp.float32)
    acc0 = jnp.zeros((B, G, g, Bq, D), jnp.float32)
    m, l, acc = lax.fori_loop(0, n_blocks, body, (m0, l0, acc0))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(qb.dtype)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    kv_map=None,
    block_q: int = 512,
    block_k: int = 512,
    scale: Optional[float] = None,
    dynamic_skip: bool = False,
) -> jnp.ndarray:
    """Blocked attention. q (B,Hq,Sq,D), k/v (B,Hkv,Skv,D) -> (B,Hq,Sq,D).

    ``dynamic_skip``: causal KV sweep per q block runs a ``fori_loop`` with a
    *dynamic* upper bound (only blocks at/below the diagonal), cutting causal
    FLOPs ~2x vs the masked full sweep.  Inference-only (while loops with
    dynamic bounds are not reverse-mode differentiable); the Pallas kernel
    does the same skip on TPU for training too.
    """
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    scale = scale or 1.0 / math.sqrt(D)
    block_q = pick_block(Sq, block_q)
    block_k = pick_block(Skv, block_k)

    if kv_map is not None:
        k = _expand_kv(k, kv_map)
        v = _expand_kv(v, kv_map)
        Hkv = Hq
    if Hq % Hkv:
        raise ValueError(f"Hq {Hq} not a multiple of Hkv {Hkv}")
    g = Hq // Hkv
    q = q.reshape(B, Hkv, g, Sq, D)  # 5-D GQA-grouped layout throughout

    nq = Sq // block_q
    wpad = None
    if window is not None:
        wpad = ((window + block_q + block_k - 1) // block_k) * block_k
        if wpad >= Skv:
            wpad = None  # window covers (almost) everything: no point slicing

    def per_qblock(i):
        z = jnp.zeros((), jnp.int32)
        qs = (i * block_q).astype(jnp.int32)
        qb = lax.dynamic_slice(q, (z, z, z, qs, z), (B, Hkv, g, block_q, D))
        qpos = q_offset + qs + jnp.arange(block_q)
        if wpad is not None:
            start = jnp.clip(q_offset + qs + block_q - wpad, 0, Skv - wpad).astype(jnp.int32)
            ks = lax.dynamic_slice(k, (z, z, start, z), (B, Hkv, wpad, D))
            vs = lax.dynamic_slice(v, (z, z, start, z), (B, Hkv, wpad, D))
            kpos0 = start
        else:
            ks, vs, kpos0 = k, v, jnp.int32(0)
        if dynamic_skip and causal and window is None and wpad is None:
            return _flash_qblock_skip(
                qb, ks, vs, qpos, q_offset + qs, block_k=block_k, scale=scale
            )
        return _flash_qblock(
            qb, ks, vs, qpos, kpos0, causal=causal, window=window,
            block_k=block_k, scale=scale,
        )

    outs = lax.map(per_qblock, jnp.arange(nq))  # (nq, B, G, g, block_q, D)
    out = outs.transpose(1, 2, 3, 0, 4, 5).reshape(B, Hq, Sq, D)
    return out


# ---------------------------------------------------------------------------
# Decode (single new token against a cache)
# ---------------------------------------------------------------------------


def decode_attention(
    q: jnp.ndarray,  # (B, Hq, 1, D)
    k_cache: jnp.ndarray,  # (B, Hkv, C, D)  C = cache capacity
    v_cache: jnp.ndarray,
    cache_len: jnp.ndarray,  # int32: #tokens written so far — scalar or (B,)
    *,
    window: Optional[int] = None,
    rolling: bool = False,
    kv_map=None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """One-step attention against a (possibly rolling/SWA) KV cache.

    With ``rolling=True`` the cache is a circular buffer of capacity C
    (== window for SWA): once cache_len >= C every slot is valid, and
    ordering does not matter for softmax(QK)V.

    ``cache_len`` may be a per-row ``(B,)`` vector (continuous-batching
    serving: rows admitted at different times sit at different positions).
    Every op here is row-independent — batched einsums contract over
    non-batch dims and the slot mask broadcasts per row — so a row at
    length L computes bitwise what the scalar path computes at length L.
    """
    B, Hq, _, D = q.shape
    Hkv, C = k_cache.shape[1], k_cache.shape[2]
    scale = scale or 1.0 / math.sqrt(D)
    if kv_map is not None:
        # replicated-kv plan (small Hkv): gather is cheap
        k_cache = _expand_kv(k_cache, kv_map)
        v_cache = _expand_kv(v_cache, kv_map)
        Hkv = Hq
    grouped = Hq != Hkv
    if grouped:
        # grouped einsum — never materialize a per-q-head cache copy
        g = Hq // Hkv
        qg = q.reshape(B, Hkv, g, D)
        s = jnp.einsum("bhgd,bhkd->bhgk", qg, k_cache).astype(jnp.float32) * scale
    else:
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k_cache).astype(jnp.float32) * scale
    slots = jnp.arange(C)[None, None, None, :]
    clen = jnp.asarray(cache_len)
    if clen.ndim:  # per-row lengths -> broadcast over (B, H, q, slot)
        clen = clen[:, None, None, None]
    valid = slots < jnp.minimum(clen, C)
    if window is not None and not rolling:
        valid = valid & (slots >= clen - window)
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    if grouped:
        out = jnp.einsum("bhgk,bhkd->bhgd", p, v_cache)
        return out.reshape(B, Hq, 1, D)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v_cache)


def update_cache(
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    k_new: jnp.ndarray,  # (B, Hkv, 1, D)
    v_new: jnp.ndarray,
    cache_len,  # int32 tokens already in cache: scalar or per-row (B,)
    rolling: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    C = k_cache.shape[2]
    pos = jnp.asarray(cache_len) % C if rolling else jnp.asarray(cache_len)
    pos = pos.astype(jnp.int32)
    if pos.ndim:
        # per-row write slots (continuous batching): one-slot scatter per
        # row — writes the exact same k/v values the scalar slice path does
        rows = jnp.arange(k_cache.shape[0])
        k_cache = k_cache.at[rows, :, pos, :].set(k_new[:, :, 0].astype(k_cache.dtype))
        v_cache = v_cache.at[rows, :, pos, :].set(v_new[:, :, 0].astype(v_cache.dtype))
        return k_cache, v_cache
    z = jnp.zeros((), jnp.int32)
    k_cache = lax.dynamic_update_slice(k_cache, k_new.astype(k_cache.dtype), (z, z, pos, z))
    v_cache = lax.dynamic_update_slice(v_cache, v_new.astype(v_cache.dtype), (z, z, pos, z))
    return k_cache, v_cache


# ---------------------------------------------------------------------------
# Ring attention: the paper's boundary/interior halo rotation applied to
# sequence-parallel attention (context parallelism)
# ---------------------------------------------------------------------------


def ring_attention(
    q: jnp.ndarray,  # (B, Hq, S_loc, D) — this member's sequence shard
    k: jnp.ndarray,  # (B, Hkv, S_loc, D)
    v: jnp.ndarray,
    axis_name: str,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Sequence-parallel attention inside ``shard_map``.

    Exactly the paper's scheme in 1-D: each ring step computes attention of
    the local queries against the KV chunk currently held (*interior* work)
    while the chunk travels to the next member via ``ppermute`` (*boundary*
    exchange); online-softmax statistics merge the steps.  P-1 ppermutes of
    the KV shard replace any all-gather of the full sequence — surface, not
    volume, over the link.
    """
    import math as _math

    B, Hq, S_loc, D = q.shape
    Hkv = k.shape[1]
    g = Hq // Hkv
    P = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    scale = scale or 1.0 / _math.sqrt(D)
    qg = q.reshape(B, Hkv, g, S_loc, D)
    qpos = idx * S_loc + jnp.arange(S_loc)

    m = jnp.full((B, Hkv, g, S_loc), NEG_INF, jnp.float32)
    l = jnp.zeros((B, Hkv, g, S_loc), jnp.float32)
    acc = jnp.zeros((B, Hkv, g, S_loc, D), jnp.float32)
    perm = [(i, (i + 1) % P) for i in range(P)]

    kc, vc = k, v
    for j in range(P):
        src = (idx - j) % P  # owner of the chunk we hold this step
        kpos = src * S_loc + jnp.arange(S_loc)
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kc).astype(jnp.float32) * scale
        if causal:
            s = jnp.where(kpos[None, :] <= qpos[:, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p, vc.astype(jnp.float32)
        )
        m = m_new
        if j < P - 1:  # boundary exchange overlaps the next interior step
            kc = lax.ppermute(kc, axis_name, perm)
            vc = lax.ppermute(vc, axis_name, perm)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, Hq, S_loc, D).astype(q.dtype)
