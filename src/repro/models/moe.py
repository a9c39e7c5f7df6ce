"""Mixture-of-Experts layer: top-k routing, capacity-bounded scatter dispatch.

Dispatch follows the production (GSPMD/MegaBlocks-lineage) pattern rather
than the O(T*E*C) one-hot einsum, which does not fit memory at these sizes:

  1. route: top-k experts per token, gate = softmax over the selected logits
     (Mixtral style) or over all logits (OLMoE style, ``norm_topk=False``);
  2. position-in-expert via a cumulative sum over the (T, k) assignment
     matrix; assignments beyond the expert's capacity C are dropped
     (capacity_factor configurable; drop fraction returned as a metric);
  3. scatter tokens into a (E', C, d) buffer, run all experts as one batched
     (grouped) matmul, gather back and combine with gates.

Expert parallelism on the fixed (data=16, model=16) mesh: expert weights are
laid out (E', d, f') with E' sharded over ``data`` (the "expert" logical
axis) and f' over ``model`` (TP inside each expert slot).  When E < 16
(Mixtral: 8) each expert's d_ff is f-SPLIT into E'/E chunks — one slot per
chunk; tokens visit every chunk of their routed expert and the combine sums
the partials.  Exact math, no extra parameters (see ``padded_experts``).
In the nested-partition language: the dispatch all-to-all is the boundary
exchange; the local grouped matmul is interior work that XLA overlaps with
the combine collective.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.models.common import ModelConfig, dense_init


def padded_experts(cfg: ModelConfig, ep_size: int) -> Tuple[int, int]:
    """(E', rep): physical expert slots and the f-split factor.

    When E < ep_size each logical expert's d_ff is SPLIT into rep = ep/E
    chunks, one per slot (Mixtral: 8 experts -> 16 half-experts of d_ff
    8192).  Tokens visit all rep slots of their routed expert and the
    combine step sums the partial outputs — exactly the logical expert, no
    parameter duplication, no replica divergence under training.
    """
    E = cfg.n_experts
    if E >= ep_size:
        if E % ep_size:
            raise ValueError(f"{E} experts not divisible by ep axis {ep_size}")
        return E, 1
    if ep_size % E:
        raise ValueError(f"ep axis {ep_size} not a multiple of {E} experts")
    rep = ep_size // E
    if cfg.d_ff % rep:
        raise ValueError(f"d_ff {cfg.d_ff} not divisible by f-split {rep}")
    return E * rep, rep


def moe_init(key, cfg: ModelConfig, ep_size: int) -> Dict[str, Any]:
    E_pad, rep = padded_experts(cfg, ep_size)
    d, f = cfg.d_model, cfg.d_ff
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    f_loc = f // rep

    def expert_init(k, kind: str):
        keys = jax.random.split(k, cfg.n_experts)
        mats = []
        for e in range(cfg.n_experts):
            if kind == "down":  # (f, d) split along f (rows)
                w = dense_init(keys[e], f, d, dt)
                mats.extend(jnp.split(w, rep, axis=0) if rep > 1 else [w])
            else:  # (d, f) split along f (cols)
                w = dense_init(keys[e], d, f, dt)
                mats.extend(jnp.split(w, rep, axis=1) if rep > 1 else [w])
        return jnp.stack(mats)  # (E_pad, d, f_loc) or (E_pad, f_loc, d)

    return {
        "router": dense_init(ks[0], d, cfg.n_experts, dt),
        "w_gate": expert_init(ks[1], "up"),
        "w_up": expert_init(ks[2], "up"),
        "w_down": expert_init(ks[3], "down"),
    }


def moe_param_axes() -> Dict[str, Any]:
    # "expert" (-> data axis) already provides the ZeRO/FSDP sharding role
    # for expert weights; the d_model dim must stay unsharded to avoid
    # mapping the data axis twice.
    return {
        "router": (None, None),
        "w_gate": ("expert", None, "ff"),
        "w_up": ("expert", None, "ff"),
        "w_down": ("expert", "ff", None),
    }


def moe_apply(
    params: Dict[str, Any],
    x: jnp.ndarray,  # (T, d) flat tokens
    cfg: ModelConfig,
    *,
    ep_size: int,
    capacity: Optional[int] = None,
    norm_topk: bool = True,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Returns (out (T, d), metrics{aux_loss, drop_frac})."""
    T, d = x.shape
    E = cfg.n_experts
    k = cfg.experts_per_token
    E_pad, rep = padded_experts(cfg, ep_size)

    logits = (x @ params["router"].astype(x.dtype)).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)  # (T, k)
    if norm_topk:
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # f-split experts: each token visits all rep slots of its routed expert;
    # the gate-weighted combine sums the partial (f-chunk) outputs
    if rep > 1:
        slot = (expert_idx[..., None] * rep + jnp.arange(rep)).reshape(T, k * rep)
        gate_vals = jnp.repeat(gate_vals, rep, axis=1)
    else:
        slot = expert_idx
    k_eff = k * rep

    if capacity is None:
        capacity = int(np.ceil(T * k / cfg.n_experts * cfg.capacity_factor))
        capacity = max(8, min(capacity, T))

    # position of each assignment within its expert slot, in token order
    onehot = jax.nn.one_hot(slot.reshape(-1), E_pad, dtype=jnp.int32)  # (T*k_eff, E')
    pos = jnp.cumsum(onehot, axis=0) - 1  # inclusive -> 0-based
    pos = (pos * onehot).sum(-1)  # (T*k_eff,)
    keep = pos < capacity

    flat_slot = slot.reshape(-1)
    flat_gate = gate_vals.reshape(-1) * keep
    safe_pos = jnp.where(keep, pos, 0)

    # dispatch: (E', C, d)
    xk = jnp.repeat(x[:, None, :], k_eff, axis=1).reshape(T * k_eff, d)
    buf = jnp.zeros((E_pad, capacity, d), x.dtype)
    buf = buf.at[flat_slot, safe_pos].add(jnp.where(keep[:, None], xk, 0))

    # grouped expert FFN (gated silu)
    g = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"].astype(x.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, params["w_up"].astype(x.dtype))
    h = jax.nn.silu(g) * u
    out_buf = jnp.einsum("ecf,efd->ecd", h, params["w_down"].astype(x.dtype))

    # combine (sums over both the k routed experts and their rep f-chunks)
    gathered = out_buf[flat_slot, safe_pos]  # (T*k_eff, d)
    y = (gathered * flat_gate[:, None].astype(gathered.dtype)).reshape(T, k_eff, d).sum(axis=1)

    # Switch-style load-balancing auxiliary loss (over logical experts)
    frac_tokens = jnp.zeros(E, jnp.float32).at[expert_idx.reshape(-1)].add(1.0) / (T * k)
    frac_probs = probs.mean(axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs)
    drop_frac = 1.0 - keep.astype(jnp.float32).mean()
    return y, {"aux_loss": aux, "drop_frac": drop_frac}


# ---------------------------------------------------------------------------
# Expert-parallel dispatch in shard_map (the production path)
# ---------------------------------------------------------------------------
#
# GSPMD partitions the scatter/gather dispatch poorly (it falls back to
# "involuntary full rematerialization": replicated (tokens, d_model)
# temporaries that blow per-device memory at Mixtral scale).  The EP path
# makes the nested-partition structure explicit instead:
#
#   boundary (slow) work: two all_to_alls over the ``data`` axis moving only
#       capacity-bounded token slots (surface, not volume);
#   interior work: the grouped expert FFN, local in both ``data`` (expert
#       shard) and ``model`` (d_ff shard), overlapped by XLA's scheduler
#       with neighbouring collectives.
#
# Per (data x model) member: tokens arrive T_loc = T/dp, each shard owns
# E_loc = E'/dp experts and f_loc = d_ff/tp of every expert.


def moe_apply_ep(
    params: Dict[str, Any],
    x: jnp.ndarray,  # (T_loc, d) — this data-shard's tokens (manual view)
    cfg: ModelConfig,
    *,
    data_axis: str = "data",
    model_axis: str = "model",
    norm_topk: bool = True,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Manual-collective MoE; call inside shard_map(manual={data, model}).

    params are the *local shards*: router (d, E) replicated,
    w_gate/w_up (E_loc, d, f_loc), w_down (E_loc, f_loc, d).
    """
    T_loc, d = x.shape
    E = cfg.n_experts
    k = cfg.experts_per_token
    dp = lax.axis_size(data_axis)
    E_loc = params["w_gate"].shape[0]
    E_pad = E_loc * dp
    rep = E_pad // E

    logits = (x @ params["router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)
    if norm_topk:
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    # f-split experts: visit all rep slots of each routed expert (see
    # padded_experts); combine sums the partial outputs
    if rep > 1:
        slot = (expert_idx[..., None] * rep + jnp.arange(rep)).reshape(T_loc, k * rep)
        gate_vals = jnp.repeat(gate_vals, rep, axis=1)
    else:
        slot = expert_idx
    k_eff = k * rep

    # local capacity per (expert slot, source shard)
    C_loc = max(4, int(np.ceil(T_loc * k / cfg.n_experts * cfg.capacity_factor)))

    onehot = jax.nn.one_hot(slot.reshape(-1), E_pad, dtype=jnp.int32)  # (T_loc*k_eff, E')
    pos = (jnp.cumsum(onehot, axis=0) - 1) * onehot
    pos = pos.sum(-1)
    keep = pos < C_loc
    flat_slot = slot.reshape(-1)
    flat_gate = gate_vals.reshape(-1) * keep
    safe_pos = jnp.where(keep, pos, 0)

    xk = jnp.repeat(x[:, None, :], k_eff, axis=1).reshape(T_loc * k_eff, d)
    buf = jnp.zeros((E_pad, C_loc, d), x.dtype)
    buf = buf.at[flat_slot, safe_pos].add(jnp.where(keep[:, None], xk, 0))

    # boundary: send each expert block to its owner; receive blocks from all
    # shards -> (E_loc, dp*C_loc, d)
    buf = lax.all_to_all(buf.reshape(dp, E_loc, C_loc, d), data_axis, 0, 0, tiled=False)
    buf = buf.transpose(1, 0, 2, 3).reshape(E_loc, dp * C_loc, d)

    # interior: grouped FFN, f sharded over model axis
    g = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"].astype(x.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, params["w_up"].astype(x.dtype))
    h = jax.nn.silu(g) * u
    out = jnp.einsum("ecf,efd->ecd", h, params["w_down"].astype(x.dtype))
    out = lax.psum(out, model_axis)  # partial sums over f_loc shards

    # boundary: return slots to their source shards
    out = out.reshape(E_loc, dp, C_loc, d).transpose(1, 0, 2, 3)
    out = lax.all_to_all(out, data_axis, 0, 0, tiled=False).reshape(E_pad, C_loc, d)

    gathered = out[flat_slot, safe_pos]
    y = (gathered * flat_gate[:, None].astype(gathered.dtype)).reshape(T_loc, k_eff, d).sum(axis=1)

    frac_tokens = jnp.zeros(E, jnp.float32).at[expert_idx.reshape(-1)].add(1.0) / (T_loc * k)
    frac_probs = probs.mean(axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs)  # local estimate; psum'd by caller's mean
    drop_frac = 1.0 - keep.astype(jnp.float32).mean()
    return y, {"aux_loss": aux, "drop_frac": drop_frac}


def moe_ep_sharded(
    params: Dict[str, Any],
    h: jnp.ndarray,  # (B, S, d) global view, batch sharded over ('pod','data')
    cfg: ModelConfig,
    mesh,
    *,
    norm_topk: bool = True,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """shard_map wrapper installing the EP dispatch on the production mesh."""
    from jax.sharding import PartitionSpec as P

    multi_pod = "pod" in mesh.axis_names
    bspec = ("pod", "data") if multi_pod else ("data",)
    B, S, d = h.shape

    pspecs = {
        "router": P(None, None),
        "w_gate": P("data", None, "model"),
        "w_up": P("data", None, "model"),
        "w_down": P("data", "model", None),
    }

    def local(pr, hl):
        T_loc = hl.shape[0] * hl.shape[1]
        x = hl.reshape(T_loc, d)
        y, met = moe_apply_ep(pr, x, cfg, norm_topk=norm_topk)
        met = {k: lax.pmean(v, bspec + ("model",)) for k, v in met.items()}
        return y.reshape(hl.shape), met

    f = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(pspecs, P(bspec, None, None)),
        out_specs=(P(bspec, None, None), {"aux_loss": P(), "drop_frac": P()}),
        check_vma=False,
    )
    return f(params, h)
