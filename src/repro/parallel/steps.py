"""Distributed train / prefill / serve step builders.

``make_train_step`` produces a donatable, jit-able
    (params, opt_state, batch) -> (params, opt_state, metrics)
with microbatch gradient accumulation via ``lax.scan`` (batch arrives as
(accum, micro, ...)), f32 grad accumulation sharded like the params, and
AdamW.  Gradient cross-device reduction is GSPMD-automatic in ``auto`` mode;
``podwise`` mode (core of the nested-partition mapping) wraps the step in a
``shard_map`` that is *manual over the pod axis only*: gradients are
explicitly summed across the slow inter-pod link — optionally int8-
compressed with error feedback — while intra-pod sharding stays automatic.

Sharding specs for jit come from the logical-axes trees
(``LM.param_axes()``/``cache_axes()``) mapped through the active rule set.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.collectives import compressed_psum
from repro.models.common import ModelConfig
from repro.models.zoo import LM
from repro.optim import OptConfig, adamw_update
from repro.parallel.axes import logical_axis_rules, make_rules, tree_specs


# ---------------------------------------------------------------------------
# Sharding plumbing
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StepShardings:
    params: Any  # pytree of NamedSharding
    opt: Any
    batch: Any
    cache: Any
    rules: Dict[str, Any]
    mesh: Mesh


def _named(mesh: Mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree, is_leaf=lambda x: isinstance(x, P)
    )


def batch_axes(cfg: ModelConfig, kind: str, accum: bool) -> Dict[str, tuple]:
    lead = ("accum",) if accum else ()
    if kind == "decode":
        ax: Dict[str, tuple] = {"tokens": ("batch",)}
        return ax
    if cfg.family == "audio":
        ax = {"features": lead + ("batch", None, None), "labels": lead + ("batch", None)}
    elif cfg.family == "vlm":
        ax = {
            "tokens": lead + ("batch", None),
            "patches": lead + ("batch", None, None),
            "labels": lead + ("batch", None),
        }
    else:
        ax = {"tokens": lead + ("batch", None), "labels": lead + ("batch", None)}
    if kind == "prefill":
        ax.pop("labels", None)
    return ax


def make_shardings(
    lm: LM,
    mesh: Mesh,
    *,
    kind: str,
    batch_shardable: bool = True,
    accum: bool = False,
    fsdp: bool = True,
) -> StepShardings:
    cfg = lm.cfg
    multi_pod = "pod" in mesh.axis_names
    rules = make_rules(
        multi_pod=multi_pod,
        batch_shardable=batch_shardable,
        kv_heads_shardable=(lm.plan is None or not lm.plan.kv_replicated),
        fsdp=fsdp,
    )
    rules["accum"] = None
    # elastic meshes may lack axes (e.g. resume on a data-only mesh):
    # degrade rules to whatever axes exist
    names = set(mesh.axis_names)
    for key, val in list(rules.items()):
        if isinstance(val, tuple):
            kept = tuple(a for a in val if a in names)
            rules[key] = kept if kept else None
        elif isinstance(val, str) and val not in names:
            rules[key] = None
    with logical_axis_rules(rules, mesh):
        pspecs = tree_specs(lm.param_axes())
        bspecs = tree_specs(batch_axes(cfg, kind, accum))
        cspecs = tree_specs(lm.cache_axes()) if kind == "decode" else None
    ospecs = {"m": pspecs, "v": pspecs, "step": P()}
    return StepShardings(
        params=_named(mesh, pspecs),
        opt=_named(mesh, ospecs),
        batch=_named(mesh, bspecs),
        cache=_named(mesh, cspecs) if cspecs is not None else None,
        rules=rules,
        mesh=mesh,
    )


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------


def make_train_step(
    lm: LM,
    opt_cfg: OptConfig,
    sh: StepShardings,
    *,
    grad_sync: str = "auto",  # auto | podwise | podwise_int8
) -> Callable:
    cfg = lm.cfg

    def loss_fn(params, mb):
        loss, metrics = lm.loss(params, mb)
        return loss, metrics

    # sharding hints use the full mesh under GSPMD-auto sync; inside the
    # manual-'pod' shard_map the ambient mesh has a Manual axis and full-mesh
    # NamedSharding hints are rejected -> hints off (outer jit shardings and
    # GSPMD propagation still pin the intra-pod layout)
    hint_mesh = sh.mesh if grad_sync == "auto" else None

    def accumulate(params, batch):
        """batch leaves: (A, micro, ...) -> mean grads/loss over A microbatches."""
        A = jax.tree.leaves(batch)[0].shape[0]

        def micro_step(carry, mb):
            gsum, lsum = carry
            with logical_axis_rules(sh.rules, hint_mesh):
                (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
            gsum = jax.tree.map(lambda a, g: a + g.astype(jnp.float32), gsum, grads)
            return (gsum, lsum + loss), None

        gsum0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (gsum, lsum), _ = lax.scan(micro_step, (gsum0, jnp.zeros(())), batch)
        inv = 1.0 / A
        return jax.tree.map(lambda g: g * inv, gsum), lsum * inv

    def train_step(params, opt_state, batch):
        grads, loss = accumulate(params, batch)
        params, opt_state, om = adamw_update(params, grads, opt_state, opt_cfg)
        metrics = {"loss": loss, **om}
        return params, opt_state, metrics

    if grad_sync == "auto":
        return train_step

    # podwise: manual over the slow 'pod' axis, auto within the pod.
    if "pod" not in sh.mesh.axis_names:
        raise ValueError("podwise grad sync needs the multi-pod mesh")
    compress = grad_sync == "podwise_int8"
    auto_axes = frozenset(a for a in sh.mesh.axis_names if a != "pod")

    def podwise_step(params, opt_state, batch):
        grads, loss = accumulate(params, batch)  # grads: summed within pod (auto)
        # explicit slow-link exchange, 1/pod of bytes prepared by in-pod
        # sharding; int8 payload if requested (paper: minimize slow-link bytes)
        if compress:
            grads = jax.tree.map(lambda g: compressed_psum(g, "pod") / 2.0, grads)
        else:
            grads = jax.tree.map(lambda g: lax.pmean(g, "pod"), grads)
        params, opt_state, om = adamw_update(params, grads, opt_state, opt_cfg)
        return params, opt_state, {"loss": lax.pmean(loss, "pod"), **om}

    def _pod_only(spec: P) -> P:
        """Manual-subset shard_map specs may only mention the manual axis."""
        def f(entry):
            if entry is None:
                return None
            if isinstance(entry, (tuple, list)):
                kept = tuple(a for a in entry if a == "pod")
                return kept[0] if len(kept) == 1 else (kept if kept else None)
            return entry if entry == "pod" else None

        return P(*(f(e) for e in spec))

    def step(params, opt_state, batch):
        pod = lambda tree: jax.tree.map(
            lambda s: _pod_only(s.spec), tree, is_leaf=lambda x: isinstance(x, NamedSharding)
        )
        f = jax.shard_map(
            podwise_step,
            mesh=sh.mesh,
            in_specs=(pod(sh.params), pod(sh.opt), pod(sh.batch)),
            out_specs=(pod(sh.params), pod(sh.opt), P()),
            check_vma=False,
            axis_names={"pod"},
        )
        return f(params, opt_state, batch)

    return step


# ---------------------------------------------------------------------------
# Serving steps
# ---------------------------------------------------------------------------


def make_prefill_step(lm: LM, sh: StepShardings) -> Callable:
    def prefill_step(params, batch):
        with logical_axis_rules(sh.rules, sh.mesh):
            logits, cache = lm.prefill(params, batch)
        return logits, cache

    return prefill_step


def make_serve_step(lm: LM, sh: StepShardings, *, masked: bool = False) -> Callable:
    """Greedy decode step.  ``masked=False`` is the classic one-shot batch
    step ``(params, cache, tokens) -> (next_tok, cache)``.

    ``masked=True`` is the continuous-batching variant the serving loop
    (``repro.runtime.serving``) drives: ``(params, cache, tokens, active)``
    where ``active`` is a per-row bool compaction/refill mask.  Inactive
    rows (finished / not-yet-refilled slots) hold their token and their
    per-row cache position (``cache["len"]``, a ``(B,)`` vector) frozen, so
    a freed row idles in place until a newly admitted request's prefill
    cache is spliced over it.  Active rows run the exact same arithmetic as
    the unmasked step — rows are computationally independent, which is what
    makes a mid-loop splice bitwise-identical to a fresh batch.
    """
    cfg = lm.cfg

    def serve_step(params, cache, tokens):
        with logical_axis_rules(sh.rules, sh.mesh):
            logits, cache = lm.decode_step(params, cache, tokens)
        # greedy over the *logical* vocab
        logits = jnp.where(
            jnp.arange(logits.shape[-1]) < cfg.vocab_size, logits, -jnp.inf
        )
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, cache

    if not masked:
        return serve_step

    def serve_step_masked(params, cache, tokens, active):
        next_tok, new_cache = serve_step(params, cache, tokens)
        next_tok = jnp.where(active, next_tok, tokens)
        # inactive rows do not advance their cache position (their slot-len
        # write above lands harmlessly and is fully overwritten on refill)
        new_cache["len"] = jnp.where(active, new_cache["len"], cache["len"])
        return next_tok, new_cache

    return serve_step_masked


# ---------------------------------------------------------------------------
# Microbatch layout helper
# ---------------------------------------------------------------------------


def accum_layout(global_batch: int, dp: int, target_per_device: int = 1) -> Tuple[int, int]:
    """(accum_steps, micro_batch): micro spread over dp, ~target/device."""
    micro = max(dp * target_per_device, 1)
    micro = min(micro, global_batch)
    while global_batch % micro:
        micro -= 1
    return global_batch // micro, micro
