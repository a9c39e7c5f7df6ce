"""Every mesh the repo builds comes from :func:`make_mesh`.

``jax.make_mesh`` gives Explicit axes by default, and the sharding hints
(``parallel.axes.shard``) and ``shard_map`` call sites here are written for
Auto axes: ``with_sharding_constraint`` refuses an Explicit one.  So every
mesh goes through this one builder, which passes ``AxisType.Auto``.

Defined as FUNCTIONS so importing this module never touches jax device
state.  The dry-run sets XLA_FLAGS=--xla_force_host_platform_device_count=512
before any jax import; smoke tests and benchmarks see the real single CPU
device and use ``debug_mesh``.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis Auto."""
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(AxisType.Auto,) * len(axis_names), devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def debug_mesh(data: int = 1, model: int = 1, pod: int = 0):
    """Small mesh over however many (possibly fake) local devices exist."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
