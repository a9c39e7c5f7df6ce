"""The cell's input, made from ``--seed``: the initial field.

One general generator for every traffic file.  A Gaussian pulse on the
x-velocity, whose centre and width the seed draws from the ranges the
traffic file gives, rides on a smooth background on all nine fields, so
that every element, every partition edge and both materials carry a
nonzero field that changes from the first step.  Every seed gives a field
of the same shape: the seed changes values, never the work.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def draw(seed: int, traffic: dict) -> dict:
    """The scalars of one seed's field (host side, a few dozen numbers)."""
    rng = np.random.default_rng([int(seed) < 0, abs(int(seed))])
    p, bg = traffic["pulse"], traffic["background"]
    lo, hi = p["centre_fraction"]
    return {
        "centre": rng.uniform(lo, hi, 3),
        "width": rng.uniform(*p["width"]),
        "amp": rng.uniform(-1.0, 1.0, (9, bg["modes"])) * bg["amplitude"],
        "wave": rng.integers(0, bg["max_wavenumber"] + 1, (9, bg["modes"], 3)),
        "phase": rng.uniform(0.0, 2 * np.pi, (9, bg["modes"])),
    }


def initial_field(prob, seed: int, traffic: dict, sharding=None) -> jax.Array:
    """The seed's field in the reference layout ``(9, M, M, M, K)``,
    float32, made on the device in one jitted call."""
    d = draw(seed, traffic)
    return field_program(prob, sharding)(
        f32(d["centre"] * np.asarray(prob.extent)), f32(d["width"]), f32(d["amp"]),
        f32(d["wave"]), f32(d["phase"]))


def field_program(prob, sharding=None):
    """The jitted program that makes a field from one seed's scalars."""
    ext = np.asarray(prob.extent)
    M, K = prob.M, prob.K

    def make(centre, width, amp, wave, phase):
        xyz = []
        for a in range(3):
            i = jax.lax.broadcasted_iota(jnp.int32, (M, M, M, K), 3)
            i = (i // prob.strides[a]) % prob.grid[a]
            r = jax.lax.broadcasted_iota(jnp.int32, (M, M, M, K), a)
            local = f32((prob.nodes + 1.0) / 2.0)[r]
            xyz.append((i.astype(jnp.float32) + local) * jnp.float32(prob.h[a]))
        x, y, z = xyz
        r2 = (x - centre[0]) ** 2 + (y - centre[1]) ** 2 + (z - centre[2]) ** 2
        k = 2 * jnp.pi * wave / f32(ext)  # (9, modes, 3)
        e = (Ellipsis, None, None, None, None)
        arg = k[..., 0][e] * x + k[..., 1][e] * y + k[..., 2][e] * z + phase[e]
        q = jnp.sum(amp[e] * jnp.sin(arg), axis=1)
        return q.at[6].add(jnp.exp(-r2 / (2 * width**2)))

    return jax.jit(make, out_shardings=sharding)


def f32(v):
    return jnp.asarray(np.asarray(v, np.float64), jnp.float32)
