"""Each cell at a size the Pallas interpreter runs in seconds."""

import dataclasses
import time

import harness

SIZES = {  # order 2; four blocks of 32 elements, or four slabs of two x-layers
    "dg-paper.nested": dict(order=2, grid=[8, 4, 4], extent=[2.0, 1.0, 1.0]),
    "dg-paper-x4.sharded": dict(order=2, grid=[8, 2, 2], extent=[4.0, 1.0, 1.0]),
}


def cell(name: str, **config) -> harness.Cell:
    c = harness.Cell.find(harness.load_spec(), name)
    return dataclasses.replace(c, config=dict(c.config, **SIZES[name], **config))


def run(c: harness.Cell, seed: int = 2**31 + 11, seconds: float = 0.2, trace=False,
        **kw) -> dict:
    import jax

    # the cache stays off: CPU programs stay out of the chip's cache
    return harness.run_cell(c, seed, seconds, trace, t_start=time.perf_counter(),
                            devices=jax.devices(), kernel_impl="interpret", cache=False,
                            log=lambda *a: None, **kw)
