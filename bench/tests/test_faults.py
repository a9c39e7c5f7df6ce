"""A run whose timed path is broken underneath reads ``correct`` false.

Each test drives the rest of a run (the chip check skipped, small sizes,
interpret-mode kernels) with one fault planted in the program's timed path:

* a dispatch that returns the state it was given;
* half of the elements left out of each dispatch's update;
* the exchange between chips left out (the ring ``ppermute`` sends zeros);
* an answer altered where it is produced (one node of the state).
"""

import jax.numpy as jnp
import pytest

from small import cell, run

CELLS = ["dg-paper.nested", "dg-paper-x4.sharded"]


class Wrapped:
    """The cell's driver with its dispatch replaced."""

    def __init__(self, drv, dispatch):
        self._drv, self._dispatch = drv, dispatch

    def __getattr__(self, name):
        return getattr(self._drv, name)

    def __setattr__(self, name, value):
        if name.startswith("_"):
            object.__setattr__(self, name, value)
        else:
            setattr(self._drv, name, value)

    def dispatch(self, q, n=None):
        return self._dispatch(self._drv, q, n)


def unchanged(drv):
    return Wrapped(drv, lambda d, q, n: jnp.copy(q))


def half_left_out(drv):
    def dispatch(d, q, n):
        out = d.dispatch(q, n)
        half = q.shape[0] // 2
        return out.at[:half].set(q[:half])

    return Wrapped(drv, dispatch)


def altered(drv):
    def dispatch(d, q, n):
        out = d.dispatch(q, n)
        return out.at[0, 6, 0, 0, 0].add(1e-2)

    return Wrapped(drv, dispatch)


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [unchanged, half_left_out, altered])
def test_fault_reads_incorrect(name, fault):
    res = run(cell(name), fault=fault)
    assert not res["correct"], res["checks"]
    assert res["failed"] == res["attempted"]
    assert res["checks"]["field_err"]["value"] > res["checks"]["field_err"]["limit"]


def test_exchange_left_out_reads_incorrect(monkeypatch):
    import repro.dg.partitioned as partitioned

    def no_exchange(lo, hi, axis, wrap=False):
        return jnp.zeros_like(hi), jnp.zeros_like(lo)

    monkeypatch.setattr(partitioned, "halo_exchange_1d", no_exchange)
    res = run(cell("dg-paper-x4.sharded"))
    assert not res["correct"], res["checks"]
