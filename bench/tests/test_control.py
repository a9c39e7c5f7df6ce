"""The control reads not correct: the plain reference computed one
precision down (three-pass bfloat16 derivative products, the step below
float32 at ``highest``), put in the program's place, lands farther from
the reference than each cell's limit allows.

At a size a test run holds: order 7 and the cells' element size (1/16), on
an 8 x 4 x 4 brick, three seeds, over as many steps as a run of the cell
makes.  The same reading at the cells' own sizes on the chip is
``bench/readings.py``'s ``control_err``.
"""

import json
import os

import numpy as np
import pytest

import harness
import inputs
from reference import dgsem

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEPS = {"dg-paper.nested": 30}


@pytest.mark.parametrize("name", sorted(STEPS))
def test_control_fails_the_limit(name):
    cell = harness.Cell.find(harness.load_spec(), name)
    limit = cell.limits["field_err"]["limit"]
    prob = dgsem.Problem(dict(cell.config, grid=[8, 4, 4], extent=[0.5, 0.25, 0.25]))
    ref, ctl = dgsem.make_run(prob, "highest"), dgsem.make_run(prob, "high")
    for seed in (2**31 + 1, 2**31 + 2, 2**31 + 3):
        q0 = inputs.initial_field(prob, seed, cell.traffic)
        q_ref = np.asarray(ref(q0, STEPS[name]))
        assert harness.field_err(np.asarray(ctl(q0, STEPS[name])), q_ref) > limit


def test_reference_agrees_with_the_programs_flat_solver():
    """The reference is written apart from the program; at float32 on the
    CPU the two agree to rounding over 20 steps of a small brick."""
    import jax

    from _solver import build_solver

    with open(os.path.join(BENCH, "configs", "dg-paper.json")) as f:
        cfg = dict(json.load(f), order=3, grid=[8, 4, 4], extent=[2.0, 1.0, 1.0])
    prob = dgsem.Problem(cfg)
    solver = build_solver(cfg, "xla")
    assert solver.cfl_dt() == pytest.approx(prob.dt, rel=1e-12)
    q0 = inputs.initial_field(prob, 7, json.load(open(os.path.join(BENCH, "traffic",
                                                                   "nested.json"))))
    q_ref = np.asarray(dgsem.make_run(prob)(q0, 20))
    with jax.default_matmul_precision("highest"):
        q = np.asarray(solver.run(jax.numpy.transpose(q0, (4, 0, 1, 2, 3)), 20, dt=prob.dt))
    assert harness.field_err(q.transpose(1, 2, 3, 4, 0), q_ref) < 1e-6
