"""The readings a limit is set from, for one cell, many seeds in one process.

  python3 bench/readings.py --workload <name> --seeds 1,2,3 --dispatches 2

For each seed: the program's timed path (the cell's driver, its warm
dispatch of ``warm_steps`` and ``dispatches`` full ones, as a run makes
them) against the plain reference (``field_err``), and the control, the
reference in the next precision down (three-pass bfloat16 products) in the
program's place, against the same reference (``control_err``).
``unchanged_err`` is what a path that returns its input unchanged would
read.  Each reading is printed with whether it passes the cell's limit by
the test that decides ``correct`` (``harness.passes``).  One JSON line per
seed.  The benchmark's own runs never run the control.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--dispatches", type=int, required=True)
    args = ap.parse_args(argv)

    import numpy as np

    harness._paths()
    import inputs
    from reference import dgsem

    cell = harness.Cell.find(harness.load_spec(), args.workload)
    chips = int(cell.workload["chips"])
    try:
        devices = harness.check_devices(chips)
    except harness.NoChip as e:
        print(f"readings: {e}", file=sys.stderr)
        return 2
    harness.use_cache()
    limit = float(cell.limits["field_err"]["limit"])
    prob = dgsem.Problem(cell.config)
    sharding = harness.reference_sharding(devices, chips)
    drv_mod = harness._module(os.path.join(harness.BENCH, "drivers",
                                           cell.traffic["driver"] + ".py"))
    ref_run, ctl_run = dgsem.make_run(prob, "highest"), dgsem.make_run(prob, "high")
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        q0 = inputs.initial_field(prob, seed, cell.traffic, sharding)
        drv = drv_mod.Driver(cell.config, cell.traffic, prob, q0, devices, cell.config["kernels"])
        warm = int(cell.traffic["warm_steps"])
        q = drv.dispatch(drv.state, warm)
        for _ in range(args.dispatches):
            q = drv.dispatch(q)
        q_prog = drv.to_reference(q)
        steps = warm + args.dispatches * drv.steps_per_dispatch
        del q, drv
        q_ref = np.asarray(ref_run(q0, steps))
        q_ctl = np.asarray(ctl_run(q0, steps))
        errs = {"field_err": harness.field_err(q_prog, q_ref),
                "control_err": harness.field_err(q_ctl, q_ref),
                "unchanged_err": harness.field_err(np.asarray(q0), q_ref)}
        print(json.dumps({
            "workload": args.workload, "seed": seed, "steps": steps, **errs,
            "limit": limit, **{k + "_passes": harness.passes(v, limit) for k, v in errs.items()},
            "seconds": time.perf_counter() - t0,
        }), flush=True)
        del q0, q_ref, q_ctl, q_prog
    return 0


if __name__ == "__main__":
    sys.exit(main())
