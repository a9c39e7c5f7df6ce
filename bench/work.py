"""Operations and bytes the DGSEM step needs, counted from the algorithm.

A copy of the program's analytic ``DGWorkModel`` (``core/cost_model.py``)
kept with the benchmark, at 4-byte words: a kernel's roofline then reads
the same work whatever implements it.  Counts are per element per rhs
evaluation (one LSRK stage) and are charged over the mesh's real elements,
never over padded rows.

What each kernel is charged:

* ``dg_volume``: the model's ``volume_loop`` FLOPs (the tensor-product
  derivative, ``2 M`` multiply-adds per node per field and axis, plus the
  flux and scaling terms), not the ``M^3 x M^3`` dense operator the kernel
  happens to apply.  Bytes: the state read once and the rhs written once,
  plus the three material words.  The model's ``volume_loop`` bytes also
  charge per-node metric terms and flux temporaries; on the affine brick the
  metric is one number per axis and a kernel need not spill temporaries, so
  charging them would let a kernel pass 100% of its roofline.
* ``dg_flux``: the model's ``int_flux`` FLOPs and bytes: three faces per
  element (each interior face shared by two), both sides' traces read and
  both sides' corrections written.

Both kernels are bound by bytes at every order the model covers here
(``bound`` says which).
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
WORD = 4  # float32
FIELDS = 9
RIEMANN_FLOPS = 170  # per face node, the model's count of the exact flux


def kernel_work(kernel: str, order: int) -> tuple[float, float]:
    """(FLOPs, bytes) per element per rhs evaluation."""
    M = order + 1
    V, A = M**3, M**2  # nodes per element, per face
    if kernel == "dg_volume":
        flops = 3 * FIELDS * V * 6 + 3 * FIELDS * V * 2 * M + FIELDS * V * 2
        return float(flops), float(2 * FIELDS * V * WORD + 3 * WORD)
    if kernel == "dg_flux":
        return float(3 * A * RIEMANN_FLOPS), float(3 * A * (2 * FIELDS) * WORD * 2)
    raise KeyError(f"no work model for kernel {kernel!r}")


def peaks(device_kind: str) -> dict:
    """The peak table's row for this device; an unknown device is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device {device_kind!r} is not in bench/peaks.json")
    return table[device_kind]


def least_seconds(kernel: str, order: int, elements: int, rhs_evals: int,
                  device_kind: str) -> tuple[float, str]:
    """(seconds, bound): the least time the chip could take for
    ``rhs_evals`` evaluations of ``kernel`` over ``elements`` elements."""
    flops, nbytes = kernel_work(kernel, order)
    pk = peaks(device_kind)
    n = float(elements) * float(rhs_evals)
    t_flops, t_bytes = n * flops / pk["flops_per_s"], n * nbytes / pk["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
