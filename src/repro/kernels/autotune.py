"""Block-size autotuner for the Pallas DG kernels — measured rooflines.

The hand-derived defaults (BE = 16 elements per volume grid step, BF = 128
element rows, six faces each, per flux grid step) were sized for a TPU
MXU/VPU on paper napkin math.
Calore et al. (PAPERS.md, lattice-Boltzmann on heterogeneous computers) show
the last ~2x of a stencil code lives in exactly this per-device-class block
tuning, and Tzovas & Predari's experimental study shows modeled costs must
be re-fit from measurements.  This module closes both loops:

1. **sweep** — time ``dg_volume_pallas`` over BE candidates and
   ``dg_flux_pallas`` over BF candidates on the *current* device — compiled
   on a TPU, or in the Pallas interpreter when the caller passes
   ``interpret=True`` (how CI exercises the machinery on CPU).  Each
   candidate is timed at two problem sizes and fit as
   ``t(K) = overhead + K * sec_per_element``, so the winner is chosen on
   the marginal (roofline) cost and the intercept is a measured per-launch
   overhead;
2. **cache** — winners land in a JSON keyed by
   ``(device_kind, order, n_fields)`` (default
   ``~/.cache/repro-dg/autotune.json``, override with
   ``$REPRO_AUTOTUNE_CACHE`` or ``--cache``), uploaded as a CI artifact so
   the per-device roofline has a tracked trajectory;
3. **feed back** — ``activate(entry)`` installs the winning block sizes in
   the kernel modules (every later trace — flat solver, blocked engine,
   fused pipeline — picks them up), and
   ``repro.core.cost_model.CalibrationTable.from_autotune`` turns the
   measured seconds into the planner's calibration table, so
   ``solve_two_way`` / ``solve_hierarchical`` plan on observed rooflines
   instead of the analytic model.

CLI::

    PYTHONPATH=src python -m repro.kernels.autotune \
        --device-class cpu-interpret --order 2 --smoke --interpret \
        --cache autotune_kernels.json

Both kernels are arithmetically block-invariant (the volume kernel is
block-diagonal per element, the flux kernel pure per-face VPU work), so the
sweep only moves *time*, never results — the bitwise differential harnesses
hold under any activated winner.
"""

from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "DEFAULT_BE_CANDIDATES",
    "DEFAULT_BF_CANDIDATES",
    "default_cache_path",
    "detect_device_kind",
    "entry_key",
    "load_cache",
    "save_entry",
    "lookup",
    "best_blocks",
    "sweep_volume",
    "sweep_flux",
    "autotune",
    "activate",
]

DEFAULT_BE_CANDIDATES = (8, 16, 32)
DEFAULT_BF_CANDIDATES = (128, 256, 512)  # element rows are lanes: multiples of 128
N_STAGES = 5  # LSRK4(5): rhs evaluations per timestep
FACES_PER_ELEMENT = 6  # our surface_rhs computes all 6 faces of every element


# ---------------------------------------------------------------------------
# Cache: JSON keyed by (device_kind, order, n_fields)
# ---------------------------------------------------------------------------


def default_cache_path() -> str:
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "repro-dg", "autotune.json")


def detect_device_kind(interpret: bool) -> str:
    """A stable label for the current accelerator class (``tpu-v4``,
    ``nvidia-a100``, ``cpu``), suffixed ``-interpret`` when the Pallas
    kernels run in interpret mode (the CPU/CI path)."""
    import jax

    d = jax.devices()[0]
    kind = str(getattr(d, "device_kind", "") or d.platform).lower()
    kind = kind.replace(" ", "-").replace("_", "-")
    return f"{kind}-interpret" if interpret else kind


def entry_key(device_kind: str, order: int, n_fields: int = 9) -> str:
    return f"{device_kind}|o{int(order)}|f{int(n_fields)}"


def load_cache(path: Optional[str] = None) -> Dict[str, dict]:
    path = path or default_cache_path()
    try:
        with open(path) as f:
            cache = json.load(f)
    except (FileNotFoundError, json.JSONDecodeError):
        return {}
    return cache if isinstance(cache, dict) else {}


def save_entry(entry: dict, path: Optional[str] = None) -> str:
    """Merge one sweep result into the cache JSON (atomic replace)."""
    path = path or default_cache_path()
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    cache = load_cache(path)
    cache[entry_key(entry["device_kind"], entry["order"], entry["n_fields"])] = entry
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(cache, f, indent=2, sort_keys=True)
    os.replace(tmp, path)
    return path


def lookup(
    device_kind: Optional[str] = None,
    order: Optional[int] = None,
    n_fields: int = 9,
    path: Optional[str] = None,
) -> Optional[dict]:
    """The cached entry for ``(device_kind, order, n_fields)`` — device kind
    auto-detected when omitted; with ``order`` omitted, the entry for the
    current device at any order (closest key wins by insertion order)."""
    cache = load_cache(path)
    if not cache:
        return None
    if device_kind is None:
        device_kind = detect_device_kind(interpret=False)
    if order is not None:
        return cache.get(entry_key(device_kind, order, n_fields))
    for e in cache.values():
        if isinstance(e, dict) and e.get("device_kind") == device_kind:
            return e
    return None


def best_blocks(
    device_kind: Optional[str] = None,
    order: Optional[int] = None,
    n_fields: int = 9,
    path: Optional[str] = None,
) -> Tuple[Optional[int], Optional[int]]:
    """(be, bf) winners from the cache, (None, None) when unmeasured."""
    e = lookup(device_kind, order, n_fields, path)
    if e is None:
        return None, None
    return int(e["be"]), int(e["bf"])


def activate(entry: Optional[dict]) -> None:
    """Install an entry's winning block sizes in the kernel modules (every
    subsequent trace uses them); ``None`` resets both to the defaults."""
    from repro.kernels import dg_flux, dg_volume

    if entry is None:
        dg_volume.set_block_elems(None)
        dg_flux.set_block_faces(None)
    else:
        dg_volume.set_block_elems(int(entry["be"]))
        dg_flux.set_block_faces(int(entry["bf"]))


# ---------------------------------------------------------------------------
# The sweep
# ---------------------------------------------------------------------------


def _median_seconds(fn, reps: int) -> float:
    import jax

    jax.block_until_ready(fn())  # warmup / compile
    ts = []
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def _two_point_fit(t_small: float, n_small: int, t_big: float, n_big: int):
    """t(n) = overhead + n * slope, clamped non-negative."""
    slope = max(0.0, (t_big - t_small) / max(1, n_big - n_small))
    overhead = max(0.0, t_small - slope * n_small)
    return slope, overhead


def sweep_volume(
    order: int,
    n_fields: int = 9,
    dtype: str = "float32",
    candidates: Sequence[int] = DEFAULT_BE_CANDIDATES,
    *,
    interpret: bool,
    reps: int = 3,
    size_factor: int = 8,
    seed: int = 0,
) -> Dict[str, dict]:
    """Per-candidate ``{sec_per_element, overhead_s}`` for ``dg_volume_pallas``."""
    import jax
    import jax.numpy as jnp

    from repro.dg.basis import diff_matrix, lgl_nodes_weights
    from repro.kernels.dg_volume import dg_volume_pallas

    M = order + 1
    x, _ = lgl_nodes_weights(order)
    D = jnp.asarray(diff_matrix(x), dtype)
    rng = np.random.default_rng(seed)
    metrics = (2.0, 2.0, 2.0)
    out: Dict[str, dict] = {}
    for be in candidates:
        be = int(be)
        results = {}
        for K in (be, size_factor * be):
            q = jnp.asarray(rng.standard_normal((K, n_fields, M, M, M)), dtype)
            rho = jnp.ones(K, dtype)
            lam = jnp.ones(K, dtype)
            mu = jnp.zeros(K, dtype)
            fn = jax.jit(
                lambda q, rho, lam, mu, be=be: dg_volume_pallas(
                    q, D, metrics, rho, lam, mu, interpret=interpret, be=be
                )
            )
            results[K] = _median_seconds(lambda: fn(q, rho, lam, mu), reps)
        (n_s, t_s), (n_b, t_b) = sorted(results.items())
        slope, ovh = _two_point_fit(t_s, n_s, t_b, n_b)
        out[str(be)] = {"sec_per_element": slope, "overhead_s": ovh,
                        "timed": {str(k): v for k, v in results.items()}}
    return out


def sweep_flux(
    order: int,
    dtype: str = "float32",
    candidates: Sequence[int] = DEFAULT_BF_CANDIDATES,
    *,
    interpret: bool,
    reps: int = 3,
    size_factor: int = 8,
    seed: int = 0,
) -> Dict[str, dict]:
    """Per-candidate ``{sec_per_face, overhead_s}`` for ``dg_flux_pallas``
    (one call covers six faces per element row)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.dg_flux import dg_flux_pallas

    M = order + 1
    rng = np.random.default_rng(seed)
    out: Dict[str, dict] = {}
    for bf in candidates:
        bf = int(bf)
        results = {}
        for R in (bf, size_factor * bf):
            tm = jnp.asarray(rng.standard_normal((6, 6, M * M, R)), dtype)
            tp = jnp.asarray(rng.standard_normal((6, 6, M * M, R)), dtype)
            mat = np.abs(rng.standard_normal((6, 10, R))) + 0.5  # HAS, KEEP > 0
            mat = jnp.asarray(mat, dtype)
            fn = jax.jit(
                lambda tm, tp, mat, bf=bf: dg_flux_pallas(
                    tm, tp, mat, (1.0, 1.0, 1.0), interpret=interpret, bf=bf
                )
            )
            results[FACES_PER_ELEMENT * R] = _median_seconds(lambda: fn(tm, tp, mat), reps)
        (n_s, t_s), (n_b, t_b) = sorted(results.items())
        slope, ovh = _two_point_fit(t_s, n_s, t_b, n_b)
        out[str(bf)] = {"sec_per_face": slope, "overhead_s": ovh,
                        "timed": {str(k): v for k, v in results.items()}}
    return out


def _winner(sweep: Dict[str, dict], cost_key: str) -> str:
    """Min marginal cost; per-launch overhead breaks ties."""
    return min(sweep, key=lambda k: (sweep[k][cost_key], sweep[k]["overhead_s"]))


def autotune(
    order: int,
    n_fields: int = 9,
    dtype: str = "float32",
    device_kind: Optional[str] = None,
    be_candidates: Sequence[int] = DEFAULT_BE_CANDIDATES,
    bf_candidates: Sequence[int] = DEFAULT_BF_CANDIDATES,
    *,
    interpret: bool,
    reps: int = 3,
    size_factor: int = 8,
    cache_path: Optional[str] = None,
    save: bool = True,
) -> dict:
    """Run both sweeps, pick winners, and (by default) merge the entry into
    the cache JSON.  Returns the entry.

    ``sec_per_element`` in the entry is per element per *timestep* (the
    marginal per-evaluation cost times the 5 LSRK stages; int_flux times the
    6 faces our surface pass computes per element) — directly consumable by
    ``CalibrationTable.from_autotune``."""
    if device_kind is None:
        device_kind = detect_device_kind(interpret)
    vol = sweep_volume(order, n_fields, dtype, be_candidates,
                       interpret=interpret, reps=reps, size_factor=size_factor)
    flx = sweep_flux(order, dtype, bf_candidates,
                     interpret=interpret, reps=reps, size_factor=size_factor)
    be = _winner(vol, "sec_per_element")
    bf = _winner(flx, "sec_per_face")
    entry = {
        "device_kind": device_kind,
        "order": int(order),
        "n_fields": int(n_fields),
        "dtype": dtype,
        "interpret": bool(interpret),
        "be": int(be),
        "bf": int(bf),
        "volume_sweep": vol,
        "flux_sweep": flx,
        "sec_per_element": {
            "volume_loop": vol[be]["sec_per_element"] * N_STAGES,
            "int_flux": flx[bf]["sec_per_face"] * FACES_PER_ELEMENT * N_STAGES,
        },
        # the measured per-launch intercept: what a fused step pays ONCE per
        # kernel now that the envelope layout is one launch per kernel
        "launch_overhead_s": 0.5 * (vol[be]["overhead_s"] + flx[bf]["overhead_s"]),
    }
    if save:
        save_entry(entry, cache_path)
    return entry


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _int_list(s: str) -> Tuple[int, ...]:
    return tuple(int(x) for x in s.split(",") if x.strip())


def main(argv: Optional[Sequence[str]] = None) -> None:
    ap = argparse.ArgumentParser(
        description="Sweep Pallas DG kernel block sizes and cache the winners."
    )
    ap.add_argument("--device-class", default=None,
                    help="cache label override (default: auto-detected, e.g. "
                         "'cpu-interpret', 'tpu-v4')")
    ap.add_argument("--order", type=int, default=3)
    ap.add_argument("--n-fields", type=int, default=9)
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--be", type=_int_list, default=None,
                    help="comma-separated BE candidates (volume kernel)")
    ap.add_argument("--bf", type=_int_list, default=None,
                    help="comma-separated BF candidates (flux kernel)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--cache", default=None,
                    help=f"cache JSON path (default: {default_cache_path()})")
    ap.add_argument("--interpret", action="store_true",
                    help="run the kernels in the Pallas interpreter (the CPU "
                         "path); by default they are compiled with Mosaic")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sweep (2 candidates, 1 rep, small sizes) — CI-safe")
    args = ap.parse_args(argv)

    be_cands = args.be or (DEFAULT_BE_CANDIDATES[:2] if args.smoke else DEFAULT_BE_CANDIDATES)
    bf_cands = args.bf or (DEFAULT_BF_CANDIDATES[:2] if args.smoke else DEFAULT_BF_CANDIDATES)
    entry = autotune(
        order=args.order,
        n_fields=args.n_fields,
        dtype=args.dtype,
        device_kind=args.device_class,
        be_candidates=be_cands,
        bf_candidates=bf_cands,
        interpret=args.interpret,
        reps=1 if args.smoke else args.reps,
        size_factor=4 if args.smoke else 8,
        cache_path=args.cache,
    )
    path = args.cache or default_cache_path()
    sec = entry["sec_per_element"]
    print(f"device_kind={entry['device_kind']} order={entry['order']} "
          f"n_fields={entry['n_fields']} dtype={entry['dtype']}")
    print(f"winners: BE={entry['be']} BF={entry['bf']}")
    print(f"volume_loop={sec['volume_loop']:.3e} s/elem/step  "
          f"int_flux={sec['int_flux']:.3e} s/elem/step  "
          f"launch_overhead={entry['launch_overhead_s']:.3e} s")
    print(f"cache: {path}")


if __name__ == "__main__":
    main()
