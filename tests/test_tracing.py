"""The DG step names its stages and its host dispatch inside the program.

Device side: every op of the compiled run programs carries the stage it
belongs to as a ``dg.*`` component of its HLO ``op_name`` (``jax.named_scope``
metadata only).  Host side: ``jax.profiler.TraceAnnotation`` spans
``dg.dispatch`` (children ``dg.copy_in``, ``dg.enqueue``), ``dg.sync``,
``dg.rebalance`` and ``dg.tables`` land in a profiler trace, nested as the
host work is."""

import glob
import json
import re

import jax
import jax.numpy as jnp

from conftest import run_with_devices
from repro.dg.solver import gaussian_pulse, make_two_tree_solver
from repro.runtime.executor import BlockedDGEngine, NestedPartitionExecutor

NESTED_SCOPES = {"dg.gather", "dg.volume", "dg.flux", "dg.scatter", "dg.lsrk"}
SHARDED_SCOPES = NESTED_SCOPES | {"dg.halo"}
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')


def scopes_in(hlo_text: str) -> set:
    """The ``dg.*`` components of every ``op_name`` (whole path components
    only: ``dg.fluxes`` would not be ``dg.flux``)."""
    return {c for name in _OP_NAME.findall(hlo_text) for c in name.split("/")
            if c.startswith("dg.")}


def host_spans(trace_dir: str) -> list:
    """[(name, start_ns, end_ns)] of the ``dg.*`` host spans, by start."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events if e.name.startswith("dg."))
    return sorted(out, key=lambda s: s[1])


def inside(child, parent) -> bool:
    return parent[1] <= child[1] and child[2] <= parent[2]


def check_dispatches(spans, n_dispatches: int) -> None:
    """One ``dg.dispatch`` per device program, each holding one
    ``dg.copy_in`` and then one ``dg.enqueue``; neither occurs outside."""
    disp = [s for s in spans if s[0] == "dg.dispatch"]
    assert len(disp) == n_dispatches, spans
    for d in disp:
        kids = [s[0] for s in spans if s[0] in ("dg.copy_in", "dg.enqueue") and inside(s, d)]
        assert kids == ["dg.copy_in", "dg.enqueue"], (d, spans)
    for s in spans:
        if s[0] in ("dg.copy_in", "dg.enqueue"):
            assert any(inside(s, d) for d in disp), s


def check_observed_chunks(spans, n_chunks: int) -> None:
    """Each observed chunk: its dispatch, then ``dg.sync`` outside it, then
    ``dg.rebalance``."""
    syncs = [s for s in spans if s[0] == "dg.sync"]
    rebal = [s for s in spans if s[0] == "dg.rebalance"]
    assert len(syncs) == len(rebal) == n_chunks, spans
    disp = [s for s in spans if s[0] == "dg.dispatch"]
    for sync, reb in zip(syncs, rebal):
        assert not any(inside(sync, d) for d in disp)
        assert any(d[2] <= sync[1] for d in disp)
        assert sync[2] <= reb[1]


def _small_engine(rebalance_every=0):
    solver = make_two_tree_solver(grid=(6, 4, 4), order=2, extent=(2.0, 1.0, 1.0))
    ex = NestedPartitionExecutor(96, 3, grid_dims=(6, 4, 4), bucket=8,
                                 rebalance_every=rebalance_every, smoothing=1.0)
    return solver, ex, BlockedDGEngine(solver, ex)


def test_fused_run_program_carries_each_stage_scope():
    solver, _, eng = _small_engine()
    pipe = eng.pipeline()
    q = jnp.zeros((solver.mesh.K, 9, solver.M, solver.M, solver.M))
    text = pipe._run_fn(pipe.bucket_signature).lower(
        q, q, solver.cfl_dt(), 2, pipe._tables, eng.scatter_base(q)).compile().as_text()
    assert scopes_in(text) == NESTED_SCOPES


def test_scope_components_match_whole_path_components():
    text = ('%a = f32[] add(), metadata={op_name="jit(run)/while/body/dg.lsrk/dg.fluxes/x"}\n'
            '%b = f32[] add(), metadata={op_name="jit(run)/dg.lsrk/dg.gather/gather"}')
    assert scopes_in(text) == {"dg.lsrk", "dg.fluxes", "dg.gather"}
    assert "dg.flux" not in scopes_in(text)


def test_fused_host_spans_nest(tmp_path):
    """A plain run (one dispatch) and an observed run of two chunks with a
    straggler, so that each chunk rebalances: the resplice rebuilds the
    engine's tables inside ``dg.rebalance``, and the pipeline's stacked
    tables inside the next ``dg.dispatch``."""
    solver, ex, eng = _small_engine(rebalance_every=2)
    ex.inject_straggler(0, 2.0)
    q0 = gaussian_pulse(solver, center=(0.5, 0.5, 0.5))
    jax.block_until_ready(eng.run(q0, 2))
    jax.block_until_ready(eng.run(q0, 4, observe=True))  # compiles the priced program
    d0, r0 = eng.pipeline().stats.dispatches, ex.round
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(eng.run(q0, 2))
        jax.block_until_ready(eng.run(q0, 4, observe=True))
    finally:
        jax.profiler.stop_trace()
    assert eng.pipeline().stats.dispatches - d0 == 3 and ex.round - r0 == 2
    spans = host_spans(str(tmp_path))
    check_dispatches(spans, 3)
    check_observed_chunks(spans, 2)
    tables = [s for s in spans if s[0] == "dg.tables"]
    rebal = [s for s in spans if s[0] == "dg.rebalance"]
    disp = [s for s in spans if s[0] == "dg.dispatch"]
    assert all(any(inside(t, r) for r in rebal) or any(inside(t, d) for d in disp)
               for t in tables)
    for r in rebal:  # each rebalance re-spliced the engine's tables
        assert any(inside(t, r) for t in tables)
    # the chunk after a resplice rebuilds the pipeline's tables first
    assert any(inside(t, disp[-1]) for t in tables)


_SHARDED = """
import glob, json, re
import jax, jax.numpy as jnp, numpy as np
from jax.profiler import ProfileData
from repro.dg.partitioned import PartitionedDG
from repro.dg.solver import make_two_tree_solver
from repro.launch.mesh import make_mesh

solver = make_two_tree_solver(grid=(8, 2, 2), order=2, extent=(4.0, 1.0, 1.0))
pdg = PartitionedDG(solver=solver, mesh_axes=make_mesh((4,), ("data",)))
pipe = pdg.pipeline()
rng = np.random.default_rng(0)
qp = pdg.permute_in(rng.standard_normal((solver.mesh.K, 9, solver.M, solver.M, solver.M)))
dt = solver.cfl_dt()
text = pipe._run_fn().lower(qp, qp, jnp.asarray(dt, qp.dtype), jnp.asarray(2, jnp.int32),
                            *pdg._operands()).compile().as_text()
op_names = re.findall(r'op_name="((?:[^"\\\\]|\\\\.)*)"', text)
pdg.bind_executor(pdg.make_executor(rebalance_every=2))
jax.block_until_ready(pdg.run(qp, 2, dt=dt))
jax.block_until_ready(pdg.run(qp, 2, dt=dt, observe=True))
d0 = pipe.stats.dispatches
jax.profiler.start_trace("{trace_dir}")
jax.block_until_ready(pdg.run(qp, 2, dt=dt))
jax.block_until_ready(pdg.run(qp, 4, dt=dt, observe=True))
jax.profiler.stop_trace()
path = sorted(glob.glob("{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
         for pl in ProfileData.from_file(path).planes if pl.name.startswith("/host:")
         for ln in pl.lines for e in ln.events if e.name.startswith("dg.")]
print(json.dumps({{"op_names": op_names, "spans": sorted(spans, key=lambda s: s[1]),
                  "dispatches": pipe.stats.dispatches - d0}}))
"""


def test_sharded_program_scopes_and_host_spans(tmp_path):
    """The sharded run program over four virtual devices carries every
    stage scope, the halo's among them; its plain and observed runs nest
    their host spans as the fused pipeline's do."""
    out = json.loads(run_with_devices(_SHARDED.format(trace_dir=tmp_path)).strip()
                     .splitlines()[-1])
    text = "\n".join(f'metadata={{op_name="{n}"}}' for n in out["op_names"])
    assert scopes_in(text) == SHARDED_SCOPES
    spans = [tuple(s) for s in out["spans"]]
    assert out["dispatches"] == 3
    check_dispatches(spans, 3)
    check_observed_chunks(spans, 2)

