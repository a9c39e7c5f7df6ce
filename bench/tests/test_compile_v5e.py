"""Every program a run of each cell dispatches compiles for a described
v5e:2x2 at the cell's own size, with no chip attached: the nested cell's
fused step loop, the sharded cell's ``shard_map`` step loop over four
chips, the initial field and the reference at both sizes.  A pass says the
programs compile and fit, not that they are right or fast.

The topology is described inside a module fixture, never at import.
"""

import json
import os

import jax
import jax.numpy as jnp
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM_BYTES = 16 * 2**30


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A described-device entry can never be read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _fits(compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes
             + m.temp_size_in_bytes)
    assert total < HBM_BYTES, m
    return compiled.as_text()


def _sds(x, sharding):
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


def test_nested_step_loop_compiles(topo):
    from jax.sharding import SingleDeviceSharding

    from _solver import build_solver
    from repro.runtime import BlockedDGEngine, NestedPartitionExecutor

    cfg = _config("dg-paper")
    one = SingleDeviceSharding(topo.devices[0])
    solver = build_solver(cfg, "pallas")
    eng = BlockedDGEngine(solver, NestedPartitionExecutor(
        solver.mesh.K, 4, grid_dims=solver.mesh.grid))
    pipe = eng.pipeline()
    M, K = solver.M, solver.mesh.K
    q = jax.ShapeDtypeStruct((K, 9, M, M, M), jnp.float32, sharding=one)
    text = _fits(pipe._run_fn(pipe.bucket_signature).lower(
        q, q, jax.ShapeDtypeStruct((), jnp.float32, sharding=one),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one),
        jax.tree.map(lambda x: _sds(x, one), pipe._tables),
        _sds(eng.scatter_base(jnp.zeros((1, 9, M, M, M), jnp.float32)), one),
    ).compile())
    assert "tpu_custom_call" in text


def test_sharded_step_loop_compiles_over_four_chips(topo, monkeypatch):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from _solver import build_solver
    from repro.dg.partitioned import PartitionedDG

    cfg = _config("dg-paper-x4")
    mesh = Mesh(topo.devices[:4], ("data",))
    # the described chips hold no arrays: place shapes where the slabs would go
    monkeypatch.setattr(PartitionedDG, "place", lambda self, x, spec: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=NamedSharding(mesh, spec)))
    solver = build_solver(cfg, "pallas")
    pdg = PartitionedDG(solver=solver, mesh_axes=mesh)
    M, K = solver.M, solver.mesh.K
    rep = NamedSharding(mesh, PartitionSpec())
    q = jax.ShapeDtypeStruct((K, 9, M, M, M), jnp.float32,
                             sharding=NamedSharding(mesh, pdg.spec_q))
    text = _fits(pdg.pipeline()._run_fn().lower(
        q, q, jax.ShapeDtypeStruct((), jnp.float32, sharding=rep),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=rep), *pdg._operands()).compile())
    assert "tpu_custom_call" in text and "collective-permute" in text


@pytest.mark.parametrize("name,chips", [("dg-paper", 1), ("dg-paper-x4", 4)])
def test_field_and_reference_compile(topo, name, chips):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    import inputs
    from reference import dgsem

    prob = dgsem.Problem(_config(name))
    mesh = Mesh(topo.devices[:chips], ("k",))
    sh = NamedSharding(mesh, PartitionSpec(None, None, None, None, "k"))
    q = jax.ShapeDtypeStruct((9, prob.M, prob.M, prob.M, prob.K), jnp.float32, sharding=sh)
    n = jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, PartitionSpec()))
    _fits(dgsem.make_run(prob).lower(q, n).compile())
    traffic = json.load(open(os.path.join(BENCH, "traffic", "nested.json")))
    d = inputs.draw(2**31 + 7, traffic)
    scalars = [jax.ShapeDtypeStruct(jnp.shape(d[k]), jnp.float32, sharding=n.sharding)
               for k in ("centre", "width", "amp", "wave", "phase")]
    _fits(inputs.field_program(prob, sh).lower(*scalars).compile())
