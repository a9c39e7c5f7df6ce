"""The ``dg-paper-skewed.observed`` cell's window program, the priced fused
step loop that each observe chunk dispatches, compiles for a described
v5e at the cell's own size (the converged split padded to a 4 x 2896-row
envelope, 11584 rows per rhs) and fits one chip's 16 GiB, with no chip
attached.

The topology is described inside a module fixture, never at import.
"""

import json
import os

import jax
import jax.numpy as jnp
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM_BYTES = 16 * 2**30


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


def test_priced_window_program_compiles_at_cell_size(topo):
    from jax.sharding import SingleDeviceSharding

    from _solver import build_solver
    from repro.runtime import BlockedDGEngine, NestedPartitionExecutor

    with open(os.path.join(BENCH, "configs", "dg-paper-skewed.json")) as f:
        cfg = json.load(f)
    node = cfg["node"]
    one = SingleDeviceSharding(topo.devices[0])
    solver = build_solver(cfg, "pallas")
    ex = NestedPartitionExecutor(solver.mesh.K, node["partitions"], grid_dims=solver.mesh.grid,
                                 initial_weights=node["initial_weights"], bucket=node["bucket"])
    eng = BlockedDGEngine(solver, ex)
    pipe = eng.pipeline()
    assert ex.counts.tolist() == [1424, 2256, 2256, 2256]
    assert pipe.bucket_signature == ((2896, 2256, 4, 0),)  # 11584 rows per rhs
    M, K, P = solver.M, solver.mesh.K, node["partitions"]
    sds = lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype, sharding=one)
    q = jax.ShapeDtypeStruct((K, 9, M, M, M), jnp.float32, sharding=one)
    vec = jax.ShapeDtypeStruct((P,), jnp.float32, sharding=one)
    compiled = pipe._priced_run_fn(pipe.bucket_signature).lower(
        q, q, vec, jax.ShapeDtypeStruct((), jnp.float32, sharding=one),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one),
        jax.tree.map(sds, pipe._tables),
        sds(eng.scatter_base(jnp.zeros((1, 9, M, M, M), jnp.float32))), vec,
    ).compile()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes
             + m.temp_size_in_bytes)
    print(f"compiled for a described v5e: {total} bytes ({m})")
    assert total < HBM_BYTES, m
    assert "tpu_custom_call" in compiled.as_text()
