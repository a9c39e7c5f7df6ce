"""The main path's Pallas programs compile for a TPU v5e, with no chip attached.

Each test compiles at the ``dg-paper`` size (order 7, K = 8192, float32)
for a described ``v5e:2x2`` topology: the volume kernel, the flux kernel,
and the fused step-loop program of ``FusedStepPipeline`` with
``kernel_impl="pallas"``.  Mosaic refuses here what it would refuse on the
chip (layouts, VMEM), so these guard the kernels at no chip time.  Nothing
runs: a pass says the programs compile and fit, not that they are right or
fast.

The topology is described inside a module fixture (not at import, not in
``conftest.py``): only the worker that runs this file loads the TPU
compiler.
"""

import jax
import jax.numpy as jnp
import pytest

K, ORDER = 8192, 7
M = ORDER + 1
HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def chip_config():
    """Compile as the chip runs: x64 off (the suite turns it on for the
    float64 physics tests).  And keep these compiles out of the persistent
    cache: a described-device entry can never be read back without the
    chip."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with jax.enable_x64(False):
            yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _check(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, mem


def _f32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def test_volume_kernel_compiles(one_chip):
    """With the solver's concrete ``D``, as every engine calls it."""
    from repro.dg.basis import diff_matrix, lgl_nodes_weights
    from repro.kernels.dg_volume import dg_volume_pallas

    D = jnp.asarray(diff_matrix(lgl_nodes_weights(ORDER)[0]), jnp.float32)
    f = jax.jit(lambda q, rho, lam, mu: dg_volume_pallas(
        q, D, (32.0, 32.0, 32.0), rho, lam, mu, interpret=False))
    _check(f.lower(_f32((K, 9, M, M, M), one_chip),
                   *[_f32((K,), one_chip)] * 3).compile())


@pytest.mark.parametrize("axis,sign", [(0, -1.0), (2, 1.0)])
def test_flux_kernel_compiles(one_chip, axis, sign):
    from repro.kernels.dg_flux import dg_flux_pallas

    f = jax.jit(lambda Sm, vm, Sp, vp, mats: dg_flux_pallas(
        Sm, vm, Sp, vp, mats, axis, sign, interpret=False))
    S, v = _f32((K, 6, M, M), one_chip), _f32((K, 3, M, M), one_chip)
    _check(f.lower(S, v, S, v, _f32((K, 8), one_chip)).compile())


def test_fused_pipeline_compiles(one_chip):
    """The program ``FusedStepPipeline.run`` dispatches (a traced step
    count: the same program serves a 2-step or a 118-step run) over a
    4-way nested partition of the paper's brick."""
    from repro.configs.registry import resolve_scenario
    from repro.runtime import BlockedDGEngine, NestedPartitionExecutor

    solver = resolve_scenario("dg-paper").build(dtype="float32", kernel_impl="pallas")
    assert solver.mesh.K == K and solver.order == ORDER
    eng = BlockedDGEngine(
        solver, NestedPartitionExecutor(K, 4, grid_dims=solver.mesh.grid)
    )
    pipe = eng.pipeline()
    sig = pipe.bucket_signature
    place = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    q = _f32((K, 9, M, M, M), one_chip)
    compiled = pipe._run_fn(sig).lower(
        q, q, _f32((), one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        jax.tree.map(place, pipe._tables),
        place(eng.scatter_base(jnp.zeros((1, 9, M, M, M), jnp.float32))),
    ).compile()
    _check(compiled)
