"""Hierarchy-aware collectives and overlap primitives (multi-device, via
subprocess with fake devices)."""

def test_overlap_primitives(subproc):
    subproc(
        """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from functools import partial
from jax import shard_map
from repro.launch.mesh import make_mesh
from repro.core.overlap import ring_allgather_matmul, matmul_ring_reducescatter, halo_exchange_1d
mesh = make_mesh((4,), ("x",))
key = jax.random.PRNGKey(0)
x = jax.random.normal(key, (16, 8), jnp.float32)
w = jax.random.normal(jax.random.PRNGKey(1), (8, 6), jnp.float32)
f = jax.jit(shard_map(partial(ring_allgather_matmul, axis_name="x"),
    mesh=mesh, in_specs=(P("x", None), P(None, None)), out_specs=P(None, None), check_vma=False))
np.testing.assert_allclose(f(x, w), x @ w, rtol=1e-5)
x2 = jax.random.normal(key, (16, 12), jnp.float32)
w2 = jax.random.normal(jax.random.PRNGKey(2), (12, 6), jnp.float32)
g = jax.jit(shard_map(partial(matmul_ring_reducescatter, axis_name="x"),
    mesh=mesh, in_specs=(P(None, "x"), P("x", None)), out_specs=P("x", None), check_vma=False))
np.testing.assert_allclose(g(x2, w2), x2 @ w2, rtol=1e-4, atol=1e-4)
print("OK")
""",
        n_devices=4,
    )


def test_hierarchical_and_compressed_psum(subproc):
    subproc(
        """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.launch.mesh import make_mesh
from repro.core.collectives import hierarchical_psum, hierarchical_psum_compressed
mesh2 = make_mesh((2, 2), ("s", "f"))
y = jax.random.normal(jax.random.PRNGKey(0), (4, 6, 5), jnp.float32)
h = jax.jit(shard_map(lambda v: hierarchical_psum(v, "f", "s"), mesh=mesh2,
    in_specs=P(("s", "f")), out_specs=P(("s", "f")), check_vma=False))
ref = jax.jit(shard_map(lambda v: jax.lax.psum(v, ("s", "f")), mesh=mesh2,
    in_specs=P(("s", "f")), out_specs=P(("s", "f")), check_vma=False))
np.testing.assert_allclose(h(y), ref(y), rtol=1e-5)
hc = jax.jit(shard_map(lambda v: hierarchical_psum_compressed(v, "f", "s"), mesh=mesh2,
    in_specs=P(("s", "f")), out_specs=P(("s", "f")), check_vma=False))
err = np.abs(np.asarray(hc(y)) - np.asarray(ref(y))).max() / np.abs(np.asarray(ref(y))).max()
assert err < 0.02, err
print("OK")
""",
        n_devices=4,
    )


def test_moe_ep_sharded_matches_gspmd(subproc):
    """The shard_map EP dispatch must equal the single-device reference."""
    subproc(
        """
import jax, jax.numpy as jnp, numpy as np
from repro.models.common import ModelConfig
from repro.models.moe import moe_init, moe_apply, moe_ep_sharded
from repro.launch.mesh import make_mesh
cfg = ModelConfig(arch_id="m", family="moe", n_layers=1, d_model=16, n_heads=2,
                  n_kv_heads=2, d_ff=32, vocab_size=64, n_experts=4,
                  experts_per_token=2, capacity_factor=8.0)
mesh = make_mesh((2, 2), ("data", "model"))
p = moe_init(jax.random.PRNGKey(0), cfg, ep_size=2)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 16))  # (B, S, d)
ref, _ = moe_apply(p, x.reshape(32, 16), cfg, ep_size=2)
out, met = moe_ep_sharded(p, x, cfg, mesh)
np.testing.assert_allclose(np.asarray(out).reshape(32, 16), np.asarray(ref), rtol=1e-4, atol=1e-4)
print("OK drop:", float(met["drop_frac"]))
""",
        n_devices=4,
    )
