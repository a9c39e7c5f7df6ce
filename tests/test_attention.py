"""Flash attention (lax path) vs the naive oracle across modes and shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import (
    decode_attention,
    flash_attention,
    naive_attention,
    pick_block,
    update_cache,
)


def _rand(key, *shape):
    return jax.random.normal(key, shape, jnp.float32)


@pytest.mark.parametrize("Hq,Hkv", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_naive(Hq, Hkv, causal):
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(0), 3)
    B, S, D = 2, 192, 32
    q = _rand(k0, B, Hq, S, D)
    k = _rand(k1, B, Hkv, S, D)
    v = _rand(k2, B, Hkv, S, D)
    o1 = naive_attention(q, k, v, causal=causal)
    o2 = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    np.testing.assert_allclose(o1, o2, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("window", [16, 48, 200])
def test_swa_matches_naive(window):
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(1), 3)
    B, H, S, D = 1, 2, 256, 16
    q, k, v = _rand(k0, B, H, S, D), _rand(k1, B, H, S, D), _rand(k2, B, H, S, D)
    o1 = naive_attention(q, k, v, causal=True, window=window)
    o2 = flash_attention(q, k, v, causal=True, window=window, block_q=32, block_k=32)
    np.testing.assert_allclose(o1, o2, rtol=2e-4, atol=2e-4)


def test_kv_map_matches_naive():
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(2), 3)
    B, S, D = 2, 128, 16
    q = _rand(k0, B, 5, S, D)
    k = _rand(k1, B, 2, S, D)
    v = _rand(k2, B, 2, S, D)
    kv_map = [0, 0, 0, 1, 1]
    o1 = naive_attention(q, k, v, causal=True, kv_map=kv_map)
    o2 = flash_attention(q, k, v, causal=True, kv_map=kv_map, block_q=32, block_k=32)
    np.testing.assert_allclose(o1, o2, rtol=2e-4, atol=2e-4)


def test_pick_block_divides():
    for n in (48, 100, 4224, 524288):
        for t in (32, 128, 512):
            b = pick_block(n, t)
            assert n % b == 0 and 1 <= b <= t


def test_rolling_cache_decode():
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(3), 3)
    B, H, S, D, W = 2, 2, 96, 16, 32
    q = _rand(k0, B, H, S, D)
    k = _rand(k1, B, H, S, D)
    v = _rand(k2, B, H, S, D)
    kr = jnp.zeros((B, H, W, D))
    vr = jnp.zeros((B, H, W, D))
    for t in range(S):
        kr, vr = update_cache(kr, vr, k[:, :, t : t + 1], v[:, :, t : t + 1], t, rolling=True)
    od = decode_attention(q[:, :, -1:], kr, vr, jnp.int32(S), window=W, rolling=True)
    ow = naive_attention(q, k, v, causal=True, window=W)[:, :, -1:]
    np.testing.assert_allclose(od, ow, rtol=2e-4, atol=2e-4)


def test_dynamic_skip_matches_naive():
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(9), 3)
    B, H, S, D = 2, 4, 256, 32
    q, k, v = _rand(k0, B, H, S, D), _rand(k1, B, 2, S, D), _rand(k2, B, 2, S, D)
    o1 = naive_attention(q, k, v, causal=True)
    o2 = flash_attention(q, k, v, causal=True, block_q=64, block_k=64, dynamic_skip=True)
    np.testing.assert_allclose(o1, o2, rtol=2e-4, atol=2e-4)


def test_ring_attention_matches_naive(subproc):
    """The paper's halo rotation as sequence-parallel attention."""
    subproc(
        """
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.models.attention import naive_attention, ring_attention
from jax import shard_map
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("data",))
ks = jax.random.split(jax.random.PRNGKey(0), 3)
q = jax.random.normal(ks[0], (2, 4, 256, 32), jnp.float32)
k = jax.random.normal(ks[1], (2, 2, 256, 32), jnp.float32)
v = jax.random.normal(ks[2], (2, 2, 256, 32), jnp.float32)
for causal in (True, False):
    f = jax.jit(shard_map(partial(ring_attention, axis_name="data", causal=causal),
        mesh=mesh, in_specs=(P(None, None, "data", None),) * 3,
        out_specs=P(None, None, "data", None), check_vma=False))
    np.testing.assert_allclose(f(q, k, v), naive_attention(q, k, v, causal=causal),
                               rtol=3e-4, atol=3e-4)
print("OK")
""",
        n_devices=4,
    )
