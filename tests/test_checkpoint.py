"""Checkpointing: atomic roundtrip, retention, async, elastic resharding."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, latest_step, restore, save


def _tree(key):
    k1, k2 = jax.random.split(key)
    return {
        "a": jax.random.normal(k1, (8, 16)),
        "nested": {"b": jax.random.normal(k2, (4,)), "step": jnp.int32(3)},
    }


def test_roundtrip(tmp_path):
    t = _tree(jax.random.PRNGKey(0))
    save(str(tmp_path), 7, t)
    assert latest_step(str(tmp_path)) == 7
    t2, manifest = restore(str(tmp_path), t)
    assert manifest["step"] == 7
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(t2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_incomplete_checkpoint_ignored(tmp_path):
    t = _tree(jax.random.PRNGKey(0))
    save(str(tmp_path), 5, t)
    # fake a partial (crashed) save: directory without the commit marker
    os.makedirs(tmp_path / "step_00000009")
    assert latest_step(str(tmp_path)) == 5


def test_manager_retention_and_async(tmp_path):
    m = CheckpointManager(str(tmp_path), keep=2, async_save=True)
    t = _tree(jax.random.PRNGKey(1))
    for s in (10, 20, 30, 40):
        m.save(s, t)
    m.wait()
    steps = sorted(int(n.split("_")[1]) for n in os.listdir(tmp_path) if n.startswith("step_"))
    assert steps == [30, 40]
    (t2, manifest) = m.restore_latest(t)
    assert manifest["step"] == 40


def test_elastic_reshard(tmp_path, subproc):
    """Save sharded on a (2,2) mesh, restore onto a (4,) mesh and 1 device."""
    subproc(
        f"""
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import save, restore
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2), ("data", "model"))
x = jnp.arange(64.0).reshape(8, 8)
xs = jax.device_put(x, NamedSharding(mesh, P("data", "model")))
save(r"{tmp_path}", 1, {{"w": xs}})
# restore to a different mesh
mesh2 = make_mesh((4,), ("data",))
sh2 = {{"w": NamedSharding(mesh2, P("data", None))}}
t2, _ = restore(r"{tmp_path}", {{"w": x}}, shardings=sh2)
np.testing.assert_array_equal(np.asarray(t2["w"]), np.asarray(x))
assert len(t2["w"].sharding.device_set) == 4
# restore fully replicated (single logical device view)
t3, _ = restore(r"{tmp_path}", {{"w": x}})
np.testing.assert_array_equal(np.asarray(t3["w"]), np.asarray(x))
print("OK")
""",
        n_devices=4,
    )


def test_same_state_saves_yield_identical_comparable_manifests(tmp_path):
    """Regression: the save wall timestamp used to be baked into the
    manifest, so two bitwise-identical checkpoints compared unequal at the
    manifest level.  The timestamp is provenance only (injectable, excluded
    from comparable_manifest) — same state must compare identical."""
    import json
    import time

    from repro.checkpoint import comparable_manifest

    t = _tree(jax.random.PRNGKey(2))
    save(str(tmp_path / "a"), 7, t, extra_meta={"seed": 0})
    time.sleep(0.01)  # distinct wall timestamps
    save(str(tmp_path / "b"), 7, t, extra_meta={"seed": 0})
    manifests = []
    for d in ("a", "b"):
        with open(tmp_path / d / "step_00000007" / "manifest.json") as f:
            manifests.append(json.load(f))
    ma, mb = manifests
    assert ma["time"] != mb["time"]  # provenance still recorded, and distinct
    assert ma != mb  # raw manifests differ only by it...
    assert comparable_manifest(ma) == comparable_manifest(mb)  # ...replay-comparable
    assert "time" not in comparable_manifest(ma)
    assert comparable_manifest(ma)["leaves"] and comparable_manifest(ma)["step"] == 7

    # injectable timestamp: replay tooling can pin it for bitwise manifests
    save(str(tmp_path / "c"), 7, t, extra_meta={"seed": 0}, timestamp=123.5)
    with open(tmp_path / "c" / "step_00000007" / "manifest.json") as f:
        assert json.load(f)["time"] == 123.5
