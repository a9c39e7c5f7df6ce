"""Elastic rescale end-to-end: train on one mesh, resume on another.

The loss trajectory of (train 4 steps on mesh A, under the fault-tolerant
supervisor with an injected failure forcing a checkpoint restore) +
(resume 4 steps on mesh B) must equal an uninterrupted 8-step run — the
checkpoint reshard, the sharding recomputation, the deterministic
pipeline AND the supervisor's restore+replay must all line up.
"""


def test_elastic_rescale_trajectory(subproc):
    subproc(
        """
import jax, numpy as np, tempfile, os
import jax.numpy as jnp
from repro.checkpoint import restore as ck_restore, save
from repro.configs.shapes import ShapeSpec, smoke_config
from repro.data import make_batch
from repro.models.zoo import LM, get_config
from repro.optim import OptConfig, init_opt_state
from repro.parallel.steps import make_shardings, make_train_step
from repro.runtime import FailureInjector, TrainSupervisor
from repro.runtime.elastic import rescale_plan
from repro.launch.mesh import make_mesh

cfg = smoke_config(get_config("qwen2-7b")).replace(tp_size=2)
lm = LM(cfg)
shape = ShapeSpec("t", 64, 8, "train")
opt_cfg = OptConfig(peak_lr=1e-3, warmup_steps=2, total_steps=8)

def run_steps(mesh, params, opt, start, n):
    sh = make_shardings(lm, mesh, kind="train", accum=True)
    step = jax.jit(make_train_step(lm, opt_cfg, sh),
                   in_shardings=(sh.params, sh.opt, sh.batch),
                   out_shardings=(sh.params, sh.opt, None))
    losses = []
    for s in range(start, start + n):
        params, opt, m = step(params, opt, make_batch(cfg, shape, s, accum=2, micro=4))
        losses.append(float(m["loss"]))
    return params, opt, losses

mesh_a = make_mesh((2, 2), ("data", "model"))
mesh_b = make_mesh((4,), ("data",))

# uninterrupted reference on mesh A
p0 = lm.init(jax.random.PRNGKey(0))
o0 = init_opt_state(p0)
_, _, ref = run_steps(mesh_a, p0, o0, 0, 8)

# elastic: 4 steps on (2,2) THROUGH the fault-tolerant supervisor — an
# injected failure at step 3 forces restore (from the step-2 checkpoint)
# + deterministic replay — then checkpoint and resume on (4,)
p1 = lm.init(jax.random.PRNGKey(0))
o1 = init_opt_state(p1)
sh_a = make_shardings(lm, mesh_a, kind="train", accum=True)
step_a = jax.jit(make_train_step(lm, opt_cfg, sh_a),
                 in_shardings=(sh_a.params, sh_a.opt, sh_a.batch),
                 out_shardings=(sh_a.params, sh_a.opt, None))
ck = tempfile.mkdtemp()
seen = {}

def step_fn(state, step, batch):
    p, o, m = step_a(state[0], state[1], batch)
    return (p, o), m

def restore_fn():
    state, manifest = ck_restore(ck, (p1, o1))
    return manifest["step"], state

sup = TrainSupervisor(
    step_fn,
    lambda step: make_batch(cfg, shape, step, accum=2, micro=4),
    lambda step, state: save(ck, step, state),
    restore_fn,
    ckpt_every=2, max_retries=0,
    injector=FailureInjector({3: "preempt"}),
    on_metrics=lambda s, m, dt, st: seen.__setitem__(s, float(m["loss"])),
)
end, (p1, o1) = sup.run((p1, o1), 0, 4)
assert end == 4 and sup.restarts == 1
first = [seen[s] for s in range(4)]  # replayed steps overwrite bitwise
save(ck, 4, (p1, o1))
p2, o2, step, sh2 = rescale_plan(ck, lm, mesh_b)
assert step == 4
assert len(jax.tree.leaves(p2)[0].sharding.device_set) == 4
_, _, second = run_steps(mesh_b, p2, o2, 4, 4)
got = first + second
np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)
print("elastic (2,2)->(4,) through supervisor restore matches uninterrupted run")

# shrink to a single device
mesh_c = make_mesh((1,), ("data",))
p3, o3, step, _ = rescale_plan(ck, lm, mesh_c)
_, _, second_c = run_steps(mesh_c, p3, o3, 4, 4)
np.testing.assert_allclose(first + second_c, ref, rtol=2e-4, atol=2e-4)
print("elastic shrink to 1 device matches too")
""",
        n_devices=4,
        timeout=900,
    )
