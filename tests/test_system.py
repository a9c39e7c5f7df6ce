"""End-to-end behaviour tests: train-to-convergence smoke, failure/restart
equivalence, serving, and the dry-run machinery on a small mesh."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.shapes import ShapeSpec, smoke_config
from repro.data import make_batch
from repro.launch.mesh import debug_mesh
from repro.models.zoo import LM, get_config
from repro.optim import OptConfig, init_opt_state
from repro.parallel.steps import accum_layout, make_serve_step, make_shardings, make_train_step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _train(arch: str, steps: int = 8, fail_at=None, tmp=None):
    cfg = smoke_config(get_config(arch))
    shape = ShapeSpec("s", seq_len=64, global_batch=4, kind="train")
    mesh = debug_mesh()
    lm = LM(cfg, ep_size=2 if cfg.n_experts else 1)
    sh = make_shardings(lm, mesh, kind="train", accum=True, batch_shardable=False)
    step_fn = make_train_step(lm, OptConfig(peak_lr=1e-3, warmup_steps=2, total_steps=steps), sh)
    jitted = jax.jit(step_fn, donate_argnums=(0, 1))
    params = lm.init(jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    losses = []
    for s in range(steps):
        batch = make_batch(cfg, shape, s, accum=2, micro=2)
        params, opt, m = jitted(params, opt, batch)
        losses.append(float(m["loss"]))
    return losses, params


@pytest.mark.parametrize("arch", ["qwen2-7b", "olmoe-1b-7b", "falcon-mamba-7b", "hubert-xlarge"])
def test_training_reduces_loss(arch):
    losses, _ = _train(arch, steps=8)
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0], losses


def test_failure_restart_replays_identically(tmp_path):
    """A killed-and-resumed run must produce the same final loss as an
    uninterrupted one (deterministic pipeline + checkpoint restore)."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    base = [sys.executable, "-m", "repro.launch.train", "--arch", "qwen2-7b",
            "--smoke", "--steps", "14", "--batch", "4", "--seq-len", "64",
            "--ckpt-every", "4"]
    r1 = subprocess.run(base + ["--metrics-out", str(tmp_path / "a.jsonl")],
                        env=env, capture_output=True, text=True, timeout=600)
    assert r1.returncode == 0, r1.stdout + r1.stderr
    r2 = subprocess.run(
        base + ["--metrics-out", str(tmp_path / "b.jsonl"),
                "--ckpt-dir", str(tmp_path / "ck"), "--fail-at", "9"],
        env=env, capture_output=True, text=True, timeout=600)
    assert r2.returncode == 0, r2.stdout + r2.stderr
    import json
    a = {json.loads(l)["step"]: json.loads(l)["loss"] for l in open(tmp_path / "a.jsonl")}
    b = {json.loads(l)["step"]: json.loads(l)["loss"] for l in open(tmp_path / "b.jsonl")}
    last = max(a)
    assert abs(a[last] - b[last]) < 1e-4, (a[last], b[last])


def test_serving_greedy_decode():
    cfg = smoke_config(get_config("qwen2-7b"))
    lm = LM(cfg)
    mesh = debug_mesh()
    params = lm.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    sh = make_shardings(lm, mesh, kind="decode", batch_shardable=False)
    serve = jax.jit(make_serve_step(lm, sh), donate_argnums=(1,))
    logits, cache = lm.prefill(params, {"tokens": toks}, max_len=32)
    tok = jnp.argmax(jnp.where(jnp.arange(logits.shape[-1]) < cfg.vocab_size, logits, -jnp.inf), -1).astype(jnp.int32)
    outs = [tok]
    for _ in range(7):
        tok, cache = serve(params, cache, tok)
        outs.append(tok)
    gen = np.stack(outs, 1)
    assert gen.shape == (2, 8)
    assert (gen >= 0).all() and (gen < cfg.vocab_size).all()


def test_accum_layout():
    assert accum_layout(256, 16) == (16, 16)
    assert accum_layout(256, 32) == (8, 32)
    assert accum_layout(1, 16) == (1, 1)
    a, m = accum_layout(30, 4)
    assert a * m == 30


def test_dryrun_machinery_small_mesh(subproc):
    """The dry-run path (lower+compile+analysis) on an 8-device mesh with a
    smoke config — exercises the exact code the 512-device run uses."""
    subproc(
        """
import jax, jax.numpy as jnp
from repro.configs.shapes import ShapeSpec, smoke_config
from repro.models.zoo import LM, get_config
from repro.optim import OptConfig, init_opt_state
from repro.parallel.steps import accum_layout, make_shardings, make_train_step
from repro.launch.specs import train_input_specs
from repro.launch.hlo_analysis import analyze
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
cfg = smoke_config(get_config("qwen2-7b")).replace(tp_size=2, dtype="bfloat16")
lm = LM(cfg)
shape = ShapeSpec("t", seq_len=64, global_batch=8, kind="train")
accum, micro = accum_layout(8, 4)
sh = make_shardings(lm, mesh, kind="train", accum=True)
batch = train_input_specs(cfg, shape, accum, micro)
params = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
opt = jax.eval_shape(init_opt_state, params)
step = make_train_step(lm, OptConfig(), sh)
jitted = jax.jit(step, in_shardings=(sh.params, sh.opt, sh.batch), out_shardings=(sh.params, sh.opt, None), donate_argnums=(0,1))
compiled = jitted.lower(params, opt, batch).compile()
r = analyze(compiled.as_text())
assert r["flops"] > 0 and r["collective_bytes_total"] > 0, r
print("OK flops=%.3g coll=%.3g" % (r["flops"], r["collective_bytes_total"]))
""",
        n_devices=8,
    )


# ---------------------------------------------------------------------------
# Determinism of the fused launch drivers (serve --fused-decode,
# train --fused-steps) — the donated-carry paths, end to end
# ---------------------------------------------------------------------------


def _run_cli(args, timeout=600):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-m"] + args, env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout


def test_serve_fused_decode_deterministic(tmp_path):
    """Two serve runs from the same seed emit identical token matrices, and
    the fused scan-compiled decode agrees with the per-token Python loop."""
    base = ["repro.launch.serve", "--arch", "qwen2-7b", "--smoke",
            "--batch", "2", "--prompt-len", "16", "--gen", "8", "--seed", "3"]
    _run_cli(base + ["--out", str(tmp_path / "a.npy")])
    _run_cli(base + ["--out", str(tmp_path / "b.npy")])
    _run_cli(base + ["--no-fused-decode", "--out", str(tmp_path / "c.npy")])
    a = np.load(tmp_path / "a.npy")
    b = np.load(tmp_path / "b.npy")
    c = np.load(tmp_path / "c.npy")
    assert (a == b).all(), "same-seed fused decode runs diverged"
    assert (a == c).all(), "fused decode != per-token loop"
    assert a.shape == (2, 8)


def test_train_fused_steps_deterministic(tmp_path):
    """Two --fused-steps runs from the same seed produce identical metrics,
    and the fused chunk driver lands on the same final loss as the per-step
    driver (PR4's identical-final-loss claim, pinned end to end)."""
    import json

    base = ["repro.launch.train", "--arch", "qwen2-7b", "--smoke",
            "--steps", "8", "--batch", "4", "--seq-len", "64", "--seed", "1"]
    o1 = _run_cli(base + ["--fused-steps", "4",
                          "--metrics-out", str(tmp_path / "a.jsonl")])
    o2 = _run_cli(base + ["--fused-steps", "4",
                          "--metrics-out", str(tmp_path / "b.jsonl")])
    o3 = _run_cli(base + ["--metrics-out", str(tmp_path / "c.jsonl")])

    def final_loss(out):
        lines = [l for l in out.splitlines() if l.startswith("final_loss=")]
        assert len(lines) == 1, out
        return float(lines[0].split("=", 1)[1])

    def records(path):
        # drop the wall-clock field: everything else must match bitwise
        out = []
        for line in open(path):
            r = json.loads(line)
            r.pop("sec")
            out.append(r)
        return out

    assert records(tmp_path / "a.jsonl") == records(tmp_path / "b.jsonl"), \
        "same-seed fused-steps runs diverged"
    assert final_loss(o1) == final_loss(o2)
    # fused chunks vs per-step driver: same optimizer trajectory
    a_last = records(tmp_path / "a.jsonl")[-1]
    c_last = records(tmp_path / "c.jsonl")[-1]
    assert a_last["step"] == c_last["step"] == 7
    assert a_last["loss"] == c_last["loss"], (a_last, c_last)


# ---------------------------------------------------------------------------
# Monotonic-clock regressions (launch-side twins of the serving
# test_decode_batch_uses_monotonic_clock)
# ---------------------------------------------------------------------------


def test_train_wall_survives_backwards_clock(subproc):
    """Regression: launch/train.py's wall duration must come from
    perf_counter — under a wall clock stepping BACKWARD (NTP adjustment)
    the reported wall seconds stay non-negative."""
    subproc(
        """
import contextlib, io, itertools, re, sys, time
ticks = itertools.count()
time.time = lambda: 1e9 - 10.0 * next(ticks)  # strictly decreasing
sys.argv = ["train", "--arch", "qwen2-7b", "--smoke",
            "--steps", "2", "--batch", "2", "--seq-len", "16"]
from repro.launch.train import main
buf = io.StringIO()
with contextlib.redirect_stdout(buf):
    main()
m = re.search(r"wall=([-0-9.]+)s", buf.getvalue())
assert m, buf.getvalue()
assert float(m.group(1)) >= 0.0, f"negative wall under backwards clock: {m.group(1)}"
print("train wall ok:", m.group(1))
""",
        n_devices=1,
    )


def test_dryrun_durations_survive_backwards_clock(subproc):
    """Regression: launch/dryrun.py's lower_s/compile_s must come from
    perf_counter.  build_cell is stubbed (no 512-device compile); only the
    timed path around lower()/compile() runs, under a backwards clock."""
    subproc(
        """
import itertools, time
import repro.launch.dryrun as dryrun_mod
ticks = itertools.count()
time.time = lambda: 1e9 - 10.0 * next(ticks)  # strictly decreasing

class Compiled:
    def memory_analysis(self): return object()
    def cost_analysis(self): return {"flops": 1.0}
class Lowered:
    def compile(self): return Compiled()
class Jitted:
    def lower(self, *a): return Lowered()

dryrun_mod.build_cell = lambda *a, **k: (Jitted(), (), None, None)
rec = dryrun_mod.run_cell("qwen2-7b", "train_4k", False, full_analysis=False)
assert rec["lower_s"] >= 0.0 and rec["compile_s"] >= 0.0, rec
print("dryrun durations ok:", rec["lower_s"], rec["compile_s"])
""",
        n_devices=1,
    )
