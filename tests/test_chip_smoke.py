"""``chip_smoke.py`` on the CPU: its phases at a tiny size with the Pallas
kernels in the interpreter, and its refusal to run without a TPU.

These check the script's control flow and checks; only
``python chip_smoke.py`` on a TPU exercises the chip.
"""

import os
import subprocess
import sys

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_one_chip_phase_tiny_interpret(capsys):
    with jax.enable_x64(False):
        chip_smoke.one_chip(chip_smoke.CompileLog(), kernel_impl="interpret",
                            grid=(16, 8, 4), extent=(1.0, 0.5, 0.25), order=2)
    out = capsys.readouterr().out
    assert "check ok: observe: calibrate -> solve -> resplice moved work off the straggler" in out
    assert "check ok: ledger: one volume and one surface launch per rhs" in out
    assert "check ok: fused: final field within" in out


def test_four_chip_phase_tiny_interpret(subproc):
    out = subproc(f"""
import sys
sys.path.insert(0, {REPO!r})
import chip_smoke
chip_smoke.four_chips(chip_smoke.CompileLog(), kernel_impl="interpret",
                      grid=(16, 4, 4), extent=(1.0, 0.25, 0.25))
""")
    assert "check ok: sharded: the state spans 4 distinct devices" in out
    assert "check ok: sharded: final field within" in out


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
