"""Cold-start regression for the driver entry points.

Round 1 shipped a red MULTICHIP artifact (rc=124): the driver imports
``__graft_entry__`` and calls ``dryrun_multichip(n)`` directly, so the
environment setup that lived in the ``__main__`` guard never ran, and JAX
initialized whatever backend the session offered instead of the virtual CPU
mesh.  These tests invoke the entry points in a subprocess with a *clean*
environment (no JAX_PLATFORMS / XLA_FLAGS) to prove the self-bootstrap works
the way the driver will exercise it.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cold_env():
    env = dict(os.environ)
    # Simulate the driver's cold environment: no JAX platform pinning from
    # conftest.
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    return env


@pytest.mark.slow
def test_dryrun_multichip_cold_import():
    """Import-and-call, exactly like the driver does — must self-bootstrap."""
    code = "import __graft_entry__ as g; g.dryrun_multichip(8)"
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=_cold_env(),
        capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, f"stdout={proc.stdout}\nstderr={proc.stderr}"
    assert "dryrun_multichip(8): ok" in proc.stdout


@pytest.mark.slow
def test_entry_compiles_cold():
    """entry() must produce a jittable fn + args without env setup."""
    code = (
        # entry() itself stays platform-agnostic (the driver compile-checks it
        # on the real TPU); pin CPU here the way conftest does: a child of
        # a test run must never take the chip.
        "from skellysim_tpu.utils.bootstrap import force_cpu_devices\n"
        "force_cpu_devices()\n"
        "import jax\n"
        "import __graft_entry__ as g\n"
        "fn, args = g.entry()\n"
        "out = jax.jit(fn)(*args)\n"
        "jax.block_until_ready(out)\n"
        "print('entry: ok')\n")
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=_cold_env(),
        capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, f"stdout={proc.stdout}\nstderr={proc.stderr}"
    assert "entry: ok" in proc.stdout
