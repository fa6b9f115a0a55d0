"""`chip_smoke.py` off the chip: it refuses to run, and its phases — the same
functions, called in-process — walk on the CPU at toy sizes.

The walk finds wrong paths (a renamed entry point, a config field, a check
that can no longer pass) before they cost a chip call. It measures nothing:
the sizes are toys and the two size-dependent gates are loosened to match.
Exactly the checks that only a chip can pass are expected to fail here.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: control flow only: a 200-node sphere resolves the drag to ~4e-5 and a
#: 150-node shell slows the body 9% too little
TOY = dict(shell_n=150, body_n=60, fiber_nodes=16, walk_steps=2,
           drag_n=200, drag_gate=1e-2, cavity_gate=0.2,
           n_fibers=8, fiber_steps=2, flow_targets=64,
           mesh_fibers=8, mesh_fused_fibers=4, mesh_shell_n=56,
           mesh_body_n=50, ring_rows=64)

#: off the chip the Pallas tiles run interpreted (no Mosaic kernel in the
#: lowering) and the fused ring runs on the TPU interpreter
CHIP_ONLY_ONE = {"kernel_impl='pallas': the step holds Mosaic kernels"}
CHIP_ONLY_MESH = {"the fused RDMA ring kernel is what executed"}


@pytest.fixture
def smoke(monkeypatch):
    monkeypatch.syspath_prepend(REPO)
    import chip_smoke

    monkeypatch.setattr(chip_smoke, "FAILURES", [])
    return chip_smoke


def test_chip_smoke_refuses_without_a_chip():
    """`chip_smoke.py` measures nothing off the chip: held to the CPU it
    exits non-zero and its last line is not the contract's ok line. (A
    child process, but one pinned to the CPU: it never loads the TPU
    library.)"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    p = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300, env=env,
                       cwd=REPO)
    assert p.returncode != 0
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and "no TPU" in last["reason"]


def test_one_chip_phases_walk_on_the_cpu(smoke, tmp_path):
    with smoke.phase("gate_kernels"):
        smoke.gate_kernels(22)
    with smoke.phase("gate_drag") as info:
        smoke.gate_drag(TOY, info)
    assert len(info["sync_retest"]["host_fetch"]) == 2
    with smoke.phase("walkthrough") as info:
        smoke.run_walkthrough(TOY, str(tmp_path), info)
    # t_final = walk_steps * dt takes exactly walk_steps steps
    assert info["steps"] == TOY["walk_steps"], info
    smoke.run_fibers(TOY, str(tmp_path), 22)
    assert set(smoke.FAILURES) == CHIP_ONLY_ONE


def test_mesh_phases_walk_on_four_cpu_devices(smoke, monkeypatch):
    monkeypatch.setenv("SKELLY_FUSED_RING", "interpret")
    smoke.run_mesh(TOY, 22)
    assert set(smoke.FAILURES) == CHIP_ONLY_MESH
