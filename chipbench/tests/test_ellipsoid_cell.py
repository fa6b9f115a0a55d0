"""The shell-and-fibers cell's toy through the whole command
(`run.run_cell`, the look for a chip lifted as `test_harness.py` lifts it):
the sound run is `correct`, traced and untraced, with the cell's four
per-layer readers loaded; with `System.step` returning its state unchanged,
and with one source block of the fiber -> shell sum zeroed under the run
(`scripts/ellipsoid_pair_control.py`), `correct` comes out false. The
toy's limits are `tests/test_ellipsoid_reference.py`'s, with their
reasons there."""

import argparse
import importlib.util
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "ellipsoid_toy.run"
NEW_METRICS = ("shell_flow_device_s", "shell_operator_device_s",
               "stresslet_tile_roofline", "shell_step_mfu")
LIMITS = {"ref_residual": 1e-8, "ref_residual_shell": 2e-2,
          "ref_residual_fiber_bc": 5e-8}


def _pair_control():
    spec = importlib.util.spec_from_file_location(
        "ellipsoid_pair_control",
        os.path.join(ROOT, "scripts", "ellipsoid_pair_control.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture()
def ellipsoid_root(toy_root):
    """`toy_root` with the ellipsoid toy as a third configuration and cell:
    the toy's file with the reference's name and the toy's limits."""
    root, bench = toy_root
    bdir = os.path.join(root, "chipbench")
    with open(os.path.join(HERE, "toy", "ellipsoid_toy.json")) as fh:
        cfg = json.load(fh)
    cfg.update(reference="clamped_shell_step", limits=LIMITS, reduced=[])
    with open(os.path.join(bdir, "configs", "ellipsoid_toy.json"), "w") as fh:
        json.dump(cfg, fh)
    bench["configs"].append({"name": "ellipsoid_toy", "source": "toy",
                             "file": "chipbench/configs/ellipsoid_toy.json",
                             "reduced": [], "why": "toy"})
    bench["workloads"].append({"name": CELL, "config": "ellipsoid_toy",
                               "traffic": "run", "chips": 1, "why": "toy"})
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            m["workloads"] = [CELL]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return root


def _args(trace=0):
    return argparse.Namespace(workload=CELL, seed=2**31 + 34, seconds=1.0,
                              trace=trace)


@pytest.mark.parametrize("trace", [0, 1])
def test_sound_run_is_correct(cpu_as_chip, ellipsoid_root, trace):
    res = cpu_as_chip.run_cell(_args(trace), root=ellipsoid_root)
    json.dumps(res)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert res["run"]["compiles_in_window"] == 0
    assert set(LIMITS) <= set(res["checks"])
    assert all(res["checks"][k]["limit"] == v for k, v in LIMITS.items())
    if trace:
        # nothing here is a device number: the readers are found, run and
        # do not raise off the chip, and those that need a device plane
        # leave their metric out
        assert set(res["metrics"]) - set(NEW_METRICS) >= {
            "step_p50_s", "compiles_in_window", "gmres_iters_per_step"}
        assert "stresslet_tile" in res["run"]["probes"]


def _state_unchanged(monkeypatch):
    from test_harness import _break_unchanged    # the same fault, one place

    _break_unchanged(monkeypatch)


def _fiber_to_shell_block_zeroed(monkeypatch):
    _pair_control().plant("fiber_to_shell_block", monkeypatch.setattr)


@pytest.mark.parametrize("fault,numbers", [
    (_state_unchanged, ("ref_residual", "ref_residual_shell",
                        "ref_residual_fiber_bc")),
    (_fiber_to_shell_block_zeroed, ("ref_residual", "ref_residual_shell"))],
    ids=["state_unchanged", "fiber_to_shell_block_zeroed"])
def test_broken_run_is_not_correct(cpu_as_chip, ellipsoid_root, monkeypatch,
                                   fault, numbers):
    fault(monkeypatch)
    res = cpu_as_chip.run_cell(_args(), root=ellipsoid_root)
    assert res["correct"] is False
    assert res["failed"] == 0         # the program itself reports nothing
    for name in numbers:
        c = res["checks"][name]
        assert c["value"] > c["limit"], (name, c)
