"""The shell-on-a-mesh cell's toy through the whole command (`run.run_cell`,
the look for a chip lifted as `test_harness.py` lifts it) on four CPU
devices: the sound run is `correct`, traced and untraced, with the cell's
four per-layer readers loaded and reading the traced step; with one chip's
quarter of every all-gathered shell density zeroed under the run
(`scripts/mesh_exchange_control.py`), `correct` comes out false by the
reference alone. The toy's limits are `tests/test_ellipsoid_reference.py`'s,
with their reasons there."""

import argparse
import importlib.util
import json
import os
import shutil

# four CPU devices, asked for before JAX starts a backend (no test file
# here starts one while it is imported)
_FLAG = "--xla_force_host_platform_device_count"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" {_FLAG}=4").strip()

import pytest  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TOY = "ellipsoid_mesh_toy"
CELL = "ellipsoid_mesh4.run"
#: `allgather-density` has no reader: on the chip the compiler rewrites the
#: density's gather as a dynamic-update-slice and an all-reduce that carry
#: no scope, so nothing sits under it there (PERF.md section 7 row 1)
NEW_METRICS = ("shell_rows_chip_s", "shell_flow_chip_s",
               "shell_rows_roofline", "shell_mesh_step_mfu")


def _exchange_control():
    spec = importlib.util.spec_from_file_location(
        "mesh_exchange_control",
        os.path.join(ROOT, "scripts", "mesh_exchange_control.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture()
def mesh_shell_root(toy_root):
    """`toy_root` with the toy's configuration and its four-chip cell
    added, the metrics `BENCHMARK.json` lists for the real cell alone
    listed for the toy's alone."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs four (CPU) devices; another test started JAX "
                    "before this file could ask for them")
    root, bench = toy_root
    shutil.copy(os.path.join(HERE, "toy", TOY + ".json"),
                os.path.join(root, "chipbench", "configs", TOY + ".json"))
    bench["configs"].append({"name": TOY, "source": "toy",
                             "file": f"chipbench/configs/{TOY}.json",
                             "reduced": [], "why": "toy"})
    bench["workloads"].append({"name": TOY + ".run", "config": TOY,
                               "traffic": "run", "chips": 4, "why": "toy"})
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        real = json.load(fh)
    mine = {m["name"] for m in real["per_layer"]
            if m.get("workloads") == [CELL]}
    assert mine == set(NEW_METRICS)
    for m in bench["per_layer"]:
        if m["name"] in mine:
            m["workloads"] = [TOY + ".run"]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return root


def _args(trace=0):
    return argparse.Namespace(workload=TOY + ".run", seed=2**31 + 36,
                              seconds=1.0, trace=trace)


def test_sound_traced_run_is_correct_and_the_new_readers_read(
        cpu_as_chip, mesh_shell_root):
    res = cpu_as_chip.run_cell(_args(trace=1), root=mesh_shell_root)
    json.dumps(res)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1 and res["device"]["count"] == 4
    assert res["run"]["compiles_in_window"] == 0
    for name in ("ref_residual", "ref_residual_shell",
                 "ref_residual_fiber_bc"):
        c = res["checks"][name]
        assert c["value"] <= c["limit"], (name, c)
    # nothing here is a device number: the readers are found, run and find
    # the scopes they read in the traced step (a CPU dump has one plane)
    m = res["metrics"]
    for name in NEW_METRICS:
        assert m[name]["value"] > 0, name
    assert m["shell_rows_roofline"]["unit"] == "%"
    phases = res["run"]["probes"]["mesh"]["phases_per_chip"]
    assert phases["gmres"]["shell"] > 0 and phases["gmres"]["shell/pair"] > 0


def test_sound_untraced_run_is_correct(cpu_as_chip, mesh_shell_root):
    res = cpu_as_chip.run_cell(_args(), root=mesh_shell_root)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"step_wall_s", "sim_rate", "setup_s"}
    assert res["run"]["compiles_in_window"] == 0


def test_a_zeroed_quarter_of_the_gathered_density_is_not_correct(
        cpu_as_chip, mesh_shell_root, monkeypatch):
    _exchange_control().plant("gathered_density_quarter",
                              monkeypatch.setattr)
    res = cpu_as_chip.run_cell(_args(), root=mesh_shell_root)
    assert res["correct"] is False
    c = res["checks"]["ref_residual_shell"]
    assert c["value"] > c["limit"]
    # the program itself saw nothing wrong: only the reference does
    assert res["failed"] == 0 and res["checks"]["steps_failed"]["value"] == 0


def test_the_new_readers_read_nothing_without_a_shell_or_a_fold():
    """What the parent, a fiber cell or an untraced run hands them: None,
    never a zero and never an exception."""
    import types

    import run as harness

    bare = harness.Run()
    bare.cell = {"chips": 4}
    bare.peaks = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    for name in NEW_METRICS:
        reader = harness.load_module("metrics", name)
        assert reader.read(bare) is None, name
    # a fold that keeps no planes (a program from before PR 28)
    bare.phase_fold = types.SimpleNamespace(stale=False)
    bare.trace = types.SimpleNamespace(window_s=1.0,
                                       span_seconds=lambda _: [1.0])
    for name in NEW_METRICS:
        assert harness.load_module("metrics", name).read(bare) is None, name
