"""The command end to end at toy sizes, with the look for a chip lifted
only here (`cpu_as_chip`), and the rest of a run driven with the timed
path broken underneath: `correct` has to come out false."""

import argparse
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def _args(trace=0, seconds=1.0, seed=2**31 + 21):
    return argparse.Namespace(workload="free_fibers_toy.run", seed=seed,
                              seconds=seconds, trace=trace)


def test_refuses_without_a_chip():
    import run

    with pytest.raises(SystemExit):
        run.require_accelerator(1)


def test_command_without_a_chip_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "free_fibers_256.run", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


def test_command_without_the_program_prints_no_result(tmp_path):
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "free_fibers_256.run", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=tmp_path, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("toy", ["free_fibers_toy", "walkthrough_toy"])
def test_sound_run_end_to_end(cpu_as_chip, toy_root, toy):
    root, bench = toy_root
    args = _args()
    args.workload = toy + ".run"
    res = cpu_as_chip.run_cell(args, root=root)
    line = json.dumps(res)              # it has to serialise
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"    # the numbers compared come last
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert set(res["metrics"]) == {"step_wall_s", "sim_rate", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["run"]["compiles_in_window"] == 0
    assert "ref_residual" in res["checks"] and "limit" in line
    if toy == "walkthrough_toy":
        assert res["run"]["precompute"] == "miss"
        assert {"ref_residual_shell", "ref_residual_body"} <= set(
            res["checks"])


def test_same_seed_same_inputs_other_seeds_mirror_images():
    import numpy as np

    import scene

    cfg = scene.load_json(os.path.join(HERE, "toy", "free_fibers_toy.json"))
    a = scene.build_config(cfg, 2**31 + 5).fibers
    b = scene.build_config(cfg, 2**31 + 5).fibers
    assert [f.x for f in a] == [f.x for f in b]
    images = set()
    for seed in range(40):
        x = np.array([f.x for f in scene.build_config(cfg, seed).fibers])
        x = x.reshape(len(a), -1, 3)
        ref = np.array([f.x for f in a]).reshape(len(a), -1, 3)
        # a mirror image: the same numbers up to the sign of each axis
        assert np.array_equal(np.abs(x), np.abs(ref))
        images.add(tuple(np.sign(x[0, 0] * ref[0, 0]).astype(int)))
    assert len(images) == 8


def test_walkthrough_seed_reaches_the_program_and_moves_no_node():
    import numpy as np

    import scene

    cfg = scene.load_json(os.path.join(BENCH, "configs", "walkthrough.json"))
    a, b = (scene.build_config(cfg, seed) for seed in (3, 2**31 + 4))
    assert a.params.seed != b.params.seed
    xa, xb = (np.array(c.fibers[0].x).reshape(-1, 3) for c in (a, b))
    assert np.array_equal(xa, xb)
    assert np.allclose(xa[0], [0.0, 3.0, 0.0]) and np.allclose(
        xa[-1], [0.0, 3.0, 1.0])      # chip_smoke.run_walkthrough's fiber


def _break_unchanged(monkeypatch):
    """A step that returns its state unchanged (time still advances)."""
    from skellysim_tpu.system import System

    for name in ("step", "_step_donating"):
        orig = getattr(System, name)

        def broken(self, state, _orig=orig):
            new_state, solution, info = _orig(self, state)
            return new_state._replace(fibers=state.fibers, shell=state.shell,
                                      bodies=state.bodies), solution, info

        monkeypatch.setattr(System, name, broken)


def _break_altered(monkeypatch):
    """One coordinate altered where the answer is produced: the advance."""
    from skellysim_tpu.fibers import container as fc

    orig = fc.step

    def broken(group, fiber_sol):
        out = orig(group, fiber_sol)
        return out._replace(x=out.x.at[0, 0, 0].add(1e-6))

    monkeypatch.setattr(fc, "step", broken)


@pytest.mark.parametrize("toy", ["free_fibers_toy", "walkthrough_toy"])
@pytest.mark.parametrize("fault", [_break_unchanged, _break_altered],
                         ids=["state_unchanged", "answer_altered"])
def test_broken_timed_path_is_not_correct(cpu_as_chip, toy_root, monkeypatch,
                                          fault, toy):
    root, _ = toy_root
    fault(monkeypatch)
    args = _args()
    args.workload = toy + ".run"
    res = cpu_as_chip.run_cell(args, root=root)
    assert res["correct"] is False
    c = res["checks"]["ref_residual"]
    assert c["value"] > c["limit"]


def test_a_cell_a_configuration_and_a_metric_added_as_files_only(
        cpu_as_chip, toy_root):
    """A later PR adds entries to BENCHMARK.json and new files, and edits
    no file that is there."""
    root, bench = toy_root
    bdir = os.path.join(root, "chipbench")
    before = {p: os.path.getmtime(os.path.join(dp, p))
              for dp, _, fs in os.walk(bdir) for p in fs}
    cfg = json.load(open(os.path.join(bdir, "configs",
                                      "free_fibers_toy.json")))
    cfg.update(name="free_fibers_other", n_fibers=6)
    json.dump(cfg, open(os.path.join(bdir, "configs",
                                     "free_fibers_other.json"), "w"))
    json.dump({"loop": "closed", "warm_calls": 1, "checked_steps": 2,
               "traced_steps": 2},
              open(os.path.join(bdir, "traffic", "two_checked.json"), "w"))
    with open(os.path.join(bdir, "metrics", "worst_residual.py"), "w") as fh:
        fh.write("def read(run):\n"
                 "    return max(r['residual_true'] for r in run.rows)\n")
    bench["configs"].append({"name": "free_fibers_other", "source": "toy",
                             "file": "chipbench/configs/free_fibers_other"
                                     ".json", "reduced": [], "why": "toy"})
    bench["workloads"].append({"name": "free_fibers_other.two",
                               "config": "free_fibers_other",
                               "traffic": "two_checked", "chips": 1,
                               "why": "toy"})
    bench["per_layer"].append({"name": "worst_residual", "unit": "1",
                               "better": "lower", "source": "program_counter",
                               "layer": "solver", "moves": "step_wall_s",
                               "workloads": ["free_fibers_other.two"]})
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))
    args = _args(trace=1)
    args.workload = "free_fibers_other.two"
    res = cpu_as_chip.run_cell(args, root=root)
    assert res["correct"] is True
    assert res["metrics"]["worst_residual"]["value"] > 0
    assert res["checks"]["steps_checked"]["value"] == 2   # the new mix's
    assert "busy_s" in res["device"] and "window_s" in res["device"]
    after = {p: os.path.getmtime(os.path.join(dp, p))
             for dp, _, fs in os.walk(bdir) for p in fs if p in before}
    assert after == before
