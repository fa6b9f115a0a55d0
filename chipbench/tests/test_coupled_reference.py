"""The coupled reference (fiber + shell + body) against the program at a
test's size, and its control: answers in float32 are not correct."""

import os

import numpy as np
import pytest

import check
import scene

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = scene.load_json(os.path.join(HERE, "toy", "walkthrough_toy.json"))


def test_reference_imports_nothing_of_the_program():
    src = open(os.path.join(os.path.dirname(HERE), "references",
                            "coupled_step.py")).read()
    assert "skellysim" not in src.replace("SkellySim", "")


@pytest.fixture(scope="module")
def stepped(tmp_path_factory):
    import jax

    jax.config.update("jax_enable_x64", True)
    import run
    from skellysim_tpu.builder import build_simulation

    scene.CACHE_DIR = str(tmp_path_factory.mktemp("cache"))
    d = tmp_path_factory.mktemp("scene")
    info = scene.write_scene(TOY, 2**31 + 9, str(d), log=lambda *_: None)
    assert info["precompute"] == "miss"
    again = scene.write_scene(TOY, 5, str(tmp_path_factory.mktemp("s2")),
                              log=lambda *_: None)
    assert again["precompute"] == "hit"     # the seed moves the fiber only
    other = dict(TOY, periphery=dict(TOY["periphery"], radius=6.5))
    assert scene.precompute_key(other) != scene.precompute_key(TOY)
    system, state, _ = build_simulation(info["config_path"])
    snaps, rows = [run.snapshot(state, geometry=True)], []
    for _ in range(2):
        new_state, _, step = system.step(state)
        state = new_state._replace(time=state.time + state.dt)
        snaps.append(run.snapshot(state))
        rows.append({"accepted": True, "health": 0, "dt": float(state.dt),
                     "loss_of_accuracy": False,
                     "residual_true": float(step.residual_true)})
    return snaps, rows, float(system.params.gmres_tol)


def _numbers(snaps, rows, tol, pre=None):
    got = check.check_window(TOY, {"checked_steps": 2}, rows, snaps, {},
                             seed=1, tol=tol, eta=1.0, log=lambda *_: None,
                             pre_snaps=pre)
    return {c["name"]: c for c in got}


def test_sound_run_is_correct_and_reads_as_the_program_does(stepped):
    snaps, rows, tol = stepped
    got = _numbers(snaps, rows, tol)
    assert all(c["ok"] for c in got.values()), got
    ours = got["ref_residual"]["value"]
    theirs = max(r["residual_true"] for r in rows)
    assert abs(ours - theirs) <= 0.05 * theirs


@pytest.mark.parametrize("variant,number", [
    ("f32_answer", "ref_residual"), ("f32_answer", "ref_residual_shell"),
    ("f32_answer", "ref_residual_body"), ("unchanged", "ref_residual"),
    ("altered", "ref_residual")])
def test_control_and_faults_are_not_correct(stepped, variant, number):
    """The control (answers in float32) fails every number; each fault
    fails the whole residual."""
    import controls

    snaps, rows, tol = stepped
    sound = _numbers(snaps, rows, tol)
    bad = controls.map_answers(snaps, controls.VARIANTS[variant])
    got = _numbers(bad, rows, tol, pre=snaps)
    assert not got[number]["ok"], got[number]
    assert got[number]["value"] > 3 * sound[number]["value"]
