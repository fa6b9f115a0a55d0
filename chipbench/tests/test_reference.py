"""The plain reference against the program at a test's size, its control,
and each fault the cell can have — through the harness's own `check.py`.

The reference imports nothing of the program; here the program (CPU, full
float64) is stepped and the reference must read its answers as solved.
"""

import importlib.util
import os

import numpy as np
import pytest

import check
import scene

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = scene.load_json(os.path.join(HERE, "toy", "free_fibers_toy.json"))


def _reference():
    return check.load_reference("free_fiber_step")


def test_reference_imports_nothing_of_the_program():
    src = open(os.path.join(os.path.dirname(HERE), "references",
                            "free_fiber_step.py")).read()
    assert "skellysim" not in src.replace("SkellySim", "")


def test_derivative_matrices_match_the_programs():
    from skellysim_tpu.fibers import matrices

    ref = _reference()
    for n in (16, 64):
        m, r = matrices.get_mats(n), ref.fiber_matrices(n)
        for k in ("D1", "D2", "D3", "D4", "P_X", "P_T"):
            a = getattr(m, k)
            assert np.abs(a - r[k]).max() <= 1e-13 * np.abs(a).max(), (n, k)
        assert np.allclose(m.weights0, r["w0"], rtol=0, atol=1e-16)


def test_exact_weights_differentiate_polynomials():
    ref = _reference()
    n = 16
    alpha = np.linspace(-1, 1, n)
    for order, width in ((1, 5), (2, 6), (3, 7), (4, 8)):
        D = ref.fd_matrix(n, order, width)
        p = alpha ** (width - 1)
        exact = np.prod(np.arange(width - order, width)) \
            * alpha ** (width - 1 - order)
        assert np.allclose(D @ p, exact, rtol=1e-9, atol=1e-7)


@pytest.fixture(scope="module")
def stepped(tmp_path_factory):
    """Three steps of the toy scene by the program, with the states held
    as the harness holds them."""
    import jax

    jax.config.update("jax_enable_x64", True)
    import run
    from skellysim_tpu.builder import build_simulation

    d = tmp_path_factory.mktemp("scene")
    info = scene.write_scene(TOY, 2**31 + 3, str(d), log=lambda *_: None)
    system, state, _ = build_simulation(info["config_path"])
    snaps, rows = [run.snapshot(state)], []
    for _ in range(3):
        new_state, _, step = system.step(state)
        state = new_state._replace(time=state.time + state.dt)
        snaps.append(run.snapshot(state))
        rows.append({"accepted": True, "health": 0, "dt": float(state.dt),
                     "loss_of_accuracy": False,
                     "residual_true": float(step.residual_true)})
    return snaps, rows, float(system.params.gmres_tol)


def _numbers(snaps, rows, tol, pre=None):
    ref = _reference()
    got = check.check_window(TOY, {"checked_steps": 3}, rows, snaps, {},
                             seed=1, tol=tol, eta=1.0, log=lambda *_: None,
                             flow=ref.oseen_flow_other_fibers, pre_snaps=pre)
    return {c["name"]: c for c in got}


def test_sound_run_is_correct(stepped):
    snaps, rows, tol = stepped
    got = _numbers(snaps, rows, tol)
    assert all(c["ok"] for c in got.values()), got
    # the program's own explicit residual and the reference's agree
    assert got["ref_residual"]["value"] < 1e-12


def test_control_and_faults_are_not_correct(stepped):
    """The control (answers in float32, the precision below the float64
    the configuration states) and each fault the cell can have fail
    `ref_residual`; the same readings at the cell's own size on the chip
    are in PERF.md."""
    import controls

    snaps, rows, tol = stepped
    lower = _numbers(snaps, rows, tol)["ref_residual"]["value"]
    for name, fn in controls.VARIANTS.items():
        bad = controls.map_answers(snaps, fn)
        got = _numbers(bad, rows, tol, pre=snaps)
        assert not got["ref_residual"]["ok"], name
        assert got["ref_residual"]["value"] > 3 * max(lower, 1e-14), name


def test_jax_flow_equals_numpy_flow():
    import jax

    jax.config.update("jax_enable_x64", True)
    ref = _reference()
    rng = np.random.default_rng(0)
    r = rng.uniform(-1, 1, (70, 3))
    wf = rng.standard_normal((70, 3))
    fid = np.repeat(np.arange(7), 10)
    a = ref.oseen_flow_other_fibers(r, wf, fid, 1.3)
    b = ref.jax_flow(block=32)(r, wf, fid, 1.3)
    assert np.abs(a - b).max() <= 1e-12 * np.abs(a).max()


def test_a_program_step_that_reports_failure_is_not_correct(stepped):
    snaps, rows, tol = stepped
    bad = [dict(r) for r in rows]
    bad[1]["residual_true"] = 10 * tol
    got = _numbers(snaps, bad, tol)
    assert not got["steps_failed"]["ok"]
