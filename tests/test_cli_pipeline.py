"""End-to-end pipeline: gen_config -> precompute -> run -> read trajectory.

Mirrors the reference's 4-stage combined tests
(`/root/reference/tests/combined/`, `src/skelly_sim/testing.py:18-33`), driven
in-process through the builder/CLI instead of a subprocess binary.
"""

import os

import numpy as np
import pytest

from skellysim_tpu import builder, cli, precompute
from skellysim_tpu.config import (Body, Config, ConfigSpherical, Fiber, Point,
                                  BackgroundSource)
from skellysim_tpu.io.trajectory import TrajectoryReader


def _free_fiber_config(tmp_path, n_nodes=16):
    cfg = Config()
    cfg.params.eta = 1.0
    cfg.params.dt_initial = 0.005
    cfg.params.dt_write = 0.005
    cfg.params.t_final = 0.02
    cfg.params.gmres_tol = 1e-10
    cfg.params.adaptive_timestep_flag = False
    fib = Fiber(n_nodes=n_nodes, length=1.0, bending_rigidity=0.01)
    fib.fill_node_positions(np.zeros(3), np.array([0.0, 0.0, 1.0]))
    cfg.fibers = [fib]
    cfg.background = BackgroundSource(uniform=[1.0, 0.0, 0.0])
    path = str(tmp_path / "skelly_config.toml")
    cfg.save(path)
    return path


@pytest.mark.slow
def test_cli_subprocess_enables_x64(tmp_path):
    """`python -m skellysim_tpu` must converge a 1e-10 mixed solve: without
    the CLI's x64 enable the builder's "f64" state silently canonicalizes to
    f32 and the residual floors at ~1e-5 while steps are still accepted
    (found by round-5 verify — the same class as the precompute CLI bug)."""
    import subprocess
    import sys

    cfg = Config()
    cfg.params.eta = 1.0
    cfg.params.dt_initial = 0.005
    cfg.params.dt_write = 0.005
    cfg.params.t_final = 0.02
    cfg.params.gmres_tol = 1e-10
    # mixed precision exercises the refinement ladder the bug starved
    cfg.params.solver_precision = "mixed"
    cfg.params.adaptive_timestep_flag = False
    fib = Fiber(n_nodes=16, length=1.0, bending_rigidity=0.01)
    fib.fill_node_positions(np.zeros(3), np.array([0.0, 0.0, 1.0]))
    cfg.fibers = [fib]
    cfg.background = BackgroundSource(uniform=[1.0, 0.0, 0.0])
    cfg_path = str(tmp_path / "skelly_config.toml")
    cfg.save(cfg_path)

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # subprocess skips conftest's CPU pin
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # PYTHONPATH = repo ONLY: the child imports this checkout and inherits
    # no site hooks from the session
    env["PYTHONPATH"] = repo
    p = subprocess.run([sys.executable, "-m", "skellysim_tpu",
                       f"--config-file={cfg_path}", "--overwrite"],
                      capture_output=True, text=True, timeout=420, env=env,
                      cwd=str(tmp_path))
    assert p.returncode == 0, p.stderr[-2000:]
    steps = [ln for ln in p.stderr.splitlines() if "step t=" in ln]
    assert steps, p.stderr[-1000:]
    for ln in steps:
        residual = float(ln.split("residual=")[1].split(" ")[0])
        assert residual <= 1e-10, ln
    assert "did not converge" not in p.stderr


def test_cli_metrics_file_schema_pinned(tmp_path):
    """--metrics-file appends one JSON step record per trial step with
    EXACTLY the METRICS_FIELDS schema (structured metrics, SURVEY.md
    §5.1/§5.5; documented in docs/performance.md)."""
    import json

    from skellysim_tpu.system.system import METRICS_FIELDS

    cfg_path = _free_fiber_config(tmp_path)
    metrics = str(tmp_path / "metrics.jsonl")
    cli.run(cfg_path, metrics_path=metrics)
    lines = [json.loads(ln) for ln in open(metrics)]
    assert len(lines) >= 2
    for rec in lines:
        assert set(rec) == set(METRICS_FIELDS)
        assert rec["accepted"] and rec["residual"] < 1e-8
        assert rec["residual_true"] < 1e-7
        assert rec["refines"] >= 0 and rec["loss_of_accuracy"] is False
        # two Gram passes an iteration, each over at least one chunk of rows
        assert rec["gram_rows"] >= 2 * rec["iters"] > 0
    # trial-step index: contiguous from 0 within one run
    assert [rec["step"] for rec in lines] == list(range(len(lines)))


def test_snapshot_path_aliasing_guard():
    """cli._snapshot_path: '.out' is substituted, anything else appended —
    a naive replace could alias the trajectory file itself."""
    assert (cli._snapshot_path("skelly_sim.out", "initial_config")
            == "skelly_sim.initial_config")
    assert (cli._snapshot_path("/a/b/run.out", "final_config")
            == "/a/b/run.final_config")
    # non-.out trajectories get the suffix APPENDED, never substituted
    assert (cli._snapshot_path("traj.bin", "initial_config")
            == "traj.bin.initial_config")
    assert (cli._snapshot_path("noext", "initial_config")
            == "noext.initial_config")
    # '.out' only counts as the final extension
    assert (cli._snapshot_path("weird.out.bak", "initial_config")
            == "weird.out.bak.initial_config")
    # the snapshot path never equals the trajectory path
    for traj in ("skelly_sim.out", "traj.bin", "noext", "a.out.out"):
        assert cli._snapshot_path(traj, "initial_config") != traj


def test_crossed_write_boundary_float_robust():
    """Satellite: the dt_write boundary check survives accumulated float
    error. With dt == dt_write == 0.1 every step crosses a boundary, but
    repeated addition lands t=0.7999999999999999 whose naive frame index is
    still 7 — the naive check skips that frame."""
    from skellysim_tpu.system.system import crossed_write_boundary

    dt = dt_write = 0.1
    t = 0.0
    naive_missed = 0
    for _ in range(16):
        t += dt
        assert crossed_write_boundary(t, dt, dt_write), t
        if not int(t / dt_write) > int((t - dt) / dt_write):
            naive_missed += 1
    assert naive_missed >= 1, "the regression case no longer reproduces"
    # no double-fire: a step strictly inside one frame window stays silent
    assert not crossed_write_boundary(0.25, 0.04, 0.1)
    assert crossed_write_boundary(0.32, 0.04, 0.1)


@pytest.mark.parametrize("t, t_final, reached", [
    (sum([0.1] * 10), 1.0, True),       # 0.9999999999999999: ten 0.1-steps
    # two 0.005-steps whose emulated-f64 clock read back low from a TPU
    # (48 of 53 mantissa bits survive the device) — no third step
    (0.01 * (1 - 2.0 ** -47), 0.01, True),
    (1.0, 1.0, True), (1.5, 1.0, True), (0.0, 0.0, True),
    (0.9, 1.0, False), (1.0 - 1e-6, 1.0, False), (0.0, 1e-3, False),
    (5.0, float("-inf"), True),         # the ensemble's idle lane
])
def test_reached_t_final_float_robust(t, t_final, reached):
    """The run loop's end test tolerates the 1e-9 relative shortfall that
    `crossed_write_boundary` does, on floats and on arrays alike: a config
    of t_final = N dt takes N steps, on the CPU and on the chip."""
    import jax.numpy as jnp

    from skellysim_tpu.system.system import reached_t_final

    assert reached_t_final(t, t_final) is reached
    assert bool(reached_t_final(jnp.asarray(t), jnp.asarray(t_final))) \
        is reached


def test_run_loop_writes_every_exact_boundary_frame(tmp_path):
    """Integration regression: dt dividing dt_write exactly must produce a
    frame at EVERY boundary (the naive check dropped one around t=0.8)."""
    cfg = Config()
    cfg.params.dt_initial = 0.1
    cfg.params.dt_write = 0.1
    # 0.95, not 1.0: accumulated t reaches 0.9999999999999999 and the loop's
    # strict `t < t_final` would take an 11th step — the off-boundary end
    # keeps this a pure frame-boundary regression
    cfg.params.t_final = 0.95
    cfg.params.gmres_tol = 1e-10
    cfg.params.adaptive_timestep_flag = False
    fib = Fiber(n_nodes=16, length=1.0, bending_rigidity=0.01)
    fib.fill_node_positions(np.zeros(3), np.array([0.0, 0.0, 1.0]))
    cfg.fibers = [fib]
    cfg.background = BackgroundSource(uniform=[1.0, 0.0, 0.0])
    cfg_path = str(tmp_path / "skelly_config.toml")
    cfg.save(cfg_path)
    cli.run(cfg_path)

    r = TrajectoryReader(str(tmp_path / "skelly_sim.out"))
    # initial frame + one per 0.1-boundary in (0, 1.0]
    assert len(r) == 11, [r.load_frame(i)["time"] for i in range(len(r))]
    times = [r.load_frame(i)["time"] for i in range(len(r))]
    np.testing.assert_allclose(times, np.arange(11) * 0.1, atol=1e-9)
    r.close()


def test_cli_run_free_fiber_uniform_background(tmp_path):
    """Fiber advected by uniform background: x advances by u*t (the reference's
    `test_fiber_uniform_background.py` oracle)."""
    cfg_path = _free_fiber_config(tmp_path)
    cli.run(cfg_path)

    traj = str(tmp_path / "skelly_sim.out")
    assert os.path.exists(traj)
    assert os.path.exists(str(tmp_path / "skelly_sim.initial_config"))
    assert os.path.exists(str(tmp_path / "skelly_sim.final_config"))

    r = TrajectoryReader(traj)
    assert len(r) >= 2
    first, last = r.load_frame(0), r.load_frame(len(r) - 1)
    t_el = last["time"] - first["time"]
    x0 = np.asarray(first["fibers"][1][0]["x_"])
    x1 = np.asarray(last["fibers"][1][0]["x_"])
    drift = (x1 - x0).reshape(-1, 3)
    np.testing.assert_allclose(drift[:, 0], t_el, atol=1e-10)
    np.testing.assert_allclose(drift[:, 1:], 0.0, atol=1e-10)
    r.close()


def test_cli_guards(tmp_path):
    cfg_path = _free_fiber_config(tmp_path)
    cli.run(cfg_path)
    with pytest.raises(SystemExit, match="refusing"):
        cli.run(cfg_path)
    with pytest.raises(SystemExit, match="does not exist"):
        cli.run(str(tmp_path / "skelly_config.toml"),
                trajectory_path=str(tmp_path / "nope.out"), resume=True)


def test_cli_resume_continues_and_appends_metrics(tmp_path):
    """--resume extends the trajectory, and with --metrics-file appends to
    the existing metrics file after a {"resume": true} marker line so
    post-hoc analysis can segment runs (step indices restart at 0 per
    run)."""
    import json

    cfg_path = _free_fiber_config(tmp_path)
    metrics = str(tmp_path / "metrics.jsonl")
    cli.run(cfg_path, metrics_path=metrics)
    traj = str(tmp_path / "skelly_sim.out")
    r = TrajectoryReader(traj)
    t_end1 = r.load_frame(len(r) - 1)["time"]
    n1 = len(r)
    r.close()
    n_first = len(open(metrics).readlines())

    # extend t_final and resume
    from skellysim_tpu.config import load_config
    cfg = load_config(cfg_path)
    cfg.params.t_final = 0.04
    cfg.save(cfg_path)
    cli.run(cfg_path, resume=True, metrics_path=metrics)

    r = TrajectoryReader(traj)
    assert len(r) > n1
    t_end2 = r.load_frame(len(r) - 1)["time"]
    assert t_end2 > t_end1
    assert t_end2 == pytest.approx(0.04, abs=0.006)
    r.close()

    lines = [json.loads(ln) for ln in open(metrics)]
    assert len(lines) > n_first + 1
    markers = [(i, rec) for i, rec in enumerate(lines) if "resume" in rec]
    assert len(markers) == 1
    i_mark, marker = markers[0]
    assert i_mark == n_first and marker["resume"] is True
    assert marker["t"] == pytest.approx(0.02, abs=0.006)
    # both segments' step indices restart at 0
    assert lines[0]["step"] == 0 and lines[i_mark + 1]["step"] == 0


def test_precompute_and_body_drag_pipeline(tmp_path):
    """Config with a sphere body under constant force inside no periphery:
    velocity matches Stokes drag 6*pi*eta*R*v (reference
    `test_body_const_force.py`, 1e-6 gate relaxed to quadrature accuracy)."""
    cfg = Config()
    cfg.params.eta = 1.3
    cfg.params.dt_initial = 0.005
    cfg.params.dt_write = 0.005
    cfg.params.t_final = 0.01
    cfg.params.adaptive_timestep_flag = False
    cfg.params.gmres_tol = 1e-10
    body = Body(radius=0.6, n_nodes=600, external_force=[0.0, 0.0, 1.0],
                precompute_file="body.npz")
    cfg.bodies = [body]
    cfg_path = str(tmp_path / "skelly_config.toml")
    cfg.save(cfg_path)

    precompute.precompute_from_config(cfg_path, verbose=False)
    assert os.path.exists(str(tmp_path / "body.npz"))

    system, state, rng = builder.build_simulation(cfg_path)
    new_state, solution, info = system.step(state)
    assert bool(info.converged)
    v = np.asarray(new_state.bodies.velocity)[0]
    # hydrodynamic radius is the quadrature-node radius (0.6 - 0.1)
    expected = 1.0 / (6 * np.pi * 1.3 * 0.5)
    assert abs(v[2] - expected) / expected < 2e-3
    np.testing.assert_allclose(v[:2], 0.0, atol=1e-8)


def test_precompute_spherical_periphery_pipeline(tmp_path):
    """Point force inside a spherical shell: rigid-wall flow at the shell is
    cancelled (shell solve converges and density is finite)."""
    cfg = ConfigSpherical()
    cfg.params.eta = 1.0
    cfg.params.dt_initial = 0.01
    cfg.params.t_final = 0.01
    cfg.params.adaptive_timestep_flag = False
    cfg.periphery.radius = 2.0
    cfg.periphery.n_nodes = 300
    cfg.periphery.precompute_file = "periphery.npz"
    cfg.point_sources = [Point(position=[0.0, 0.0, 0.5], force=[0.0, 0.0, 1.0])]
    fib = Fiber(n_nodes=16, length=0.5, bending_rigidity=0.01)
    fib.fill_node_positions(np.array([0.5, 0.0, 0.0]), np.array([0.0, 0.0, 1.0]))
    cfg.fibers = [fib]
    cfg_path = str(tmp_path / "skelly_config.toml")
    cfg.save(cfg_path)

    precompute.precompute_from_config(cfg_path, verbose=False)
    # the stored operator must be genuine float64: the assembly runs through
    # the JAX kernels, and a missing x64 enable silently degraded it to
    # f32-grade values (~2.7e-8 relative — found by round-5 verify)
    peri_npz = np.load(str(tmp_path / "periphery.npz"))
    assert peri_npz["stresslet_plus_complementary"].dtype == np.float64
    system, state, rng = builder.build_simulation(cfg_path)
    new_state, solution, info = system.step(state)
    assert bool(info.converged)
    assert np.all(np.isfinite(np.asarray(new_state.shell.density)))
    assert np.all(np.isfinite(np.asarray(new_state.fibers.x)))


def test_builder_buckets_mixed_resolution(tmp_path):
    """Mixed n_nodes configs bucket by resolution (round 4 — previously
    rejected; the reference's mixed std::list container,
    `fiber_finite_difference.cpp:519-562`)."""
    cfg = Config()
    f1 = Fiber(n_nodes=16); f1.fill_node_positions(np.zeros(3), np.array([0, 0, 1.0]))
    f2 = Fiber(n_nodes=32); f2.fill_node_positions(np.ones(3), np.array([0, 0, 1.0]))
    cfg.fibers = [f1, f2]
    groups = builder.build_fibers(cfg.fibers, np.float64)
    assert isinstance(groups, tuple) and len(groups) == 2
    assert [g.n_nodes for g in groups] == [16, 32]
    assert [int(g.config_rank[0]) for g in groups] == [0, 1]


def test_listener_evaluator_mapping():
    """Reference evaluator names map onto the pair-evaluator seam
    (`listener.cpp:117` -> direct/ring/ewald)."""
    from skellysim_tpu.listener import switch_evaluator
    from skellysim_tpu.params import Params
    from skellysim_tpu.system import System

    system = System(Params(adaptive_timestep_flag=False))
    for name in ("CPU", "GPU", "TPU", None, "direct"):
        s2, switched = switch_evaluator(system, name)
        assert not switched and s2 is system, name
    # unrecognized names are rejected (the schema path's reject-typos policy)
    with pytest.raises(ValueError):
        switch_evaluator(system, "unknown")
    s2, switched = switch_evaluator(system, "FMM")
    assert switched and s2.params.pair_evaluator == "ewald"
    s2r, switched = switch_evaluator(system, "ring")
    assert switched and s2r.params.pair_evaluator == "ring"
    # and back
    s3, switched = switch_evaluator(s2, "CPU")
    assert switched and s3.params.pair_evaluator == "direct"


@pytest.mark.slow
def test_cli_pipeline_revolution_periphery(tmp_path):
    """gen -> precompute -> run for a surface-of-revolution periphery
    (`examples/oocyte` shape at fixture scale): exercises the envelope fit,
    the precompute node-count write-back, and the generic-shell solve."""
    from skellysim_tpu.config import ConfigRevolution

    cfg = ConfigRevolution()
    cfg.params.dt_initial = 0.01
    cfg.params.dt_write = 0.01
    cfg.params.t_final = 0.02
    cfg.params.adaptive_timestep_flag = False
    cfg.periphery.envelope = {
        "n_nodes_target": 150,
        "lower_bound": -3.75, "upper_bound": 3.75,
        "height": "0.5 * T * ((1 + 2*x/length)**p1) * ((1 - 2*x/length)**p2) * length",
        "T": 0.72, "p1": 0.4, "p2": 0.2, "length": 7.5,
    }
    fib = Fiber(n_nodes=8, length=0.5, bending_rigidity=0.0025,
                minus_clamped=True)
    cfg.fibers = [fib]
    cfg.periphery.move_fibers_to_surface(cfg.fibers, ds_min=0.2, verbose=False,
                                         rng=np.random.default_rng(3))
    cfg_path = str(tmp_path / "skelly_config.toml")
    cfg.save(cfg_path)

    precompute.precompute_from_config(cfg_path, verbose=False)
    # revolution precompute rewrites the config with the realized node count
    from skellysim_tpu.config import load_config

    back = load_config(cfg_path)
    assert os.path.exists(str(tmp_path / back.periphery.precompute_file))
    n_realized = int(np.load(str(tmp_path / back.periphery.precompute_file))
                     ["nodes"].shape[0])
    assert back.periphery.n_nodes == n_realized

    cli.run(cfg_path)
    traj = TrajectoryReader(str(tmp_path / "skelly_sim.out"))
    assert len(traj) >= 1
    frame = traj.load_frame(-1)
    assert np.asarray(frame["shell"]["solution_vec_"]).size == 3 * n_realized


@pytest.mark.slow
def test_cli_pipeline_ellipsoid_periphery(tmp_path):
    """gen -> precompute -> run for an ellipsoidal periphery
    (`examples/ellipsoid` shape at fixture scale)."""
    from skellysim_tpu.config import ConfigEllipsoidal, load_config

    cfg = ConfigEllipsoidal()
    cfg.params.dt_initial = 0.01
    cfg.params.dt_write = 0.01
    cfg.params.t_final = 0.02
    cfg.params.adaptive_timestep_flag = False
    cfg.periphery.n_nodes = 150
    cfg.periphery.a, cfg.periphery.b, cfg.periphery.c = 6.0, 4.0, 4.0
    fib = Fiber(n_nodes=8, length=0.5, bending_rigidity=0.0025,
                minus_clamped=True)
    cfg.fibers = [fib]
    cfg.periphery.move_fibers_to_surface(cfg.fibers, ds_min=0.2, verbose=False,
                                         rng=np.random.default_rng(5))
    cfg_path = str(tmp_path / "skelly_config.toml")
    cfg.save(cfg_path)

    precompute.precompute_from_config(cfg_path, verbose=False)
    cli.run(cfg_path)
    traj = TrajectoryReader(str(tmp_path / "skelly_sim.out"))
    assert len(traj) >= 1
    back = load_config(cfg_path)
    frame = traj.load_frame(-1)
    assert (np.asarray(frame["shell"]["solution_vec_"]).size
            == 3 * back.periphery.n_nodes)
