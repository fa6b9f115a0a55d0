"""A shell whose node count the mesh does not divide, through the mesh step
(`parallel.spmd`, the program `System.run` steps on a mesh): the toy cut of
`examples/oocyte` (`chipbench/configs/oocyte_1024.json` at 16 clamped
32-node fibers and ``n_nodes_target`` 300: 287 nodes, 861 rows) with
``params.mesh_devices`` 4 and 8, through the harness's own `run.build` and
`System.run` on the forced CPU devices.

* 287 nodes pad to 288 on both meshes (72 and 36 a device): the operators
  come from the builder padded to 864 rows and divided by rows, the O(N)
  vectors stay at 287 nodes, and the state `System.run` returns has the
  shell the user configured: a frame read back equals it;
* one step is the one-device step of the same scene to rounding on the
  full tier, and meets the plain reference `revolution_shell_step`;
* the padded node made live (its mask set and node 0's normal and weight
  copied onto it, planted by `scripts/mesh_exchange_control.py`) fails the
  reference while the program reports nothing;
* a dividing shell's placement is what it was; the events and the
  ``place_state`` span state how the shell was divided.

**The tolerances, and why.** On the full tier the padded rows are exact
zeros in every Krylov vector, so the mesh step runs the one-device solve's
iterations with its dot products summed in another order: positions agree
to 1e-10 of their size, tensions and the density to 1e-8 of their largest
entry (the readings are 7e-13 and, for the density, 1.7e-9; rounding of the
reordered sums, grown by the solve). The reference's limits are `tests/test_oocyte_reference.py`'s, with
their reasons there.
"""

import copy
import importlib.util
import json
import logging
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "chipbench")
if BENCH not in sys.path:       # the benchmark's modules name each other bare
    sys.path.insert(0, BENCH)

import check  # noqa: E402
import run as harness  # noqa: E402
import scene  # noqa: E402

from skellysim_tpu.io.trajectory import TrajectoryReader  # noqa: E402
from skellysim_tpu.parallel import shard_state  # noqa: E402
from skellysim_tpu.parallel.mesh import padded_nodes, shell_specs  # noqa: E402
from skellysim_tpu.parallel.spmd import spmd_shell_mode  # noqa: E402

TOL = 1e-8
LIMITS = {"ref_residual": TOL, "ref_residual_shell": 4e-4,
          "ref_residual_fiber_bc": 9e-8}
CELL = scene.load_json(os.path.join(BENCH, "configs", "oocyte_1024.json"))
N_SHELL, N_PADDED = 287, 288


def _by_path(name, *parts):
    spec = importlib.util.spec_from_file_location(name,
                                                  os.path.join(ROOT, *parts))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


exchange_control = _by_path("mesh_exchange_control", "scripts",
                            "mesh_exchange_control.py")


def _configuration(n_dev, tier="full"):
    cfg = copy.deepcopy(CELL)
    cfg["fibers"]["n_fibers"] = 16
    cfg["periphery"]["envelope"]["n_nodes_target"] = 300
    cfg["params"]["solver_precision"] = tier
    cfg["params"]["mesh_devices"] = n_dev
    cfg["params"]["dt_write"] = cfg["params"]["dt_initial"]  # a frame a step
    return dict(cfg, limits=LIMITS)


def _stepped(tmp, n_dev, tier="full", calls=2):
    """``calls`` x `System.run(max_steps=1)` on one trajectory, as the
    harness drives a window: (system, cfg, state, snaps, rows, frames)."""
    cfg = _configuration(n_dev, tier)
    system, state, rng, writer, traj, _ = harness.build(
        cfg, 2**31 + 42, str(tmp / "scene"))
    metrics_path = str(tmp / "metrics.jsonl")
    snaps = [harness.snapshot(state, geometry=True)]
    for _ in range(calls):
        state = system.run(state, writer=writer.write_frame, rng=rng,
                           metrics_path=metrics_path, max_steps=1)
        snaps.append(harness.snapshot(state))
    writer.close()
    with open(metrics_path) as fh:
        rows = [json.loads(line) for line in fh]
    return system, cfg, state, snaps, rows, check.read_frames(traj)


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    old, scene.CACHE_DIR = scene.CACHE_DIR, str(
        tmp_path_factory.mktemp("cache"))
    log, harness.log = harness.log, lambda *_: None
    yield
    scene.CACHE_DIR, harness.log = old, log


@pytest.fixture(scope="module")
def runs(cache, tmp_path_factory):
    return {n: _stepped(tmp_path_factory.mktemp(f"padded_d{n}"), n)
            for n in (1, 4, 8)}


def _numbers(cfg, snaps, rows, frames=None):
    got = check.check_window(cfg, {"checked_steps": len(rows)}, rows, snaps,
                             frames or {}, seed=1, tol=TOL, eta=1.0,
                             log=lambda *_: None)
    return {c["name"]: c for c in got}


# ---------------------------------------- the one-device step and the reference

@pytest.mark.parametrize("n_dev", [4, 8])
def test_padded_step_is_the_one_device_step_and_meets_the_reference(runs,
                                                                    n_dev):
    system, cfg, _, snaps, rows, frames = runs[n_dev]
    _, _, _, snaps1, rows1, _ = runs[1]
    assert system.mesh.size == n_dev and len(system._spmd_steps) == 1
    assert padded_nodes(N_SHELL, n_dev) == N_PADDED
    for s, s1 in zip(snaps[1:], snaps1[1:]):
        x, x1 = s["fibers"][0]["x"], s1["fibers"][0]["x"]
        assert x.shape == x1.shape == (16, 32, 3)
        assert np.abs(x - x1).max() <= 1e-10 * np.abs(x1).max()
        t, t1 = s["fibers"][0]["tension"], s1["fibers"][0]["tension"]
        assert np.abs(t - t1).max() <= 1e-8 * np.abs(t1).max()
        rho, rho1 = s["shell_density"], s1["shell_density"]
        assert rho.shape == rho1.shape == (3 * N_SHELL,)
        assert np.abs(rho - rho1).max() <= 1e-8 * np.abs(rho1).max()
    for r, r1 in zip(rows, rows1):
        assert r["iters"] == r1["iters"] and r["accepted"] and r["health"] == 0
        assert r["residual_true"] <= TOL
    got = _numbers(cfg, snaps, rows, frames)
    assert set(LIMITS) <= set(got)
    assert all(c["ok"] for c in got.values()), got
    theirs = max(r["residual_true"] for r in rows)
    assert abs(got["ref_residual"]["value"] - theirs) <= 0.01 * theirs


def test_mixed_tier_padded_step_meets_the_reference(cache, tmp_path):
    _, cfg, _, snaps, rows, frames = _stepped(tmp_path, 4, tier="mixed")
    assert not check.failed_steps(rows, TOL)
    got = _numbers(cfg, snaps, rows, frames)
    assert all(c["ok"] for c in got.values()), got


# ------------------------------------------------- what the user reads back

@pytest.mark.parametrize("n_dev", [4, 8])
def test_returned_state_keeps_the_live_shell_and_frames_equal_it(runs,
                                                                 n_dev):
    system, _, state, snaps, _, frames = runs[n_dev]
    shell = state.shell
    assert shell.nodes.shape == shell.normals.shape == (N_SHELL, 3)
    assert shell.weights.shape == (N_SHELL,)
    assert shell.density.shape == (3 * N_SHELL,)
    assert shell.node_mask is None
    for leaf in (shell.M_inv, shell.stresslet_plus_complementary):
        assert leaf.shape == (3 * N_PADDED, 3 * N_PADDED)
        assert len(leaf.sharding.device_set) == n_dev
        # no device ever holds the whole operator
        assert max(s.data.shape[0] for s in leaf.addressable_shards) == (
            3 * N_PADDED // n_dev)
    # the padded rows solve the identity and the padded columns are zero
    op = np.asarray(shell.stresslet_plus_complementary)
    pad = slice(3 * N_SHELL, None)
    np.testing.assert_array_equal(op[pad, pad], np.eye(3))
    assert not op[pad, :3 * N_SHELL].any() and not op[:3 * N_SHELL, pad].any()
    assert (shell.nodes.sharding.is_fully_replicated
            and shell.density.sharding.is_fully_replicated)
    # the frame of the last step, read back, is the state
    (t_last,) = [t for t in frames if abs(t - snaps[-1]["time"]) < 1e-9]
    frame = frames[t_last]
    assert check.frame_state_gap(frame, snaps[-1]) == 0.0
    np.testing.assert_array_equal(frame["shell"], np.asarray(shell.density))


def test_a_dividing_shells_placement_is_unchanged(cache, tmp_path):
    """288 = 287 + 1 nodes on four devices: every leaf divided by rows as
    before, nothing padded, the mode ``sharded``."""
    from skellysim_tpu.periphery import periphery as peri

    system, state, *_ = harness.build(_configuration(4), 3,
                                      str(tmp_path / "scene"))
    shell = state.shell
    rows = 3 * N_PADDED
    grown = peri.make_state(
        np.concatenate([np.asarray(shell.nodes), np.asarray(shell.nodes[:1])]),
        np.concatenate([np.asarray(shell.normals), np.zeros((1, 3))]),
        np.concatenate([np.asarray(shell.weights), np.zeros(1)]),
        np.eye(rows), np.eye(rows))
    placed = shard_state(state._replace(shell=grown), system.mesh,
                         step="spmd")
    assert spmd_shell_mode(placed, system.mesh) == "sharded"
    spec = jax.sharding.PartitionSpec("fib")
    assert all(s == spec for s in shell_specs(placed.shell, "spmd")
               if s is not None)
    for name in ("nodes", "density", "M_inv"):
        leaf = getattr(placed.shell, name)
        assert leaf.shape == getattr(grown, name).shape
        assert leaf.sharding.spec == spec


# ------------------------------------------------------ the padded row, live

def test_the_padded_row_made_live_fails_the_reference(cache, tmp_path,
                                                      monkeypatch):
    """Planted by `scripts/mesh_exchange_control.py` (never a switch in the
    program): the padded node masked live with node 0's normal and weight,
    a second copy of node 0 whose density solves against the fibers' flow
    and whose double layer reaches every fiber. The program's own residual
    sees nothing; the reference, which has 287 nodes, does."""
    exchange_control.plant("padded_row_live", monkeypatch.setattr)
    _, cfg, _, snaps, rows, _ = _stepped(tmp_path, 4)
    assert not check.failed_steps(rows, TOL)
    got = _numbers(cfg, snaps, rows)
    # 9.4e-8 at this toy's size: one live copy of a node among 287
    assert got["ref_residual"]["value"] >= 5 * LIMITS["ref_residual"], got
    assert not all(c["ok"] for c in got.values())


# ------------------------------------------------------------ what is said

def test_events_and_place_state_state_the_padded_layout(runs, cache,
                                                        tmp_path, caplog):
    from skellysim_tpu.obs import tracer as obs_tracer
    from skellysim_tpu.obs.summarize import Summary

    system, state, rng, writer, _, _ = harness.build(
        _configuration(4), 3, str(tmp_path / "scene"))
    writer.close()
    tr = obs_tracer.Tracer()
    with obs_tracer.use(tr), caplog.at_level(logging.INFO, "skellysim_tpu"):
        system.run(state, rng=rng, max_steps=1)
    rows = 3 * N_PADDED
    (ev,) = [e for e in tr.events if e["ev"] == "periphery"]
    assert (ev["nodes"], ev["nodes_padded"], ev["chips"],
            ev["rows_per_chip"]) == (N_SHELL, N_PADDED, 4, rows // 4)
    assert ev["operator"] == ev["m_inv"] == f"{rows}x{rows}"
    assert ev["operator_bytes_per_chip"] == (rows // 4) * rows * (8 + 8)
    (span,) = [e for e in tr.events if e.get("name") == "place_state"]
    assert {k: span[k] for k in (
        "devices", "shell_layout", "shell_nodes", "shell_nodes_padded",
        "shell_rows_per_chip", "operator_bytes_per_chip")} == {
        "devices": 4, "shell_layout": "padded", "shell_nodes": N_SHELL,
        "shell_nodes_padded": N_PADDED, "shell_rows_per_chip": rows // 4,
        "operator_bytes_per_chip": (rows // 4) * rows * 16}
    (mesh_ev,) = [e for e in tr.events if e["ev"] == "mesh"]
    assert (mesh_ev["shell"], mesh_ev["shell_rows_per_chip"]) == (
        "padded", rows // 4)
    assert (f"periphery shape=revolution nodes={N_SHELL} "
            f"operator={rows}x{rows}") in caplog.text
    assert (f"chips=4 rows_per_chip={rows // 4} nodes_padded={N_PADDED} "
            f"operator_bytes_per_chip={(rows // 4) * rows * 16}"
            ) in caplog.text
    report = Summary()
    report.add_record(ev)
    assert f"nodes_padded={N_PADDED}" in report.render()


def test_every_pad_and_unpad_sits_under_shell_pad(runs):
    """The scope the benchmark's ``shell_pad_chip_s`` reads: the entry pad
    of the nodes, normals, weights, density and mask, and the density's
    gather back to 3N, all under ``shell_pad`` in the lowered program."""
    system, _, state, *_ = runs[4]
    (fn,) = system._spmd_steps.values()
    text = fn.lower(state).as_text(debug_info=True)
    padded = [ln for ln in text.splitlines() if "shell_pad" in ln]
    assert any("concatenate" in ln for ln in padded)
    assert any("dynamic_slice" in ln for ln in padded)
    assert any("all_gather" in ln for ln in padded)


def test_the_spread_reference_is_the_reference(runs):
    """`revolution_shell_step_mesh` (the cell's reference: the pair sums
    dealt to the eight CPU devices) against `revolution_shell_step` on the
    same answers: one target row's sum is the same on any device, so the
    numbers agree to the float64 rounding of the norms."""
    _, cfg, _, snaps, _, _ = runs[4]
    pre = dict(snaps[0])
    one = check.load_reference("revolution_shell_step")
    spread = check.load_reference("revolution_shell_step_mesh")
    for post in snaps[1:]:
        a = one.step_residual(cfg, pre, post, dt=pre["dt"], eta=1.0)
        b = spread.step_residual(cfg, pre, post, dt=pre["dt"], eta=1.0)
        assert set(a) == set(b) == set(LIMITS)
        for k in a:
            assert b[k] == pytest.approx(a[k], rel=1e-9, abs=1e-20), k
        pre = dict(post, geometry=snaps[0]["geometry"])


def test_the_examples_toml_steps_from_the_front_door(tmp_path, caplog):
    """`examples/oocyte/gen_config.py`'s scene with its chip count (16 of
    its fibers, its envelope at ``n_nodes_target`` 300 to fit a test) ->
    `precompute` -> `cli.run`: the mesh step on four devices with no other
    flag, the frames holding the shell's 287 nodes."""
    from skellysim_tpu import cli, precompute

    example = _by_path("oocyte_gen_config", "examples", "oocyte",
                       "gen_config.py")
    config = example.build_config(16, 4)
    config.periphery.envelope.n_nodes_target = 300
    config.params.t_final = 0.02
    config.params.dt_write = 0.01
    config.params.adaptive_timestep_flag = False
    path = str(tmp_path / "skelly_config.toml")
    config.save(path)
    precompute.precompute_from_config(path, verbose=False)
    with caplog.at_level(logging.INFO, "skellysim_tpu"):
        cli.run(path)
    assert "mesh devices=4 axis=fib step=spmd" in caplog.text
    assert f"nodes_padded={N_PADDED}" in caplog.text
    reader = TrajectoryReader(str(tmp_path / "skelly_sim.out"))
    try:
        assert len(reader) == 3
        frame = reader.load_frame(len(reader) - 1)
        assert np.asarray(frame["shell"]["solution_vec_"]).size == (
            3 * N_SHELL)
        assert np.isfinite(np.asarray(frame["shell"]["solution_vec_"])).all()
    finally:
        reader.close()
