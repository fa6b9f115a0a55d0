"""A SHELL on a mesh from the front door: the toy cut of `examples/ellipsoid`
(`chipbench/tests/toy/ellipsoid_toy.json`: 8 clamped, force-carrying 16-node
fibers on a 300-node ellipsoid) with ``params.mesh_devices = 4`` through the
harness's own `run.build` (`scene.write_scene` -> `build_simulation` ->
`bucketize`) and `System.run`, on four of the forced CPU devices.

* held to the plain reference `clamped_shell_step` (judged by
  `check.check_window`, as a benchmark run is) and to the one-device run of
  the same TOML;
* the exchange carries part of the answer here (these fibers push on the
  wall), so a dropped ring hop, a zeroed fiber -> shell block of one hop and
  a zeroed quarter of the all-gathered density each fail the reference,
  while the program, whose own residual is taken with the same fault,
  reports nothing (`scripts/mesh_exchange_control.py` plants them on the
  chip at the cell's size);
* the mesh step is compiled ONCE by a `run` of several steps and a
  re-entry: the state is placed as that program takes and returns it
  (`parallel.mesh.shell_specs`), so no step sees a second argument signature;
* the builder hands every operator row from the host to its shard: no
  device ever holds a whole operator; the shards' row-block products,
  concatenated, are the whole operator's product;
* the ``periphery`` and ``mesh`` events state the whole shell and a chip's
  rows.

The limits are `tests/test_ellipsoid_reference.py`'s, with their reasons
there (the shell's rows are held to ``gmres_tol`` x |b| / |b_shell|).
"""

import copy
import importlib.util
import json
import logging
import os
import sys

import jax
import jax.numpy as jnp
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
from skellysim_tpu.parallel import FIBER_AXIS, make_mesh, shard_state  # noqa: E402
from skellysim_tpu.parallel.mesh import shell_specs  # noqa: E402
from skellysim_tpu.periphery import periphery as peri  # noqa: E402

N_DEV = 4
TOL = 1e-8
PARITY = 1e-7
LIMITS = {"ref_residual": TOL, "ref_residual_shell": 2e-2,
          "ref_residual_fiber_bc": 5e-8}
TOY = scene.load_json(os.path.join(BENCH, "tests", "toy",
                                   "ellipsoid_toy.json"))
N_SHELL = TOY["periphery"]["n_nodes"]
ROWS = 3 * N_SHELL


def _by_path(name, *parts):
    spec = importlib.util.spec_from_file_location(name,
                                                  os.path.join(ROOT, *parts))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


exchange_control = _by_path("mesh_exchange_control", "scripts",
                            "mesh_exchange_control.py")


def _configuration(n_dev):
    cfg = copy.deepcopy(TOY)
    cfg["params"]["mesh_devices"] = n_dev
    return dict(cfg, reference="clamped_shell_step", limits=LIMITS)


def _stepped(tmp, n_dev, calls=3):
    """``calls`` x `System.run(max_steps=1)` on one trajectory, as the
    harness drives a window: (system, cfg, snaps, rows, frames)."""
    cfg = _configuration(n_dev)
    system, state, rng, writer, traj, _ = harness.build(
        cfg, 2**31 + 36, str(tmp / "scene"))
    metrics_path = str(tmp / "metrics.jsonl")
    snaps = [harness.snapshot(state, geometry=True)]
    for _ in range(calls):
        state = system.run(state, writer=writer.write_frame, rng=rng,
                           metrics_path=metrics_path, max_steps=1)
        snaps.append(harness.snapshot(state))
    writer.close()
    with open(metrics_path) as fh:
        rows = [json.loads(line) for line in fh]
    reader = TrajectoryReader(traj)
    frames = [reader.load_frame(i) for i in range(len(reader))]
    reader.close()
    return system, cfg, snaps, rows, frames


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    old, scene.CACHE_DIR = scene.CACHE_DIR, str(
        tmp_path_factory.mktemp("cache"))
    log, harness.log = harness.log, lambda *_: None
    yield
    scene.CACHE_DIR, harness.log = old, log


@pytest.fixture(scope="module")
def runs(cache, tmp_path_factory):
    return {n: _stepped(tmp_path_factory.mktemp(f"shell_d{n}"), n)
            for n in (N_DEV, 1)}


def _numbers(cfg, snaps, rows):
    got = check.check_window(cfg, {"checked_steps": len(rows)}, rows, snaps,
                             {}, seed=1, tol=TOL, eta=1.0,
                             log=lambda *_: None)
    return {c["name"]: c for c in got}


# ----------------------------------------------- the reference and the twin

def test_mesh_shell_run_meets_the_plain_reference(runs):
    system, cfg, snaps, rows, _ = runs[N_DEV]
    assert system.mesh is not None and system.mesh.size == N_DEV
    assert len(system._spmd_steps) == 1 and len(rows) == 3
    assert not check.failed_steps(rows, TOL)
    got = _numbers(cfg, snaps, rows)
    assert set(LIMITS) <= set(got)
    assert all(c["ok"] for c in got.values()), got
    # the reference and the program's own explicit residual are one number
    theirs = max(r["residual_true"] for r in rows)
    assert abs(got["ref_residual"]["value"] - theirs) <= 0.01 * theirs
    # these fibers carry force: what the exchange moves is part of the answer
    assert all(r["fiber_error"] > 1e-5 and r["iters"] > 1 for r in rows)


def test_mesh_shell_frames_and_rows_equal_the_one_device_run(runs):
    _, _, snaps4, rows4, frames4 = runs[N_DEV]
    system1, _, snaps1, rows1, frames1 = runs[1]
    assert system1.mesh is None and not system1._spmd_steps
    assert len(frames4) == len(frames1) and len(snaps4) == len(snaps1) == 4
    for f4, f1 in zip(frames4, frames1):
        assert f4["time"] == f1["time"]
        for a, b in zip(f4["fibers"][1], f1["fibers"][1]):
            x4, x1 = np.asarray(a["x_"], float), np.asarray(b["x_"], float)
            assert np.abs(x4 - x1).max() <= PARITY * np.abs(x1).max()
        d4 = np.asarray(f4["shell"]["solution_vec_"], float)
        d1 = np.asarray(f1["shell"]["solution_vec_"], float)
        assert d4.shape == d1.shape == (ROWS,)
        assert np.abs(d4 - d1).max() <= PARITY * max(np.abs(d1).max(), 1.0)
    # every step's state, frame or no frame
    for s4, s1 in zip(snaps4[1:], snaps1[1:]):
        x4, x1 = s4["fibers"][0]["x"], s1["fibers"][0]["x"]
        t4, t1 = s4["fibers"][0]["tension"], s1["fibers"][0]["tension"]
        assert x4.shape == x1.shape == (8, 16, 3)
        assert np.abs(x4 - x1).max() <= PARITY * np.abs(x1).max()
        assert np.abs(t4 - t1).max() <= PARITY * max(np.abs(t1).max(), 1.0)
        assert (np.abs(s4["shell_density"] - s1["shell_density"]).max()
                <= PARITY * max(np.abs(s1["shell_density"]).max(), 1.0))
    for r4, r1 in zip(rows4, rows1):
        assert set(r4) == set(r1)
        for key in ("step", "t", "dt", "accepted", "health", "refines",
                    "loss_of_accuracy", "guard_retries", "active_fibers"):
            assert r4[key] == r1[key], key
        assert abs(r4["iters"] - r1["iters"]) <= 1
        assert r4["residual_true"] <= TOL and r1["residual_true"] <= TOL
        assert abs(r4["fiber_error"] - r1["fiber_error"]) <= PARITY


# ------------------------------------------------------ faults in the exchange

@pytest.mark.parametrize("fault,number", [
    ("dropped_hop", "ref_residual"),
    ("shell_rows_of_a_hop", "ref_residual_shell"),
    ("gathered_density_quarter", "ref_residual_shell")])
def test_a_fault_in_the_exchange_fails_the_reference(cache, tmp_path,
                                                     monkeypatch, fault,
                                                     number):
    """Planted by `scripts/mesh_exchange_control.py` (never a switch in the
    program), in the right-hand side, the Krylov loop and the program's own
    explicit residual alike: the program reports a sound step, the
    reference does not."""
    exchange_control.plant(fault, monkeypatch.setattr,
                           fiber_rows=(8 // N_DEV) * 16)
    _, cfg, snaps, rows, _ = _stepped(tmp_path, N_DEV, calls=2)
    assert not check.failed_steps(rows, TOL)
    got = _numbers(cfg, snaps, rows)
    assert got[number]["value"] >= 100 * LIMITS[number], got
    assert not all(c["ok"] for c in got.values())


# ------------------------------------------------- one program, placed once

def test_a_run_of_steps_and_a_reentry_compile_the_mesh_step_once(cache,
                                                                  tmp_path,
                                                                  monkeypatch):
    """Counted in backend compiles: a state whose shell vectors entered
    replicated came back from the first step divided by rows, and the
    second step of the same `run` compiled the whole mesh program again."""
    from jax import monitoring

    from skellysim_tpu.parallel import spmd

    builds, compiles = [], []
    orig = spmd.build_spmd_step

    def counting(*args, **kw):
        builds.append(1)
        return orig(*args, **kw)

    monkeypatch.setattr(spmd, "build_spmd_step", counting)

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    system, state, rng, writer, _, _ = harness.build(
        _configuration(N_DEV), 3, str(tmp_path / "scene"))
    writer.close()
    monitoring.register_event_duration_secs_listener(on_duration)
    try:
        state = system.run(state, rng=rng, max_steps=1)
        after_one = len(compiles)
        placed = [leaf.sharding for leaf in state.shell if leaf is not None]
        state = system.run(state, rng=rng, max_steps=3)   # a run of steps
        state = system.run(state, rng=rng, max_steps=1)   # a re-entry
    finally:
        monitoring.unregister_event_duration_listener(on_duration)
    assert len(builds) == 1 and len(system._spmd_steps) == 1
    assert after_one >= 1
    assert len(compiles) == after_one       # nothing compiled after step one
    assert float(state.time) == pytest.approx(5 * 0.008)
    # ... and nothing was re-placed: every shell leaf is where step one left it
    assert [leaf.sharding for leaf in state.shell
            if leaf is not None] == placed


def _largest_share(leaf):
    return max(s.data.shape[0] for s in leaf.addressable_shards)


def test_no_operator_leaf_is_whole_on_any_one_device(cache, tmp_path):
    """From the builder on: rows go from the host's npz to their shards, and
    `run`'s `place_state` finds them there."""
    system, state, rng, writer, _, _ = harness.build(
        _configuration(N_DEV), 3, str(tmp_path / "scene"))
    writer.close()
    spec = jax.sharding.NamedSharding(system.mesh,
                                      jax.sharding.PartitionSpec(FIBER_AXIS))
    for when in ("built", "placed", "stepped"):
        if when == "placed":
            state = shard_state(state, system.mesh, step="spmd")
        elif when == "stepped":
            state = system.run(state, rng=rng, max_steps=1)
        shell = state.shell
        for name in ("stresslet_plus_complementary", "M_inv"):
            leaf = getattr(shell, name)
            assert leaf.shape == (ROWS, ROWS)
            assert len(leaf.sharding.device_set) == N_DEV, (when, name)
            assert _largest_share(leaf) == ROWS // N_DEV, (when, name)
        for name, rows in (("nodes", N_SHELL), ("normals", N_SHELL),
                           ("weights", N_SHELL), ("density", ROWS)):
            leaf = getattr(shell, name)
            assert leaf.sharding.is_equivalent_to(spec, leaf.ndim), (when,
                                                                     name)
            assert _largest_share(leaf) == rows // N_DEV
    # the table is the one the mesh step's in_specs are made from
    assert all(s == jax.sharding.PartitionSpec(FIBER_AXIS)
               for s in shell_specs(state.shell, "spmd") if s is not None)
    gspmd = shell_specs(state.shell, "gspmd")
    assert gspmd.M_inv == jax.sharding.PartitionSpec(FIBER_AXIS)
    assert gspmd.nodes == gspmd.density == jax.sharding.PartitionSpec()


def test_a_shell_the_mesh_does_not_divide_is_refused_in_words(cache):
    # 300 nodes over 8 devices: GSPMD's whole rows a device (900 / 8 no);
    # the mesh step pads such a shell (tests/test_spmd_padded_shell.py)
    state = jax.eval_shape(lambda: peri.make_state(
        np.zeros((N_SHELL, 3)), np.zeros((N_SHELL, 3)), np.zeros(N_SHELL),
        np.zeros((ROWS, ROWS)), np.zeros((ROWS, ROWS))))
    from skellysim_tpu.system.system import SimState

    sim = SimState(time=jnp.zeros(()), dt=jnp.zeros(()), fibers=None,
                   points=None, background=None, shell=state, bodies=None)
    with pytest.raises(ValueError, match=r"3\*n_nodes = 900\).*multiple "
                                         r"of 8.*step='spmd'"):
        shard_state(sim, make_mesh(8), step="gspmd")
    with pytest.raises(ValueError, match="step 'pjit'"):
        shell_specs(state, "pjit")


def test_the_shards_row_block_products_are_the_whole_operators(cache,
                                                               tmp_path):
    """What ties a chip's share to the whole: each shard's rows of the
    float64 operator and of `M_inv` applied to the all-gathered density, as
    the mesh step applies them (`periphery._apply_operator` on the chip's
    rows), concatenated, against the whole operator's product."""
    system, state, _, writer, _, _ = harness.build(
        _configuration(N_DEV), 3, str(tmp_path / "scene"))
    writer.close()
    x = np.random.default_rng(36).normal(size=ROWS)
    for name in ("stresslet_plus_complementary", "M_inv"):
        leaf = getattr(state.shell, name)
        whole = np.asarray(leaf) @ x
        shards = sorted(leaf.addressable_shards,
                        key=lambda s: s.index[0].start)
        assert [s.index[0].start for s in shards] == [
            i * ROWS // N_DEV for i in range(N_DEV)]
        parts = [np.asarray(peri._apply_operator(
            jnp.asarray(np.asarray(s.data)), jnp.asarray(x, leaf.dtype)))
            for s in shards]
        assert all(p.shape == (ROWS // N_DEV,) for p in parts)
        np.testing.assert_allclose(np.concatenate(parts), whole,
                                   rtol=1e-12, atol=1e-12 * np.abs(whole).max())


# ------------------------------------------------------------------ the events

def test_periphery_and_mesh_events_state_the_whole_shell_and_a_chips_rows(
        cache, tmp_path, caplog):
    from skellysim_tpu.obs import tracer as obs_tracer
    from skellysim_tpu.obs.summarize import Summary

    system, state, rng, writer, _, _ = harness.build(
        _configuration(N_DEV), 3, str(tmp_path / "scene"))
    writer.close()
    tr = obs_tracer.Tracer()
    with obs_tracer.use(tr), caplog.at_level(logging.INFO, "skellysim_tpu"):
        system.run(state, rng=rng, max_steps=2)
    (ev,) = [e for e in tr.events if e["ev"] == "periphery"]
    assert (ev["shape"], ev["nodes"], ev["operator"], ev["operator_bytes"],
            ev["m_inv"]) == ("ellipsoid", N_SHELL, f"{ROWS}x{ROWS}",
                             ROWS * ROWS * 8, f"{ROWS}x{ROWS}")
    assert (ev["chips"], ev["rows_per_chip"]) == (N_DEV, ROWS // N_DEV)
    # the policy is a chip's: 225 float64 rows go whole
    assert (ev["f64_product"], ev["row_block"]) == ("whole", 0)
    assert (f"periphery shape=ellipsoid nodes={N_SHELL} "
            f"operator={ROWS}x{ROWS} float64") in caplog.text
    assert f"chips={N_DEV} rows_per_chip={ROWS // N_DEV}" in caplog.text
    (mesh_ev,) = [e for e in tr.events if e["ev"] == "mesh"]
    assert (mesh_ev["devices"], mesh_ev["step"], mesh_ev["shell"],
            mesh_ev["shell_rows_per_chip"]) == (N_DEV, "spmd", "rows",
                                                ROWS // N_DEV)
    report = Summary()
    report.add_record(ev)
    assert f"chips={N_DEV} rows_per_chip={ROWS // N_DEV}" in report.render()


def test_one_device_periphery_event_states_one_chip(cache, tmp_path):
    from skellysim_tpu.obs import tracer as obs_tracer

    system, state, *_ = harness.build(_configuration(1), 3,
                                      str(tmp_path / "scene"))
    tr = obs_tracer.Tracer()
    with obs_tracer.use(tr):
        jax.eval_shape(system._solve_impl, state)
    (ev,) = [e for e in tr.events if e["ev"] == "periphery"]
    assert (ev["nodes"], ev["chips"], ev["rows_per_chip"]) == (N_SHELL, 1,
                                                               ROWS)
    # a chip's 6,000 of the cell's 24,000 float64 rows go in row blocks
    shard = peri.PeripheryState(*[
        jax.ShapeDtypeStruct(s, d) for s, d in (
            ((2000, 3), np.float64), ((2000, 3), np.float64),
            ((2000,), np.float64), ((6000, 24000), np.float32),
            ((6000, 24000), np.float64), ((6000,), np.float64))])
    said = peri.describe(shard, chips=4)
    assert (said["nodes"], said["operator"], said["operator_bytes"],
            said["m_inv_bytes"]) == (8000, "24000x24000", 4_608_000_000,
                                     2_304_000_000)
    assert (said["f64_product"], said["row_block"], said["chips"],
            said["rows_per_chip"]) == ("row_blocks", 2048, 4, 6000)
