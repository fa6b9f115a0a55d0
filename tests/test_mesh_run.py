"""A mesh from the front door: a TOML with ``params.mesh_devices = 4``
through `build_simulation` -> `bucketize` -> `System.run`, on four of the
forced CPU devices (`conftest.py`), held to the benchmark's plain reference
(``chipbench/references/free_fiber_step.py``, loaded by path: it imports
nothing of the program) and to the one-device run of the same TOML.

The fibers here are BENT (arcs): straight free fibers under their
tangential motor force stay straight and tension-free, exert no force on
the fluid and so exchange zeros — a dropped ring hop would change nothing
(that is the benchmark's `free_fibers_*` scene; PERF.md section 7). A bent
fiber's bending force drives a flow at every other fiber, so the exchange
carries what the answer depends on.
"""

import importlib.util
import json
import logging
import os

import jax
import numpy as np
import pytest
from jax import lax

from skellysim_tpu import builder
from skellysim_tpu.config import Config, Fiber
from skellysim_tpu.config.schema import load_runtime_config
from skellysim_tpu.io.trajectory import TrajectoryReader, TrajectoryWriter
from skellysim_tpu.system import buckets as bucket_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_DEV = 4
N_NODES = 16
DT = 0.005
#: the tolerance the configuration states and the benchmark's `correct`
#: holds a cell to (`check.py`: ``ref_residual`` <= ``gmres_tol``). The
#: solve here is full float64 (`solver_precision="auto"` on a CPU), the
#: reference's residual agrees with the program's own to its rounding
#: (~1e-13), so a sound step reads at most what GMRES stopped at: <= 1e-8
TOL = 1e-8
#: `chip_smoke.MESH_PARITY_GATE`: mesh step against the one-device step,
#: both converged to TOL on one float64 system
PARITY = 1e-7


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(ROOT, "chipbench", "references",
                        "free_fiber_step.py")
    spec = importlib.util.spec_from_file_location("plain_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _arc(rng, box):
    """[N_NODES, 3] nodes of a unit-length circular arc (curvature 1.5),
    uniformly spaced in arc length, placed and turned by ``rng``."""
    origin = rng.uniform(-box / 2, box / 2, 3)
    d = rng.normal(size=3)
    d /= np.linalg.norm(d)
    n = np.cross(d, rng.normal(size=3))
    n /= np.linalg.norm(n)
    k, s = 1.5, np.linspace(0.0, 1.0, N_NODES)
    return (origin[None, :] + (np.sin(k * s) / k)[:, None] * d[None, :]
            + ((1.0 - np.cos(k * s)) / k)[:, None] * n[None, :])


def write_config(path, n_fibers, mesh_devices, seed=100, box=1.6):
    cfg = Config()
    p = cfg.params
    p.dt_initial = p.dt_max = p.dt_write = DT
    p.t_final = 1e6
    p.gmres_tol = TOL
    p.adaptive_timestep_flag = False
    p.pair_evaluator = "ring"
    p.mesh_devices = mesh_devices
    rng = np.random.default_rng(seed)
    for _ in range(n_fibers):
        fib = Fiber(n_nodes=N_NODES, length=1.0, bending_rigidity=0.0025,
                    radius=0.0125, force_scale=-0.05)
        fib.x = _arc(rng, box).ravel().tolist()
        cfg.fibers.append(fib)
    cfg.save(str(path))
    return str(path)


def run_steps(cfg_path, workdir, calls=3, max_steps=1, mesh=None):
    """`cli.run`'s sequence; ``calls`` x `System.run(max_steps=...)` on one
    trajectory, as the benchmark's harness drives it. Returns (system,
    frames, metrics rows). ``mesh``: one the caller brings."""
    system, state, rng = builder.build_simulation(cfg_path, mesh=mesh)
    policy = bucket_mod.BucketPolicy.from_runtime(
        load_runtime_config(cfg_path))
    state, _ = bucket_mod.bucketize(
        state, policy, pair_evaluator=system.params.pair_evaluator)
    traj = os.path.join(workdir, "skelly_sim.out")
    metrics = os.path.join(workdir, "metrics.jsonl")
    with TrajectoryWriter(traj) as writer:
        writer.write_frame(state, rng_state=rng.dump_state())
        for _ in range(calls):
            state = system.run(state, writer=writer.write_frame, rng=rng,
                               metrics_path=metrics, max_steps=max_steps)
    reader = TrajectoryReader(traj)
    frames = [reader.load_frame(i) for i in range(len(reader))]
    reader.close()
    rows = [json.loads(ln) for ln in open(metrics)]
    return system, frames, rows


def frame_fibers(frame):
    fibs = frame["fibers"][1]
    return (np.array([np.asarray(f["x_"], float).reshape(-1, 3)
                      for f in fibs]),
            np.array([np.asarray(f["tension_"], float).ravel()
                      for f in fibs]))


def reference_residuals(reference, frames):
    """The plain reference's ||b - A x|| / ||b|| of every step: the system
    built from the frame before it, applied to the frame after it."""
    out = []
    for before, after in zip(frames, frames[1:]):
        x0, t0 = frame_fibers(before)
        x1, t1 = frame_fibers(after)
        nf = x0.shape[0]
        const = {"length": np.full(nf, 1.0),
                 "bending_rigidity": np.full(nf, 0.0025),
                 "radius": np.full(nf, 0.0125),
                 "force_scale": np.full(nf, -0.05)}
        pre = {"fibers": [dict(const, x=x0, tension=t0)]}
        post = {"fibers": [dict(const, x=x1, tension=t1)]}
        out.append(float(reference.step_residual({}, pre, post, dt=DT,
                                                 eta=1.0)))
    return out


def _drop_last_ring_position(monkeypatch):
    """The ring with one hop dropped: every position but the last is summed,
    so each target misses the flow of one neighbour's sources — in the
    right-hand side, in the Krylov loop and in the program's own explicit
    residual alike."""
    from skellysim_tpu.parallel import ring

    def dropped(block_fn, axis_name, n_dev, u0, *rotating, unroll=False):
        perm = [(j, (j + 1) % n_dev) for j in range(n_dev)]
        u, rot = u0, tuple(rotating)
        for _ in range(n_dev - 1):
            nxt = jax.tree_util.tree_map(
                lambda a: lax.ppermute(a, axis_name, perm), rot)
            u = u + block_fn(*rot)
            rot = nxt
        return u

    monkeypatch.setattr(ring, "_ring_accumulate", dropped)


@pytest.fixture(scope="module")
def mesh_run(tmp_path_factory):
    """Three steps of 6 bent fibers on four devices (4 does not divide 6:
    the builder pads to 8 slots) and the same TOML's run on one device."""
    out = {}
    for n_dev in (N_DEV, 1):
        work = tmp_path_factory.mktemp(f"mesh_run_d{n_dev}")
        cfg = write_config(work / "skelly_config.toml", 6, n_dev)
        out[n_dev] = run_steps(cfg, str(work))
    return out


def test_mesh_run_meets_the_plain_reference(mesh_run, reference):
    system, frames, rows = mesh_run[N_DEV]
    assert system.mesh is not None and system.mesh.size == N_DEV
    assert len(frames) == 4 and len(rows) == 3
    residuals = reference_residuals(reference, frames)
    assert max(residuals) <= TOL, residuals
    # the program's own verdicts, as the benchmark's `failed` reads them
    for row in rows:
        assert row["accepted"] and row["health"] == 0
        assert not row["loss_of_accuracy"]
        assert row["residual_true"] <= TOL
        assert row["iters"] > 1       # the fibers do drive each other


def test_mesh_run_with_a_dropped_ring_hop_fails_the_reference(
        tmp_path, monkeypatch, reference):
    _drop_last_ring_position(monkeypatch)
    cfg = write_config(tmp_path / "skelly_config.toml", 8, N_DEV)
    _, frames, rows = run_steps(cfg, str(tmp_path), calls=1)
    residuals = reference_residuals(reference, frames)
    assert min(residuals) > 100 * TOL, residuals
    # the program cannot see a flow it never summed: only the reference does
    assert all(r["residual_true"] <= TOL and r["health"] == 0 for r in rows)


def test_mesh_run_frames_and_rows_equal_the_one_device_run(mesh_run):
    _, frames4, rows4 = mesh_run[N_DEV]
    _, frames1, rows1 = mesh_run[1]
    assert len(frames4) == len(frames1)
    for f4, f1 in zip(frames4, frames1):
        x4, t4 = frame_fibers(f4)
        x1, t1 = frame_fibers(f1)
        # live fibers only, in the one-device (config) order: the two
        # padding slots of the mesh run never reach a frame
        assert x4.shape == x1.shape == (6, N_NODES, 3)
        assert f4["time"] == f1["time"]
        assert np.abs(x4 - x1).max() <= PARITY * np.abs(x1).max()
        assert np.abs(t4 - t1).max() <= PARITY * max(np.abs(t1).max(), 1.0)
    assert len(rows4) == len(rows1) == 3
    for r4, r1 in zip(rows4, rows1):
        assert set(r4) == set(r1)
        for key in ("step", "t", "dt", "accepted", "health", "refines",
                    "loss_of_accuracy", "guard_retries", "nucleations",
                    "catastrophes", "active_fibers"):
            assert r4[key] == r1[key], key
        assert abs(r4["iters"] - r1["iters"]) <= 1
        assert r4["residual_true"] <= TOL and r1["residual_true"] <= TOL
        assert abs(r4["fiber_error"] - r1["fiber_error"]) <= PARITY


def test_a_mesh_the_caller_brings_steps_the_mesh_program(tmp_path, mesh_run):
    """`build_simulation(cfg, mesh=m)` with the TOML's default
    ``mesh_devices = 1``: one key decides (the System has a mesh), so the
    builder pads to whole fibers a device (6 -> 8 on four) and `run` steps
    the mesh program, as for a mesh the config asked for."""
    from skellysim_tpu.parallel import make_mesh

    cfg = write_config(tmp_path / "skelly_config.toml", 6, 1)
    system, frames, rows = run_steps(cfg, str(tmp_path), calls=2,
                                     mesh=make_mesh(N_DEV))
    assert system.params.mesh_devices == 1 and system.mesh.size == N_DEV
    assert len(system._spmd_steps) == 1
    _, frames1, rows1 = mesh_run[1]
    for f4, f1 in zip(frames, frames1):
        x4, t4 = frame_fibers(f4)
        x1, t1 = frame_fibers(f1)
        assert x4.shape == x1.shape == (6, N_NODES, 3)
        assert np.abs(x4 - x1).max() <= PARITY * np.abs(x1).max()
        assert np.abs(t4 - t1).max() <= PARITY * max(np.abs(t1).max(), 1.0)
    assert all(r["residual_true"] <= TOL and r["health"] == 0 for r in rows)
    assert [r["refines"] for r in rows] == [r["refines"] for r in rows1[:2]]


def test_mesh_step_program_is_built_once_across_run_calls(tmp_path,
                                                          monkeypatch):
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
            compiles.append(event)

    monitoring.register_event_duration_secs_listener(on_duration)
    try:
        cfg = write_config(tmp_path / "skelly_config.toml", 8, N_DEV)
        system, state, rng = builder.build_simulation(cfg)
        state = system.run(state, rng=rng, max_steps=1)
        state = system.run(state, rng=rng, max_steps=1)
        after_two = len(compiles)
        for _ in range(3):
            state = system.run(state, rng=rng, max_steps=1)
        # one loop of several steps on the state `run` handed back
        state = system.run(state, rng=rng, max_steps=3)
    finally:
        monitoring.unregister_event_duration_listener(on_duration)
    assert len(builds) == 1 and len(system._spmd_steps) == 1
    assert len(compiles) == after_two   # nothing compiled from call 3 on
    assert float(state.time) == pytest.approx(8 * DT)
    assert len(state.fibers.x.sharding.device_set) == N_DEV


@pytest.mark.parametrize("case", ["over_visible", "zero", "one_with_ring"])
def test_mesh_devices_field(tmp_path, caplog, case):
    if case == "one_with_ring":
        cfg = write_config(tmp_path / "skelly_config.toml", 4, 1)
        with caplog.at_level(logging.WARNING, logger="skellysim_tpu"):
            system, state, _ = builder.build_simulation(cfg)
        # today's log line and today's step function
        assert system.mesh is None and system.params.mesh_devices == 1
        assert "using the direct evaluator on one device" in caplog.text
        assert "mesh_devices" in caplog.text
        state = system.run(state, max_steps=1)
        assert not system._spmd_steps
        assert len(state.fibers.x.sharding.device_set) == 1
        return
    n = {"over_visible": 64, "zero": 0}[case]
    if case == "zero":
        # the schema refuses it where a config is written ...
        with pytest.raises(ValueError, match="mesh_devices"):
            write_config(tmp_path / "skelly_config.toml", 4, n)
        cfg = write_config(tmp_path / "skelly_config.toml", 4, 1)
        text = open(cfg).read().replace("mesh_devices = 1",
                                        "mesh_devices = 0")
        assert "mesh_devices = 0" in text
        open(cfg, "w").write(text)
        # ... and the builder where one is read
        with pytest.raises(ValueError, match=r"mesh_devices.*>= 1"):
            builder.build_simulation(cfg)
        return
    cfg = write_config(tmp_path / "skelly_config.toml", 4, n)
    with pytest.raises(ValueError) as err:
        builder.build_simulation(cfg)
    # both numbers, in words
    assert "mesh_devices = 64" in str(err.value)
    assert f"{len(jax.devices())} device(s)" in str(err.value)
