#!/usr/bin/env python3
"""The quickest proof that skellysim_tpu still starts on the chip.

One process, one TPU. Drives the main path once through the entry points a
user calls — config dataclasses -> `skellysim_tpu.precompute.main` ->
`skellysim_tpu.cli.main` -> `io.trajectory.TrajectoryReader` — on

* the walkthrough scene at upstream's documented widths (one 64-node
  fiber, one 400-node sphere body under a constant force, a 6,000-node
  spherical periphery, gmres_tol 1e-10: the only solve upstream publishes,
  7 iterations), four steps;
* `examples/free_fibers_10k` cut to 1,024 fibers x 64 nodes (65,536 nodes,
  ~4.3 Gpairs per matvec — the pair-tile hot loop one fiber never enters),
  two steps, once with kernel_impl="exact" (XLA's tile, named: the default
  "auto" is the Pallas tile on a chip) and once with kernel_impl="pallas";

and checks, not only prints: every step's explicit residual <= gmres_tol,
the body moves along its force as slowly as the wall makes it, and the
on-chip gates that used to live in tests/test_tpu_device.py (Stokes drag of
a 600-node sphere within 1e-6, the f64 Stokeslet against the NumPy oracle
below within 5e-9, Pallas-vs-exact flows within 1e-5, double-float Pallas
tiles within 1e-11).

`--chips 4` runs ONLY the mesh path and what it is compared with: the fused
RDMA ring against the one-device kernel, and one coupled step through
`System.step_spmd` on `parallel.make_mesh(4)` against `System.step`.

Every earlier stdout line is one JSON object worth keeping; the LAST line is
exactly `{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.
Without a TPU the script prints the reason and exits non-zero; it has no
other mode. `tests/test_chip_smoke.py` walks these same functions on the CPU
at toy sizes, to find wrong paths before a chip call.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import logging
import math
import os
import shutil
import statistics
import sys
import tempfile
import time

#: upstream's published walkthrough solve (getting_started.rst:96-100)
UPSTREAM_WALKTHROUGH_ITERS = 7

#: sizes of the run (upstream's documented widths; the fiber scene's cut is
#: printed) and the two gates that depend on them: a coarser sphere or shell
#: (the CPU test's toy sizes) resolves the drag and the wall less well
REAL = dict(shell_n=6000, body_n=400, fiber_nodes=64, walk_steps=4,
            drag_n=600, drag_gate=1e-6, cavity_gate=1e-2,
            n_fibers=1024, fiber_steps=2, flow_targets=2048,
            mesh_fibers=256, mesh_fused_fibers=32, mesh_shell_n=1024,
            mesh_body_n=400, ring_rows=1024)

#: one-device vs four-device coupled step, mixed precision at gmres_tol
#: 1e-10: both sides converge the same f64 system, so positions and
#: solutions agree far below the f32 tile noise (1e-7 relative)
MESH_PARITY_GATE = 1e-7

FAILURES: list[str] = []
COMPILE = {"backend_compile_s": 0.0, "compiles": 0, "cache_hits": 0,
           "cache_misses": 0}


#: every line is also kept here (`chiprun` brings the directory back)
OUT_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "chiprun_out")
_out = None


def emit(**fields) -> None:
    line = json.dumps(fields, default=float)
    print(line, flush=True)
    if _out is not None:
        _out.write(line + "\n")
        _out.flush()


def check(ok: bool, what: str, **detail) -> bool:
    ok = bool(ok)
    emit(check=what, ok=ok, **detail)
    if not ok:
        FAILURES.append(what)
    return ok


class _Warnings(logging.Handler):
    """Collects the package's WARNING+ log lines so a phase can print the
    fallbacks it ran behind (ring -> direct, pallas -> exact, native ->
    Python)."""

    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.lines: list[str] = []

    def emit(self, record):
        self.lines.append(record.getMessage())

    def drain(self) -> list[str]:
        out, self.lines = list(dict.fromkeys(self.lines)), []
        return out


WARNINGS = _Warnings()


def _watch_compiles() -> None:
    from jax import monitoring

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            COMPILE["backend_compile_s"] += secs
            COMPILE["compiles"] += 1

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            COMPILE["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            COMPILE["cache_misses"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


@contextlib.contextmanager
def phase(name: str):
    """Time one phase; a caught exception fails the run, never hides. Each
    phase starts from an empty device: the last one's arrays and programs
    are dropped first (the walkthrough alone holds ~5 GB of operators)."""
    import gc

    import jax

    gc.collect()
    jax.clear_caches()
    t0 = time.perf_counter()
    c0 = dict(COMPILE)
    info: dict = {}
    try:
        yield info
    except BaseException as e:  # noqa: BLE001 - SystemExit from a CLI too
        if isinstance(e, KeyboardInterrupt):
            raise
        import traceback

        traceback.print_exc()
        FAILURES.append(f"{name}: {type(e).__name__}: {e}")
        info["error"] = f"{type(e).__name__}: {e}"[:500]
    emit(phase=name, seconds=time.perf_counter() - t0,
         compile_seconds=COMPILE["backend_compile_s"] - c0["backend_compile_s"],
         compiles=COMPILE["compiles"] - c0["compiles"],
         cache_hits=COMPILE["cache_hits"] - c0["cache_hits"],
         peak_bytes_in_use=_peak_bytes(), warnings=WARNINGS.drain(), **info)


# --------------------------------------------------------------- oracles

def _pairs(r_src, r_trg):
    """Displacements [t, s, 3] and 1/r [t, s] (0 on coincident pairs)."""
    import numpy as np

    d = r_trg[:, None, :] - r_src[None, :, :]
    r2 = np.sum(d * d, axis=-1)
    return d, np.where(r2 > 0, 1.0 / np.sqrt(np.where(r2 > 0, r2, 1.0)), 0.0)


def stokeslet_oracle(r_src, r_trg, f_src, eta=1.0):
    """Plain NumPy f64 Stokeslet sum — independent of the package."""
    import numpy as np

    d, rinv = _pairs(r_src, r_trg)
    df = np.einsum("tsk,sk->ts", d, f_src)
    return (np.einsum("ts,sk->tk", rinv, f_src)
            + np.einsum("ts,tsk->tk", df * rinv**3, d)) / (8 * np.pi * eta)


def stresslet_oracle(r_src, r_trg, S, eta=1.0):
    import numpy as np

    d, rinv = _pairs(r_src, r_trg)
    dSd = np.einsum("tsi,sij,tsj->ts", d, S, d)
    return np.einsum("ts,tsk->tk", -3.0 * dSd * rinv**5, d) / (8 * np.pi * eta)


def rel_err(a, b) -> float:
    import numpy as np

    a, b = np.asarray(a, dtype=np.float64), np.asarray(b, dtype=np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


# ----------------------------------------------------------------- gates

def gate_kernels(seed: int) -> None:
    """f64 tile vs the NumPy oracle (5e-9), Mosaic Pallas tiles vs the XLA
    tiles (1e-5), double-float Pallas tiles vs the oracle (1e-11: three
    orders inside the 5e-9 backend-agreement gate)."""
    import jax.numpy as jnp
    import numpy as np

    from skellysim_tpu.ops import kernels

    rng = np.random.default_rng(seed)
    r_src = rng.uniform(-1, 1, (256, 3))
    r_trg = rng.uniform(-1, 1, (199, 3))
    f = rng.standard_normal((256, 3))
    dev = kernels.stokeslet_direct(jnp.asarray(r_src), jnp.asarray(r_trg),
                                   jnp.asarray(f), 1.0)
    check(dev.dtype == jnp.float64, "f64 stokeslet stays float64",
          dtype=str(dev.dtype))
    err = rel_err(dev, stokeslet_oracle(r_src, r_trg, f))
    check(err <= 5e-9, "f64 stokeslet vs numpy oracle <= 5e-9", err=err)

    r = jnp.asarray(rng.uniform(-2, 2, (2048, 3)), jnp.float32)
    f32 = jnp.asarray(rng.standard_normal((2048, 3)), jnp.float32)
    S32 = jnp.asarray(rng.standard_normal((2048, 3, 3)), jnp.float32)
    e1 = rel_err(kernels.stokeslet_direct(r, r, f32, 1.3, impl="pallas"),
                 kernels.stokeslet_direct(r, r, f32, 1.3))
    e2 = rel_err(kernels.stresslet_direct(r, r, S32, 1.3, impl="pallas"),
                 kernels.stresslet_direct(r, r, S32, 1.3))
    check(e1 < 1e-5, "pallas stokeslet vs exact < 1e-5", err=e1)
    check(e2 < 1e-5, "pallas stresslet vs exact < 1e-5", err=e2)

    r_s = rng.uniform(-5, 5, (1024, 3))
    r_t = np.concatenate([r_s[:128], rng.uniform(-5, 5, (517, 3))], axis=0)
    fd = rng.standard_normal((1024, 3))
    Sd = rng.standard_normal((1024, 3, 3))
    e3 = rel_err(kernels.stokeslet_direct(
        jnp.asarray(r_s), jnp.asarray(r_t), jnp.asarray(fd), 1.0,
        impl="pallas_df"), stokeslet_oracle(r_s, r_t, fd))
    e4 = rel_err(kernels.stresslet_direct(
        jnp.asarray(r_s), jnp.asarray(r_t), jnp.asarray(Sd), 1.0,
        impl="pallas_df"), stresslet_oracle(r_s, r_t, Sd))
    check(e3 < 1e-11, "pallas_df stokeslet vs numpy oracle < 1e-11", err=e3)
    check(e4 < 1e-11, "pallas_df stresslet vs numpy oracle < 1e-11", err=e4)


def gate_drag(sz: dict, info: dict) -> None:
    """Free-space Stokes drag of a forced sphere, mixed precision at
    gmres_tol 1e-10, within 1e-6 of F / (6 pi eta R). The same small step
    then re-tests docs/performance.md's claim that `block_until_ready`
    returns before the program finished: time it both ways."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from skellysim_tpu.bodies import bodies as bd
    from skellysim_tpu.params import Params
    from skellysim_tpu.periphery.precompute import precompute_body
    from skellysim_tpu.system import System

    eta, radius, force = 1.0, 0.5, 1.0
    pre = precompute_body("sphere", sz["drag_n"], radius=radius)
    bodies = bd.make_group(
        pre["node_positions_ref"], pre["node_normals_ref"],
        pre["node_weights"], position=np.zeros((1, 3)),
        external_force=np.array([[0.0, 0.0, force]]),
        radius=np.array([radius]), kind="sphere", dtype=jnp.float64)
    params = Params(eta=eta, dt_initial=0.1, t_final=1.0, gmres_tol=1e-10,
                    solver_precision="mixed", adaptive_timestep_flag=False)
    system = System(params)
    state = system.make_state(bodies=bodies)
    new_state, _, step_info = system.step(state)

    r_eff = np.linalg.norm(np.asarray(pre["node_positions_ref"])[0])
    v_theory = force / (6 * np.pi * eta * r_eff)
    v = float(new_state.bodies.velocity[0, 2])
    info.update(iters=int(step_info.iters),
                residual_true=float(step_info.residual_true),
                refines=int(step_info.refines))
    check(bool(step_info.converged)
          and float(step_info.residual_true) <= 1e-10,
          "drag step converged, explicit residual <= 1e-10",
          residual_true=float(step_info.residual_true))
    gate = sz["drag_gate"]
    check(abs(1 - v / v_theory) < gate, f"stokes drag within {gate:g}",
          drag_rel_err=abs(1 - v / v_theory), nodes=sz["drag_n"])

    def timed(sync):
        t0 = time.perf_counter()
        out = system.step(state)
        sync(out)
        t1 = time.perf_counter()
        float(out[2].residual)         # whatever is left after `sync`
        return t1 - t0, time.perf_counter() - t1

    block = [timed(lambda o: o[2].residual.block_until_ready())
             for _ in range(5)]
    fetch = [timed(lambda o: float(o[2].residual)) for _ in range(5)]
    block_all = [timed(jax.block_until_ready) for _ in range(5)]
    info["sync_retest"] = {
        "what": "seconds for one step: until the sync returned, then the "
                "host fetch that followed it (medians of 5)",
        "block_until_ready_one_leaf": [statistics.median(x)
                                       for x in zip(*block)],
        "block_until_ready_all": [statistics.median(x)
                                  for x in zip(*block_all)],
        "host_fetch": [statistics.median(x) for x in zip(*fetch)]}


# ------------------------------------------------------------ main path

def _run_cli(cfg_path: str, workdir: str, tag: str):
    """`precompute` was done by the caller; run the CLI in-process and read
    back its metrics and trajectory."""
    from skellysim_tpu import cli
    from skellysim_tpu.io.trajectory import TrajectoryReader

    metrics = os.path.join(workdir, f"{tag}_metrics.jsonl")
    cli.main([f"--config-file={cfg_path}", "--overwrite",
              f"--metrics-file={metrics}"])
    steps = [json.loads(ln) for ln in open(metrics)]
    reader = TrajectoryReader(os.path.join(os.path.dirname(cfg_path),
                                           "skelly_sim.out"))
    return steps, reader


def _step_summary(steps: list, tol: float, info: dict) -> None:
    walls = [s["wall_s"] for s in steps]
    steady = statistics.median(walls[1:]) if len(walls) > 1 else None
    info.update(
        steps=len(steps), gmres_tol=tol,
        iters=[s["iters"] for s in steps],
        refines=[s["refines"] for s in steps],
        residual_true=[s["residual_true"] for s in steps],
        step_wall_s=walls, steady_step_s=steady,
        # the first step's wall holds trace + compile + one step
        first_step_minus_steady_s=(walls[0] - steady if steady else None))
    check(all(s["accepted"] and s["health"] == 0
              and not s["loss_of_accuracy"]
              and s["residual"] <= tol and s["residual_true"] <= tol
              for s in steps),
          f"every step converged with explicit residual <= {tol:g}",
          worst=max(s["residual_true"] for s in steps))


def _tiles_in_step(cfg_path: str, lower: bool) -> dict:
    """What a run of this config resolves to: precision and tile names, and
    — with ``lower``, for scenes cheap to build twice — how many Mosaic
    kernels the step program's lowering holds (trace + lower only)."""
    from skellysim_tpu.builder import build_simulation
    from skellysim_tpu.config import schema
    from skellysim_tpu.params import resolve_precision
    from skellysim_tpu.system import System

    params = schema.to_runtime_params(schema.load_config(cfg_path).params)
    out = {"solver_precision": resolve_precision(params.solver_precision,
                                                 True),   # the CLI's f64 state
           "kernel_impl": params.kernel_impl,
           "refine_pair_impl": System(params)._refine_impl,
           "pair_evaluator": params.pair_evaluator}
    if lower:
        system, state, _ = build_simulation(cfg_path)
        state = system.ensure_flight(state)
        pair, anchors = system._pair_args(state)
        out["mosaic_kernels_in_step"] = system._solve_jit.trace(
            state, pair=pair,
            pair_anchors=anchors).lower().as_text().count("tpu_custom_call")
    return out


def run_walkthrough(sz: dict, workdir: str, info: dict) -> None:
    import numpy as np

    from skellysim_tpu import precompute
    from skellysim_tpu.config import Body, ConfigSpherical, Fiber

    eta, force, radius, dt = 1.0, 0.5, 0.5, 0.1
    cfg = ConfigSpherical()
    cfg.params.eta = eta
    cfg.params.dt_initial = dt
    cfg.params.dt_write = dt
    cfg.params.t_final = dt * sz["walk_steps"]
    cfg.params.gmres_tol = 1e-10
    cfg.params.adaptive_timestep_flag = False
    cfg.periphery.n_nodes = sz["shell_n"]
    cfg.periphery.radius = 6.0
    cfg.bodies = [Body(position=[0.0, 0.0, 0.0], shape="sphere",
                       radius=radius, n_nodes=sz["body_n"],
                       external_force=[0.0, 0.0, force])]
    fib = Fiber(n_nodes=sz["fiber_nodes"], length=1.0,
                bending_rigidity=0.01, radius=0.0125)
    fib.fill_node_positions(np.array([0.0, 3.0, 0.0]),
                            np.array([0.0, 0.0, 1.0]))
    cfg.fibers = [fib]
    scene = os.path.join(workdir, "walkthrough")
    os.makedirs(scene)
    cfg_path = os.path.join(scene, "skelly_config.toml")
    cfg.save(cfg_path)

    # the host inverse: `--device-operator` needs 11.6 GB for the on-device
    # inverse at 6,000 nodes and does not fit a v5e (PR 22's first chip run)
    t0 = time.perf_counter()
    precompute.main([cfg_path])
    info.update(precompute_seconds=time.perf_counter() - t0,
                precompute_operator="host")

    steps, reader = _run_cli(cfg_path, workdir, "walkthrough")
    _step_summary(steps, cfg.params.gmres_tol, info)
    info["upstream_iters"] = UPSTREAM_WALKTHROUGH_ITERS
    check(len(steps) == sz["walk_steps"],
          f"walkthrough took exactly {sz['walk_steps']} steps for t_final = "
          f"{sz['walk_steps']} dt", steps=len(steps))

    check(len(reader) == len(steps) + 1,
          "trajectory holds the initial frame and one frame per step",
          frames=len(reader))
    z = []
    for i in (0, -1):
        reader.load_frame(i)
        z.append(np.asarray(reader["bodies"][0]["position_"],
                            dtype=float).ravel())
    reader.close()
    speed = (z[1][2] - z[0][2]) / (reader.times[-1] - reader.times[0])
    # the discretised body's hydrodynamic radius is its node radius (the
    # drag gate holds it to 1e-6), and the wall is where the shell's nodes
    # are; a sphere at the centre of a spherical cavity then moves at the
    # free-space speed over Haberman & Sayre's factor K(a / R)
    a = float(np.linalg.norm(np.load(os.path.join(
        scene, cfg.bodies[0].precompute_file))["node_positions_ref"][0]))
    wall = float(np.linalg.norm(np.load(os.path.join(
        scene, cfg.periphery.precompute_file))["nodes"], axis=1).mean())
    lam = a / wall
    K = (1 - lam**5) / (1 - 2.25 * lam + 2.5 * lam**3 - 2.25 * lam**5
                        + lam**6)
    free = force / (6 * math.pi * eta * a)
    info.update(body_speed=speed, free_space_speed=free,
                cavity_speed=free / K, node_radius=a, wall_radius=wall)
    check(0.0 < speed and abs(z[1][0]) + abs(z[1][1]) < 1e-3 * radius
          and abs(speed * K / free - 1) < sz["cavity_gate"],
          "body moves along its force at the speed of a sphere in a "
          f"spherical cavity, within {sz['cavity_gate']:g}", speed=speed,
          free_space=free, cavity=free / K)
    info["tiles"] = _tiles_in_step(cfg_path, lower=False)


def _load_example(name: str):
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "examples", name, "gen_config.py")
    spec = importlib.util.spec_from_file_location(f"example_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fiber_positions(reader, i: int):
    import numpy as np

    reader.load_frame(i)
    return np.stack([np.asarray(f["x_"], dtype=float).reshape(-1, 3)
                     for f in reader["fibers"]])


def run_fibers(sz: dict, workdir: str, seed: int) -> None:
    """The free-fiber scene, XLA's exact tile then Pallas tile, and the two
    compared: flows on the scene's own nodes and the positions the two
    runs end at."""
    import jax.numpy as jnp
    import numpy as np

    from skellysim_tpu.ops import kernels

    full, n = 10_000, sz["n_fibers"]
    box = 20.0 * (n / full) ** (1 / 3)
    emit(cut="examples/free_fibers_10k", n_fibers=[full, n],
         nodes=n * 64, box=[20.0, box],
         why="a smoke, not the benchmark cell: fibers cut 10,000 -> "
             f"{n}, box scaled to keep fibers per volume; fiber width "
             "(64 nodes), tolerance and time step are the example's",
         steps=sz["fiber_steps"])
    gen = _load_example("free_fibers_10k")

    ends, x0 = {}, None
    for impl in ("exact", "pallas"):
        with phase(f"fibers_{impl}") as info:
            cfg = gen.build_config(n_fibers=n, box=box)
            cfg.params.kernel_impl = impl
            cfg.params.t_final = cfg.params.dt_initial * sz["fiber_steps"]
            cfg.params.dt_write = cfg.params.dt_initial
            cfg.params.adaptive_timestep_flag = False
            scene = os.path.join(workdir, f"fibers_{impl}")
            os.makedirs(scene)
            cfg_path = os.path.join(scene, "skelly_config.toml")
            cfg.save(cfg_path)
            steps, reader = _run_cli(cfg_path, workdir, f"fibers_{impl}")
            _step_summary(steps, cfg.params.gmres_tol, info)
            check(len(steps) == sz["fiber_steps"],
                  f"fibers_{impl}: exactly {sz['fiber_steps']} steps",
                  steps=len(steps))
            first, last = (_fiber_positions(reader, i) for i in (0, -1))
            reader.close()
            x0, ends[impl] = first, last
            check(np.isfinite(last).all() and last.shape == (n, 64, 3),
                  f"fibers_{impl}: final frame finite, {n} x 64 nodes",
                  shape=list(last.shape))
            info["tiles"] = _tiles_in_step(cfg_path, lower=True)
            if impl == "pallas":
                check(info["tiles"]["mosaic_kernels_in_step"] > 0,
                      "kernel_impl='pallas': the step holds Mosaic kernels",
                      **info["tiles"])

    with phase("fibers_compare") as info:
        if len(ends) == 2:
            moved = ends["exact"] - x0
            err = rel_err(ends["pallas"] - x0, moved)
            info["displacement_norm"] = float(np.linalg.norm(moved))
            check(err < 1e-5, "pallas run vs exact run: fiber "
                  "displacements agree to 1e-5", err=err)
        rng = np.random.default_rng(seed)
        r = jnp.asarray(x0.reshape(-1, 3), jnp.float32)
        f = jnp.asarray(rng.standard_normal(r.shape), jnp.float32)
        u_p = kernels.stokeslet_direct(r, r, f, 1.0, impl="pallas")
        u_x = kernels.stokeslet_direct(r, r, f, 1.0)
        k = sz["flow_targets"]
        ref = kernels.stokeslet_direct(r.astype(jnp.float64),
                                       r[:k].astype(jnp.float64),
                                       f.astype(jnp.float64), 1.0, impl="df")
        info.update(pairs=int(r.shape[0]) ** 2,
                    pallas_vs_f64=rel_err(u_p[:k], ref),
                    exact_vs_f64=rel_err(u_x[:k], ref))
        check(rel_err(u_p, u_x) < 1e-5, "fiber scene flow: pallas vs exact "
              "tile < 1e-5", err=rel_err(u_p, u_x))


# -------------------------------------------------------------- four chips

def run_mesh(sz: dict, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from skellysim_tpu.obs import tracer as obs_tracer
    from skellysim_tpu.ops import kernels
    from skellysim_tpu.parallel import make_mesh, shard_state
    from skellysim_tpu.parallel.compat import fused_ring_mode
    from skellysim_tpu.parallel.ring import ring_stokeslet, ring_stresslet
    from skellysim_tpu.solver.gmres import history_rows

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from __graft_entry__ import _make_system

    mesh = make_mesh(4)

    with phase("ring_fused") as info:
        n = 4 * sz["ring_rows"]
        rng = np.random.default_rng(seed)
        r = jnp.asarray(rng.uniform(-2, 2, (n, 3)), jnp.float32)
        f = jnp.asarray(rng.standard_normal((n, 3)), jnp.float32)
        S = jnp.asarray(rng.standard_normal((n, 3, 3)), jnp.float32)
        info.update(ring_mode=fused_ring_mode("pallas"), rows_per_shard=n // 4)
        u_f = kernels.stokeslet_direct(r, r, f, 1.0)
        u_S = kernels.stresslet_direct(r, r, S, 1.0)
        # "exact" is the ppermute ring around the XLA tile: the f32 pair
        # flows of the mesh step's inner operator under that name, held
        # against the one-device step's (the step parity below converges both
        # sides to one f64 system and cannot see an inner operator that is off)
        for impl in ("exact", "pallas"):
            e1 = rel_err(ring_stokeslet(r, r, f, 1.0, mesh=mesh, impl=impl),
                         u_f)
            e2 = rel_err(ring_stresslet(r, r, S, 1.0, mesh=mesh, impl=impl),
                         u_S)
            check(e1 < 1e-5, f"ring stokeslet ({impl}) on 4 devices vs "
                  "one-device exact tile < 1e-5", err=e1, **info)
            check(e2 < 1e-5, f"ring stresslet ({impl}) on 4 devices vs "
                  "one-device exact tile < 1e-5", err=e2)
        check(info["ring_mode"] == "fused",
              "the fused RDMA ring kernel is what executed",
              mode=info["ring_mode"])

    # the third scene is small enough that its rings pass `fused_ring_fits`
    # (<= 1,024 target rows a shard): there the fused kernel runs inside
    # the solver loop, instance after instance
    for impl, n_fibers in (("exact", sz["mesh_fibers"]),
                           ("pallas", sz["mesh_fibers"]),
                           ("pallas", sz["mesh_fused_fibers"])):
        with phase(f"step_spmd_{impl}_{n_fibers}") as info:
            def build():
                return _make_system(
                    n_fibers, sz["fiber_nodes"], jnp.float64,
                    coupled=True, clear_of_body=True,
                    solver_precision="mixed", kernel_impl=impl,
                    shell_n=sz["mesh_shell_n"], body_n=sz["mesh_body_n"])

            system, state = build()
            t0 = time.perf_counter()
            ref_state, ref_sol, ref_info = system.step(state)
            ref_x = np.asarray(ref_state.fibers.x)
            ref_sol = np.asarray(ref_sol)
            info["one_device_seconds"] = time.perf_counter() - t0

            sys_sp, state_sp = build()
            state_sp = shard_state(state_sp, mesh)
            placed = len(state_sp.fibers.x.sharding.device_set)
            tr = obs_tracer.Tracer()
            t0 = time.perf_counter()
            with obs_tracer.use(tr):
                new_state, sol, sp_info = sys_sp.step_spmd(state_sp, mesh)
                x = np.asarray(new_state.fibers.x)
            info["four_device_seconds"] = time.perf_counter() - t0
            faults = [e for e in tr.events if e["ev"] == "fault"]
            info.update(
                fibers=n_fibers, kernel_impl=impl,
                ring_mode=fused_ring_mode(impl),
                rings_fused=[f"{e['kind']}-{e['n_trg']}x{e['n_src']}"
                             for e in tr.events if e["ev"] == "ring_fused"],
                ring_fallbacks=[e.get("reason") for e in faults
                                if e.get("kind") == "fused_ring_fallback"],
                iters=[int(ref_info.iters), int(sp_info.iters)],
                # refinement sweeps, and the one-device step's rows of
                # [cumulative iterations, inner exit residual, explicit
                # residual]: a residual near the tolerance after a sweep is
                # where rounding decides whether another sweep runs
                refines=[int(ref_info.refines), int(sp_info.refines)],
                one_device_sweeps=history_rows(ref_info.history,
                                               ref_info.cycles),
                residual_true=[float(ref_info.residual_true),
                               float(sp_info.residual_true)])
            if impl == "pallas" and n_fibers == sz["mesh_fused_fibers"]:
                check(info["rings_fused"] and not info["ring_fallbacks"],
                      "every ring of the small pallas step ran as the fused "
                      "kernel", rings_fused=info["rings_fused"],
                      ring_fallbacks=info["ring_fallbacks"])
            check(placed == 4 and
                  len(new_state.fibers.x.sharding.device_set) == 4,
                  "fiber leaves live on four distinct devices",
                  devices=placed)
            check(bool(sp_info.converged)
                  and float(sp_info.residual_true) <= 1e-10
                  and float(ref_info.residual_true) <= 1e-10,
                  "both steps converged, explicit residual <= 1e-10",
                  residual_true=info["residual_true"])
            gap_x = float(np.abs(x - ref_x).max() / np.abs(ref_x).max())
            gap_s = rel_err(np.asarray(sol), ref_sol)
            check(gap_x <= MESH_PARITY_GATE and gap_s <= MESH_PARITY_GATE,
                  f"step_spmd on 4 devices vs System.step on one <= "
                  f"{MESH_PARITY_GATE:g}", positions=gap_x, solution=gap_s)


# --------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the mesh path (step_spmd + fused ring) "
                         "and what it is compared with")
    ap.add_argument("--seed", type=int, default=22)
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu":
        emit(ok=False, reason=f"no TPU: jax.devices()[0].platform is "
             f"{dev.platform!r}; this script measures nothing off the chip")
        return 1
    if len(jax.devices()) < args.chips:
        emit(ok=False, reason=f"--chips {args.chips} needs {args.chips} "
             f"devices, jax sees {len(jax.devices())}")
        return 1

    import skellysim_tpu
    from skellysim_tpu.utils.bootstrap import enable_compilation_cache

    global _out
    os.makedirs(OUT_DIR, exist_ok=True)
    _out = open(os.path.join(OUT_DIR, f"chip_smoke_{args.chips}.jsonl"), "a")
    jax.config.update("jax_enable_x64", True)
    logging.getLogger("skellysim_tpu").addHandler(WARNINGS)
    _watch_compiles()
    emit(start="chip_smoke", chips=args.chips, device=device,
         jax=jax.__version__,
         package=os.path.dirname(skellysim_tpu.__file__),
         compile_cache_dir=enable_compilation_cache("auto"),
         cache_dir_from_env=os.environ.get("JAX_COMPILATION_CACHE_DIR"))

    t_all = time.perf_counter()
    if args.chips == 4:
        run_mesh(REAL, args.seed)
    else:
        workdir = tempfile.mkdtemp(prefix="chip_smoke_")
        try:
            with phase("gate_kernels"):
                gate_kernels(args.seed)
            with phase("gate_drag") as info:
                gate_drag(REAL, info)
            with phase("walkthrough") as info:
                run_walkthrough(REAL, workdir, info)
            run_fibers(REAL, workdir, args.seed)
            from skellysim_tpu.native import load_library

            emit(frame_encoder={name: ("native" if load_library(name)
                                       else "python")
                                for name in ("frameenc", "trajscan")})
        finally:
            shutil.rmtree(workdir, ignore_errors=True)
    emit(total_seconds=time.perf_counter() - t_all, failures=FAILURES,
         **COMPILE)

    if FAILURES:
        emit(ok=False, device=device)
        return 1
    emit(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
