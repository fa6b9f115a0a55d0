"""The fiber blocks' two ways to multiply (`ops.block_df`, `fc.matvec`,
`fc.apply_fiber_force`).

In the lo operator of the mixed tier on a TPU the fiber-local dense products
run as double-float (hi, lo float32) words through a fused Pallas tile; the
hi operator, the full tier and every CPU run keep the float64 ``dot``. These
tests hold, on the CPU with the tile in interpret mode and by values and
counts alone: (a) the tile against the float64 ``dot`` on blocks `prep`
really makes, relative to ``|A| |x|``; (b) a mixed step with the tile forced
against the same step on the ``dot``, one device and a mesh of four; (c) the
program's structure: no float64 ``dot_general`` under ``fiber`` in the
Krylov loop of the forced step, the full tier untouched by the override;
(d) the ``fiber_ops`` announcement, once a build.
"""

import dataclasses
import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_block_inverse as scenes
import test_mesh_run as mesh_fixture
from test_mesh_run import reference  # noqa: F401 - the plain reference, a fixture
from skellysim_tpu.config import Config, Fiber
from skellysim_tpu.fibers import container as fc
from skellysim_tpu.obs import tracer as obs_tracer
from skellysim_tpu.obs.summarize import Summary
from skellysim_tpu.ops import block_df
from skellysim_tpu.system import System
from skellysim_tpu.testing import make_coupled_parts

DT, ETA = scenes.DT, scenes.ETA
#: relative to ``|A| |x|``, row by row. Readings: 3e-15 to 1.1e-14 (the
#: float64 ``dot`` itself: 2e-16 to 9e-16)
TOL = 1e-12


# ------------------------------------------------------------- (a) the tile

@pytest.mark.parametrize("nb,rows,cols,shared", [
    (3, 128, 128, False), (2, 96, 128, False), (1, 72, 96, False),
    (5, 242, 256, True), (4, 64, 64, True)])
def test_block_matvec_df_on_wide_ranging_entries(nb, rows, cols, shared):
    """Entries over ten decades, so that every row cancels: the product the
    words give against NumPy's extended precision."""
    rng = np.random.default_rng(rows + cols)
    shape = (rows, cols) if shared else (nb, rows, cols)
    m = rng.normal(size=shape) * 10.0 ** rng.uniform(-3, 7, size=shape)
    x = rng.normal(size=(nb, cols))
    got = np.asarray(block_df.block_matvec_df(
        block_df.split_words(jnp.asarray(m)), jnp.asarray(x), n_rows=rows,
        interpret=True))
    ml, xl = m.astype(np.longdouble), x.astype(np.longdouble)
    mb = np.broadcast_to(ml, (nb, rows, cols))
    ref = np.einsum("bij,bj->bi", mb, xl)
    scale = np.einsum("bij,bj->bi", np.abs(mb), np.abs(xl))
    assert got.shape == (nb, rows) and got.dtype == np.float64
    assert float(np.max(np.abs(got - ref) / scale)) < TOL


def test_split_words_on_the_host_and_in_a_trace_agree():
    m = np.random.default_rng(0).normal(size=(2, 18, 32)) * 1e6
    host = block_df.split_words(m)
    traced = jax.jit(block_df.split_words)(jnp.asarray(m))
    for h, t in zip(host, traced):
        assert isinstance(h, np.ndarray) and h.dtype == np.float32
        assert h.shape == (2, 24, 128)
        np.testing.assert_array_equal(h, np.asarray(t))
    back = host[0].astype(np.float64) + host[1].astype(np.float64)
    np.testing.assert_allclose(back[:, :18, :32], m, rtol=2.0 ** -47)
    assert not back[:, 18:].any() and not back[:, :, 32:].any()


def _lines(n, n_fibers=3):
    t = np.linspace(0, 1, n)
    origins = np.array([[0, 0, 0], [0.5, 0, 0], [0, 2, 0]], float)
    dirs = np.array([[0, 0, 1], [1, 1, 0], [1, 0, 0]], float)
    dirs /= np.linalg.norm(dirs, axis=1)[:, None]
    return (origins[:n_fibers, None, :]
            + t[None, :, None] * dirs[:n_fibers, None, :])


def _bucket(kind):
    """A bucket through `update_cache` / `update_rhs_and_bc` as the mixed
    tier on a TPU calls them: slot 0 free, slot 1 minus-clamped (and, in
    `_operands`, bound to a body: its 7 link rows carry values), slot 2
    inactive. ``static32``: 4n = 128, on the lane grid; ``off_grid24``:
    4n = 96 and 3n = 72, padded to the lanes where the words are formed;
    ``runtime_padded``: 24 live nodes in a capacity of 32, the resolution's
    matrices riding the group as data."""
    n = {"static32": 32, "off_grid24": 24, "runtime_padded": 24}[kind]
    group = fc.make_group(
        _lines(n), lengths=1.0, bending_rigidity=0.0025, radius=0.0125,
        force_scale=-0.05, minus_clamped=np.array([False, True, False]),
        dtype=jnp.float64)
    group = group._replace(active=jnp.array([True, True, False]))
    if kind == "runtime_padded":
        group = fc.grow_node_capacity(group, 32)
    rng = np.random.default_rng(n)
    shape = (3, group.n_nodes, 3)
    caches = fc.update_cache(group, DT, ETA)
    v = jnp.asarray(rng.normal(size=shape) * 0.01)
    f = jnp.asarray(rng.normal(size=shape) * 0.01)
    caches = fc.update_rhs_and_bc(group, caches, DT, ETA, v, f, f,
                                  precond_dtype=jnp.float32, df_words=True)
    return group, caches


@functools.lru_cache(maxsize=None)
def _operands(kind):
    group, caches = _bucket(kind)
    rng = np.random.default_rng(7)
    n = group.n_nodes
    x = jnp.asarray(rng.normal(size=(3, 4 * n)))
    # the flows of the lo operator: float32 values in the vectors' dtype
    v = jnp.asarray(rng.normal(size=(3, n, 3)).astype(np.float32),
                    dtype=jnp.float64)
    vb = jnp.asarray(rng.normal(size=(3, 7))
                     * np.array([0.0, 1.0, 0.0])[:, None])
    out = {df: (np.asarray(fc.matvec(group, caches, x, v, vb, df=df)),
                np.asarray(fc.apply_fiber_force(group, caches, x, df=df)))
           for df in (False, True)}
    return group, caches, x, v, out


@pytest.mark.parametrize("kind,slot,name", [
    ("static32", 0, "free"), ("static32", 1, "clamped_bound_to_a_body"),
    ("static32", 2, "inactive"),
    ("off_grid24", 0, "free"), ("off_grid24", 1, "clamped_bound_to_a_body"),
    ("runtime_padded", 0, "free"),
    ("runtime_padded", 1, "clamped_bound_to_a_body")])
def test_tile_agrees_with_f64_dot_on_real_blocks(kind, slot, name):
    group, caches, x, v, out = _operands(kind)
    assert caches.df is not None
    n = group.n_nodes
    mats = group.mats
    (mv_dot, f_dot), (mv_df, f_df) = out[False], out[True]
    assert mv_df.dtype == f_df.dtype == np.float64
    if name == "inactive":
        # the slot's block is the identity: its input comes back exactly
        np.testing.assert_array_equal(mv_df[slot], np.asarray(x[slot]))
        np.testing.assert_array_equal(mv_dot[slot], np.asarray(x[slot]))
        return
    absx = np.abs(np.asarray(x[slot]))
    vs = np.asarray(v[slot]) * (np.asarray(mats.node_mask)[:, None]
                                if hasattr(mats, "node_mask") else 1.0)
    xs = np.asarray(caches.xs[slot])
    d1 = np.abs(np.asarray(mats.D1)) * 2.0 / float(group.length_prev[slot])
    vT = np.concatenate([np.abs(vs).T.ravel(),
                         d1 @ np.abs(xs * vs).sum(axis=1)])
    scale = np.abs(np.asarray(caches.A_bc[slot])) @ absx
    scale[:4 * n - 14] += np.abs(np.asarray(mats.P_down)) @ vT
    assert float(np.max(np.abs(mv_df[slot] - mv_dot[slot]) / scale)) < TOL
    # rows of the force operator that padded nodes own are zero: 0 / 0
    f_scale = np.abs(np.asarray(caches.force_op[slot])) @ absx
    f_err = np.abs(f_df[slot] - f_dot[slot]).T.ravel()
    assert float(np.max(f_err / np.maximum(f_scale, 1e-300))) < TOL
    assert np.all(f_err[f_scale == 0.0] == 0.0)


def test_words_are_formed_only_where_asked():
    group, caches = _bucket("static32")
    assert caches.df.A_bc[0].shape == (3, 128, 128)
    assert caches.df.force_op[1].shape == (3, 96, 128)
    assert caches.df.P_down[0].shape == (120, 128)   # 4n - 14 = 114 rows
    assert caches.df.D1[0].shape == (32, 128)
    assert all(w.dtype == jnp.float32 for pair in caches.df for w in pair)
    plain = fc.update_rhs_and_bc(
        group, fc.update_cache(group, DT, ETA), DT, ETA,
        jnp.zeros((3, 32, 3)), jnp.zeros((3, 32, 3)), jnp.zeros((3, 32, 3)))
    assert plain.df is None
    # without words `df=True` is the float64 dot, bit for bit
    x = jnp.ones((3, 128))
    np.testing.assert_array_equal(
        np.asarray(fc.apply_fiber_force(group, plain, x, df=True)),
        np.asarray(fc.apply_fiber_force(group, plain, x)))


# ----------------------------------------------------------------- (b) steps

def _two_steps(scene, params, fiber_ops):
    system, state = scene(params)
    system._fiber_ops = fiber_ops
    infos = []
    for _ in range(2):
        state, _, info = system.step(state)
        infos.append((int(info.iters), int(info.refines),
                      float(info.residual_true), bool(info.converged)))
    return infos


@pytest.mark.parametrize("name", sorted(scenes.STEP_SCENES))
def test_mixed_step_with_the_tile_holds_sweeps_and_iterations(name):
    """Readings (iterations / sweeps a step, tile against ``dot``): free
    9, 8 / 2 against 9, 8 / 2; fiber + body + shell 6, 7 / 2 against 7, 7
    / 2; clamped on a shell 24, 22 / 3 against 24, 24 / 3."""
    scene, params = scenes.STEP_SCENES[name]
    got = _two_steps(scene, params, "df_tile")
    want = _two_steps(scene, params, None)
    for (iters, sweeps, res, ok), (r_iters, r_sweeps, r_res, r_ok) in zip(
            got, want):
        assert ok and r_ok
        assert res <= params.gmres_tol and r_res <= params.gmres_tol
        assert sweeps == r_sweeps, (got, want)
        assert abs(iters - r_iters) <= 2, (got, want)


def _mesh_config(tmp_path):
    cfg = Config()
    p = cfg.params
    p.dt_initial = p.dt_max = p.dt_write = mesh_fixture.DT
    p.t_final = 1e6
    p.gmres_tol = mesh_fixture.TOL
    p.adaptive_timestep_flag = False
    p.pair_evaluator = "ring"
    p.solver_precision = "mixed"
    p.mesh_devices = mesh_fixture.N_DEV
    rng = np.random.default_rng(100)
    for _ in range(6):
        fib = Fiber(n_nodes=mesh_fixture.N_NODES, length=1.0,
                    bending_rigidity=0.0025, radius=0.0125,
                    force_scale=-0.05)
        fib.x = mesh_fixture._arc(rng, 1.6).ravel().tolist()
        cfg.fibers.append(fib)
    path = str(tmp_path / "skelly_config.toml")
    cfg.save(path)
    return path


def test_mesh_step_with_the_tile(tmp_path, monkeypatch, reference):  # noqa: F811
    """Six bent fibers on four devices in the mixed tier (two slots a
    device, two of the eight padding): two steps of the mesh program with
    the tile against the same on the ``dot``, and the forced run against
    the benchmark's plain reference."""
    path = _mesh_config(tmp_path)
    runs = {}
    for ops in ("df_tile", None):
        monkeypatch.setattr(System, "_fiber_ops", ops)
        work = tmp_path / str(ops)
        work.mkdir()
        tr = obs_tracer.Tracer()
        with obs_tracer.use(tr):
            _, frames, rows = mesh_fixture.run_steps(path, str(work), calls=2)
        (ev,) = [e for e in tr.events if e["ev"] == "fiber_ops"]
        runs[ops] = frames, rows, ev
    frames, rows, ev = runs["df_tile"]
    m = 4 * mesh_fixture.N_NODES
    assert (ev["apply"], ev["fibers"], ev["fallback"]) == (
        "df_tile", f"2x{m}x{m}", "-")
    assert runs[None][2]["apply"] == "f64_dot"
    for row, want in zip(rows, runs[None][1]):
        assert row["accepted"] and row["residual_true"] <= mesh_fixture.TOL
        assert row["refines"] == want["refines"]
        assert abs(row["iters"] - want["iters"]) <= 2
    for res in mesh_fixture.reference_residuals(reference, frames):
        assert res <= mesh_fixture.TOL


# ------------------------------------------------------------- (c) structure

def _f64_dots_under_fiber(jaxpr, in_loop=False, found=None):
    """Name stacks of the float64 ``dot_general``s under a ``fiber`` scope
    inside a `while`, through every nested jaxpr."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        stack = str(eqn.source_info.name_stack)
        if (in_loop and eqn.primitive.name == "dot_general"
                and eqn.outvars[0].aval.dtype == jnp.float64
                and "fiber" in stack.split("/")):
            found.append(stack)
        loop = in_loop or eqn.primitive.name == "while"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _f64_dots_under_fiber(sub, loop, found)
    return found


def _step_jaxpr(precision, fiber_ops, scene=scenes._free_scene, **kw):
    system, state = scene(
        dataclasses.replace(scenes.FREE, solver_precision=precision), **kw)
    system._fiber_ops = fiber_ops
    return jax.make_jaxpr(system._solve_impl)(state)


def test_forced_mixed_step_multiplies_no_f64_block_in_the_krylov_loop():
    """With the tile the only float64 ``dot``s left under ``fiber`` inside
    a loop are the hi operator's (``refine``: every step's acceptance);
    on the ``dot`` the Krylov loop's own are there too."""
    stacks = _f64_dots_under_fiber(_step_jaxpr("mixed", "df_tile",
                                               n_fibers=3).jaxpr)
    assert stacks and all("refine" in s.split("/") for s in stacks)
    plain = _f64_dots_under_fiber(_step_jaxpr("mixed", None,
                                              n_fibers=3).jaxpr)
    assert any("refine" not in s.split("/") for s in plain)
    assert "pallas_call" in str(_step_jaxpr("mixed", "df_tile", n_fibers=3))


def test_full_tier_never_takes_the_tile():
    """The full tier's program does not depend on the override: the same
    text with it and without, and no tile in it."""
    forced = str(_step_jaxpr("full", "df_tile", n_fibers=3))
    assert forced == str(_step_jaxpr("full", None, n_fibers=3))
    assert "pallas_call" not in forced


# ---------------------------------------------------------- (d) announcement

def _announced(system, state, caplog):
    tr = obs_tracer.Tracer()
    with obs_tracer.use(tr), caplog.at_level(logging.INFO, "skellysim_tpu"):
        jax.eval_shape(system._solve_impl, state)
    (ev,) = [e for e in tr.events if e["ev"] == "fiber_ops"]
    return ev


@pytest.mark.parametrize("precision,fiber_ops,want", [
    ("mixed", "df_tile", ("df_tile", "float32x2", "-")),
    ("mixed", None, ("f64_dot", "float64", "backend_cpu")),
    ("mixed", "f64_dot", ("f64_dot", "float64", "forced")),
    ("full", "df_tile", ("f64_dot", "float64", "full_tier"))])
def test_fiber_ops_is_announced_once_a_build(caplog, precision, fiber_ops,
                                             want):
    params = dataclasses.replace(scenes.MIXED, dt_initial=0.1,
                                 solver_precision=precision)
    system, state = scenes._coupled_scene(params)
    system._fiber_ops = fiber_ops
    ev = _announced(system, state, caplog)
    n = 4 * scenes.N
    assert (ev["apply"], ev["dtype"], ev["fallback"]) == want
    assert ev["fibers"] == f"1x{n}x{n}"
    line = (f"fiber_ops apply={want[0]} dtype={want[1]} fibers=1x{n}x{n} "
            f"fallback={want[2]}")
    assert line in caplog.text
    report = Summary()
    report.add_record(ev)
    assert line in report.render()


@pytest.mark.parametrize("n_fibers,want", [
    (3, ("f64_dot", "float64", "3x128x128", "small_bucket")),
    (32, ("df_tile", "float32x2", "32x128x128", "-"))])
def test_a_bucket_too_small_for_a_grid_step_keeps_the_dot(
        monkeypatch, caplog, n_fibers, want):
    """What a TPU run decides, traced here under its backend's name: 32
    blocks of 128 x 128 are the 2 MiB of words one grid step of the tile
    takes; three are not, and say so."""
    system, state = scenes._free_scene(scenes.FREE, n_fibers=n_fibers)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ev = _announced(system, state, caplog)
    assert (ev["apply"], ev["dtype"], ev["fibers"], ev["fallback"]) == want


def test_fiber_ops_without_fibers_says_so(caplog):
    shell, shape, bodies = make_coupled_parts(192, 96, jnp.float64)
    system = System(dataclasses.replace(scenes.MIXED, dt_initial=0.1),
                    shell_shape=shape)
    ev = _announced(system, system.make_state(shell=shell, bodies=bodies),
                    caplog)
    assert (ev["apply"], ev["dtype"], ev["fibers"], ev["fallback"]) == (
        "-", "-", "-", "no_fibers")
