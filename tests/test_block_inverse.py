"""The block preconditioner's two ways to apply a block (`ops.block_precond`).

Where the blocks are stored in a lower precision than the state (the mixed
tier: every run on a TPU) the step forms each block's inverse once, where it
factors, and applies it as one batched matmul; the full tier keeps its LU
factors and `lu_solve`. These tests hold, on the CPU and by counts and values
alone: (a) the stored inverse against `lu_solve` on the same float32 factors,
on real blocks; (b) a mixed step against a reference step whose blocks are
applied by `lu_solve` (built here by handing `factor` the parent's
behaviour, not by a switch in the package): under the tolerance, the same
sweeps, at most 4 iterations more; (c) the program's structure: no
triangular solve inside any loop of the mixed step; (d) the `block_precond`
announcement, once a build, in both tiers and from the mesh step.
"""

import dataclasses
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_mesh_run as mesh_fixture
import test_precond as precond_scenes
from skellysim_tpu.bodies import bodies as bd
from skellysim_tpu.config import Config, Fiber
from skellysim_tpu.fibers import container as fc
from skellysim_tpu.obs import tracer as obs_tracer
from skellysim_tpu.obs.summarize import Summary
from skellysim_tpu.ops import block_precond
from skellysim_tpu.params import Params
from skellysim_tpu.system import System
from skellysim_tpu.testing import make_coupled_parts

N = 32
DT, ETA = 0.005, 1.0
#: relative distance between the stored inverse's product and `lu_solve` on
#: the same float32 factors. Both are float32 approximations of a block whose
#: condition number is ~1e6 (fibers; the body's is 18), and each stands
#: 3e-5 to 2.3e-4 from the float64 solve (fibers; 1e-6 the body): readings
#: over five vectors are 2e-6 to 5.3e-5 (fibers) and 8e-7 to 1.2e-6 (body)
FIBER_TOL, BODY_TOL = 1e-3, 1e-5


def _lu_solve(lu, piv, b):
    return jax.scipy.linalg.lu_solve((lu, piv), b)


def _keep_the_factors(A, precond_dtype=None):
    """`block_precond.factor` as the parent commit had it: LU factors in
    ``precond_dtype``, applied by `lu_solve` in either tier."""
    lu, piv = jax.vmap(jax.scipy.linalg.lu_factor)(
        A if precond_dtype is None else A.astype(precond_dtype))
    return lu, piv, None


# ---------------------------------------------------------------- (a) blocks

@pytest.fixture(scope="module")
def fiber_blocks():
    """A free fiber, a minus-clamped fiber and an inactive slot through
    `update_rhs_and_bc` in the mixed tier, under a random flow and force."""
    rng = np.random.default_rng(0)
    t = np.linspace(0, 1, N)

    def line(origin, d):
        d = np.asarray(d, float) / np.linalg.norm(d)
        return np.asarray(origin, float)[None] + t[:, None] * d[None]

    x = np.stack([line([0, 0, 0], [0, 0, 1]), line([0.5, 0, 0], [1, 1, 0]),
                  line([0, 2, 0], [1, 0, 0])])
    group = fc.make_group(
        x, lengths=1.0, bending_rigidity=0.0025, radius=0.0125,
        force_scale=-0.05, minus_clamped=np.array([False, True, False]),
        dtype=jnp.float64)
    group = group._replace(active=jnp.array([True, True, False]))
    caches = fc.update_cache(group, DT, ETA)
    v = jnp.asarray(rng.normal(size=(3, N, 3)) * 0.01)
    f = jnp.asarray(rng.normal(size=(3, N, 3)) * 0.01)
    caches = fc.update_rhs_and_bc(group, caches, DT, ETA, v, f, f,
                                  precond_dtype=jnp.float32)
    return group, caches


@pytest.mark.parametrize("slot,name", [(0, "free"), (1, "minus_clamped"),
                                       (2, "inactive")])
def test_fiber_inverse_agrees_with_lu_solve(fiber_blocks, slot, name):
    group, caches = fiber_blocks
    assert caches.lu is None and caches.piv is None
    assert caches.inv.dtype == jnp.float32
    assert caches.inv.shape == (3, 4 * N, 4 * N)
    lu, piv = jax.vmap(jax.scipy.linalg.lu_factor)(
        caches.A_bc.astype(jnp.float32))
    rng = np.random.default_rng(1 + slot)
    for _ in range(5):
        x = jnp.asarray(rng.normal(size=(3, 4 * N)))
        out = fc.apply_preconditioner(group, caches, x)
        assert out.dtype == x.dtype
        ref = jax.vmap(_lu_solve)(lu, piv, x.astype(jnp.float32))
        if name == "inactive":
            # the identity inverts to itself: the slot's input comes back
            # as float32 rounds it, bit for bit
            np.testing.assert_array_equal(
                np.asarray(out[slot]),
                np.asarray(x[slot].astype(jnp.float32).astype(x.dtype)))
            np.testing.assert_array_equal(
                np.asarray(caches.inv[slot]), np.eye(4 * N, dtype=np.float32))
        else:
            err = float(jnp.linalg.norm(out[slot] - ref[slot])
                        / jnp.linalg.norm(ref[slot]))
            assert err < FIBER_TOL, (name, err)


def test_body_inverse_agrees_with_lu_solve():
    _, _, bodies = make_coupled_parts(192, 96, jnp.float64)
    mixed = bd.update_cache(bodies, ETA, precond_dtype=jnp.float32)
    full = bd.update_cache(bodies, ETA)
    assert mixed.lu is None and mixed.piv is None
    assert full.inv is None and full.lu.dtype == jnp.float64
    m = 3 * 96 + 6
    assert mixed.inv.shape == (1, m, m) and mixed.inv.dtype == jnp.float32
    # the block itself is not kept: rebuild it from the float64 factors
    low = jnp.tril(full.lu[0], -1) + jnp.eye(m)
    perm = jax.lax.linalg.lu_pivots_to_permutation(full.piv[0], m)
    A = (low @ jnp.triu(full.lu[0]))[jnp.argsort(perm)]
    lu, piv = jax.scipy.linalg.lu_factor(A.astype(jnp.float32))
    rng = np.random.default_rng(7)
    for _ in range(5):
        x = jnp.asarray(rng.normal(size=(1, m)))
        out = bd.apply_preconditioner(bodies, mixed, x)[0]
        ref = _lu_solve(lu, piv, x[0].astype(jnp.float32))
        err = float(jnp.linalg.norm(out - ref) / jnp.linalg.norm(ref))
        assert err < BODY_TOL, err


# ----------------------------------------------------------------- (b) steps

def _free_scene(params, n_fibers=12, box=1.6, seed=3):
    rng = np.random.default_rng(seed)
    origin = rng.uniform(-box / 2, box / 2, (n_fibers, 3))
    d = rng.normal(size=(n_fibers, 3))
    d /= np.linalg.norm(d, axis=1)[:, None]
    x = (origin[:, None, :]
         + np.linspace(0, 1, N)[None, :, None] * d[:, None, :])
    fibers = fc.make_group(x, lengths=1.0, bending_rigidity=0.0025,
                           radius=0.0125, force_scale=-0.05,
                           dtype=jnp.float64)
    system = System(params)
    return system, system.make_state(fibers=fibers)


def _coupled_scene(params):
    """Fiber + body + shell: `test_precond.test_mixed_precision_solve_through_gs`'s."""
    shell, shape, bodies = make_coupled_parts(192, 96, jnp.float64)
    t = np.linspace(0, 1, N)
    x = (np.array([0.0, 3.0, 0.0])[None, :]
         + t[:, None] * np.array([0.0, 0.0, 1.0]))
    fibers = fc.make_group(x[None], lengths=1.0, bending_rigidity=0.01,
                           radius=0.0125, dtype=jnp.float64)
    system = System(params, shell_shape=shape)
    return system, system.make_state(fibers=fibers, shell=shell,
                                     bodies=bodies)


FREE = Params(eta=ETA, dt_initial=DT, t_final=1.0, gmres_tol=1e-8,
              solver_precision="mixed", adaptive_timestep_flag=False)
MIXED = dataclasses.replace(precond_scenes.BASE, solver_precision="mixed")
STEP_SCENES = {
    "free_fibers": (_free_scene, FREE),
    "fiber_body_shell": (_coupled_scene,
                         dataclasses.replace(MIXED, dt_initial=0.1)),
    "clamped_fibers_on_shell": (precond_scenes._clamped_shell_scene, MIXED),
}


def _two_steps(scene, params):
    system, state = scene(params)
    infos = []
    for _ in range(2):
        state, _, info = system.step(state)
        infos.append((int(info.iters), int(info.refines),
                      float(info.residual_true), bool(info.converged)))
    return infos


@pytest.mark.parametrize("name", sorted(STEP_SCENES))
def test_mixed_step_holds_the_iteration_count(monkeypatch, name):
    """Readings (iterations / sweeps a step, inverse against `lu_solve`):
    free 9, 8 / 2 against 7, 7 / 2; fiber + body + shell 7, 7 / 2 against
    7, 6 / 2; clamped 24, 24 / 3 against 21, 21 / 3."""
    scene, params = STEP_SCENES[name]
    got = _two_steps(scene, params)
    monkeypatch.setattr(block_precond, "factor", _keep_the_factors)
    want = _two_steps(scene, params)
    for (iters, sweeps, res, ok), (r_iters, r_sweeps, r_res, r_ok) in zip(
            got, want):
        assert ok and r_ok
        assert res <= params.gmres_tol and r_res <= params.gmres_tol
        assert sweeps == r_sweeps, (got, want)
        assert iters <= r_iters + 4, (got, want)


# ------------------------------------------------------------- (c) structure

def _count(jaxpr, name, in_loop=False):
    """(inside a `while`, outside every `while`) occurrences of the
    primitive ``name``, through every nested jaxpr."""
    inside = outside = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            inside, outside = inside + in_loop, outside + (not in_loop)
        loop = in_loop or eqn.primitive.name == "while"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            a, b = _count(sub, name, loop)
            inside, outside = inside + a, outside + b
    return inside, outside


def _step_jaxpr(precision):
    system, state = _free_scene(
        dataclasses.replace(FREE, solver_precision=precision), n_fibers=3)
    return jax.make_jaxpr(system._solve_impl)(state).jaxpr


def test_mixed_step_solves_no_triangle_inside_a_loop():
    jaxpr = _step_jaxpr("mixed")
    inside, outside = _count(jaxpr, "triangular_solve")
    # every Krylov loop is a `while`; `prep` is straight-line code
    assert inside == 0
    # L and U of the one solve that forms the inverses
    assert outside == 2
    assert sum(_count(jaxpr, "lu")) == 1
    assert sum(_count(jaxpr, "lu_pivots_to_permutation")) == 1


def test_full_step_keeps_lu_solve():
    """The full tier's program is the parent's: the factors in the state's
    dtype, `lu_solve` wherever the preconditioner is applied (the Arnoldi
    body and the cycle's closing `M(y @ V)`), no inverse formed."""
    jaxpr = _step_jaxpr("full")
    inside, outside = _count(jaxpr, "triangular_solve")
    assert (inside, outside) == (4, 0)
    assert sum(_count(jaxpr, "lu")) == 1
    # a permutation per solve
    assert _count(jaxpr, "lu_pivots_to_permutation") == (2, 0)


# ---------------------------------------------------------- (d) announcement

def _announced(system, state, caplog):
    tr = obs_tracer.Tracer()
    with obs_tracer.use(tr), caplog.at_level(logging.INFO, "skellysim_tpu"):
        jax.eval_shape(system._solve_impl, state)
    (ev,) = [e for e in tr.events if e["ev"] == "block_precond"]
    return ev


@pytest.mark.parametrize("precision,apply,dtype", [
    ("mixed", "inverse", "float32"), ("full", "lu_solve", "float64")])
def test_block_precond_is_announced_once_a_build(caplog, precision, apply,
                                                 dtype):
    params = dataclasses.replace(MIXED, dt_initial=0.1,
                                 solver_precision=precision)
    ev = _announced(*_coupled_scene(params), caplog)
    want = dict(apply=apply, dtype=dtype, fibers=f"1x{4 * N}x{4 * N}",
                bodies="1x294x294")
    assert {k: ev[k] for k in want} == want
    line = (f"block_precond apply={apply} dtype={dtype} "
            f"fibers=1x{4 * N}x{4 * N} bodies=1x294x294")
    assert line in caplog.text
    report = Summary()
    report.add_record(ev)
    assert line in report.render()


def test_block_precond_without_fibers_names_the_bodies(caplog):
    shell, shape, bodies = make_coupled_parts(192, 96, jnp.float64)
    system = System(dataclasses.replace(MIXED, dt_initial=0.1),
                    shell_shape=shape)
    ev = _announced(system, system.make_state(shell=shell, bodies=bodies),
                    caplog)
    assert (ev["apply"], ev["dtype"], ev["fibers"], ev["bodies"]) == (
        "inverse", "float32", "-", "1x294x294")


def test_mesh_step_announces_its_blocks(tmp_path):
    """The mesh step (`parallel/spmd.py`) goes through the same functions:
    six bent fibers on four devices in the mixed tier (padded to 8 slots,
    two a device), one step under the tolerance with the stored inverse."""
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
    tr = obs_tracer.Tracer()
    with obs_tracer.use(tr):
        _, _, rows = mesh_fixture.run_steps(path, str(tmp_path), calls=1)
    (ev,) = [e for e in tr.events if e["ev"] == "block_precond"]
    m = 4 * mesh_fixture.N_NODES
    assert (ev["apply"], ev["dtype"], ev["fibers"], ev["bodies"]) == (
        "inverse", "float32", f"2x{m}x{m}", "-")
    assert [e["step"] for e in tr.events if e["ev"] == "mesh"] == ["spmd"]
    (row,) = rows
    assert row["accepted"] and row["residual_true"] <= mesh_fixture.TOL
