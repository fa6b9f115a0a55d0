"""Periphery tests: quadrature accuracy, operator consistency, and an analytic
interior-Stokes oracle.

The physics oracle: a point force F at the center of a rigid no-slip sphere of
radius R has the closed-form interior solution (Stokeslet + stokeson + uniform
completion; classical Lorenz-type result)

    u(x) = k (F/r + (F.x)x/r^3) - (k/R^3)((F.x)x - 2 r^2 F) - (3k/R) F,
    k = 1/(8 pi eta),

which vanishes identically on r = R. The solved shell density must reproduce
this field at interior points to quadrature accuracy.
"""

import numpy as np
import pytest
from scipy.spatial import ConvexHull

import jax
import jax.numpy as jnp

from skellysim_tpu.ops import kernels
from skellysim_tpu.params import Params
from skellysim_tpu.periphery import (periphery as peri, sphere_shape,
                                     surface_quadrature_weights)
from skellysim_tpu.periphery.periphery import PeripheryShape
from skellysim_tpu.system import PointSources, System


def build_sphere_shell(n_nodes, radius, eta=1.0):
    spec = sphere_shape(n_nodes, radius=radius)
    normals = -spec.node_normals  # inward, periphery convention
    tris = ConvexHull(spec.nodes).simplices
    weights = surface_quadrature_weights(spec.nodes, tris, spec.gradh)
    operator, M_inv = peri.build_shell_operator(spec.nodes, normals, weights, eta=eta)
    return peri.make_state(spec.nodes, normals, weights, operator, M_inv)


def test_quadrature_sphere_area():
    spec = sphere_shape(400, radius=1.3)
    tris = ConvexHull(spec.nodes).simplices
    w = surface_quadrature_weights(spec.nodes, tris, spec.gradh)
    exact = 4 * np.pi * 1.3**2
    assert abs(w.sum() - exact) / exact < 5e-5


def test_shell_operator_inverse_consistent():
    shell = build_sphere_shell(200, radius=1.0)
    M = np.asarray(shell.stresslet_plus_complementary)
    M_inv = np.asarray(shell.M_inv)
    err = np.abs(M @ M_inv - np.eye(M.shape[0])).max()
    assert err < 1e-8, err


def analytic_center_force(points, R, eta, F):
    k = 1.0 / (8 * np.pi * eta)
    r = np.linalg.norm(points, axis=1)
    Fx = points @ F
    u = k * (F[None, :] / r[:, None] + Fx[:, None] * points / r[:, None] ** 3)
    u -= (k / R**3) * (Fx[:, None] * points - 2 * (r**2)[:, None] * F[None, :])
    u -= (3 * k / R) * F[None, :]
    return u


def test_point_force_in_sphere_analytic():
    eta = 1.1
    R = 2.0
    F = np.array([0.0, 0.0, 1.0])
    shell = build_sphere_shell(700, radius=R, eta=eta)

    # RHS: the shell cancels the point-source slip velocity at its nodes
    v_shell = np.asarray(kernels.oseen_contract(
        np.zeros((1, 3)), shell.nodes, F[None, :], eta))
    rhs = -v_shell.reshape(-1)

    # solve the second-kind system directly with the precomputed inverse
    density = jnp.asarray(np.asarray(shell.M_inv) @ rhs)

    rng = np.random.default_rng(3)
    pts = rng.uniform(-0.9, 0.9, size=(20, 3))
    pts = pts[np.linalg.norm(pts, axis=1) > 0.4]

    u_ps = np.asarray(kernels.oseen_contract(np.zeros((1, 3)), pts, F[None, :], eta))
    u_shell = np.asarray(peri.flow(shell, jnp.asarray(pts), density, eta))
    u_total = u_ps + u_shell
    u_exact = analytic_center_force(pts, R, eta, F)

    scale = np.abs(u_exact).max()
    err = np.abs(u_total - u_exact).max() / scale
    assert err < 1e-4, err


def test_system_solve_with_shell_matches_direct_inverse():
    """GMRES through the coupled System must reproduce the direct M_inv solve."""
    eta = 1.0
    R = 1.5
    shell = build_sphere_shell(300, radius=R, eta=eta)
    params = Params(eta=eta, dt_initial=1e-3, t_final=1e-3, gmres_tol=1e-12,
                    adaptive_timestep_flag=False)
    system = System(params, shell_shape=PeripheryShape(kind="sphere", radius=R))
    points = PointSources.make(position=[[0.3, 0.0, 0.0]], force=[[0.0, 0.0, 1.0]])
    state = system.make_state(points=points, shell=shell)

    new_state, solution, info = system.step(state)
    assert bool(info.converged)

    v_shell = np.asarray(points.flow(shell.nodes, eta, 0.0))
    direct = np.asarray(shell.M_inv) @ (-v_shell.reshape(-1))
    np.testing.assert_allclose(np.asarray(solution), direct, rtol=1e-8, atol=1e-10)


def test_fiber_steric_force_direction():
    shape = PeripheryShape(kind="sphere", radius=1.0)
    pts = jnp.asarray([[0.0, 0.0, 0.97], [0.0, 0.0, 0.2]])
    f = peri.fiber_steric_force(shape, pts, 20.0, 0.05, skip_first=jnp.asarray(False))
    f = np.asarray(f)
    assert f[0, 2] < 0.0          # pushes the near-wall node inward
    assert abs(f[0, 2]) > abs(f[1, 2])  # decays away from the wall


def test_collision_detection():
    shape = PeripheryShape(kind="sphere", radius=1.0)
    inside = jnp.asarray([[0.0, 0.0, 0.5]])
    outside = jnp.asarray([[0.0, 0.0, 1.01]])
    assert not bool(peri.check_collision(shape, inside, 0.0))
    assert bool(peri.check_collision(shape, outside, 0.0))


def test_fiber_inside_shell_coupled_solve():
    """Fiber + periphery coupled matvec converges and keeps the fiber inside."""
    from skellysim_tpu.fibers import container as fc

    eta = 1.0
    R = 2.0
    shell = build_sphere_shell(300, radius=R, eta=eta)
    params = Params(eta=eta, dt_initial=1e-3, t_final=2e-3, gmres_tol=1e-10,
                    adaptive_timestep_flag=False, periphery_interaction_flag=True)
    system = System(params, shell_shape=PeripheryShape(kind="sphere", radius=R))

    t = np.linspace(0, 1, 16)
    x = np.stack([0.8 * t, np.zeros(16), np.zeros(16)], axis=1)[None]
    fibers = fc.make_group(x, lengths=0.8, bending_rigidity=0.01, radius=0.0125)
    points = PointSources.make(position=[[0.0, 0.5, 0.0]], force=[[1.0, 0.0, 0.0]])
    state = system.make_state(fibers=fibers, points=points, shell=shell)

    new_state, _, info = system.step(state)
    assert bool(info.converged)
    assert float(info.fiber_error) < 0.05
    assert not bool(system._collision_jit(new_state))
    # the shell density actually responded to the flow
    assert float(jnp.linalg.norm(new_state.shell.density)) > 0.0


@pytest.mark.parametrize("rows", [64, 70, 33])
def test_large_f64_operator_applies_in_row_blocks(monkeypatch, rows):
    """`peri._apply_operator` blocks a large float64 operator by rows (a TPU
    emulates the f64 dot through an 8x f32 expansion of the whole matrix —
    the walkthrough's 6,000-node shell did not fit a v5e without this). The
    blocked product equals ``op @ x``, ragged last block included; f32 and
    small operators keep the plain product."""
    monkeypatch.setattr(peri, "_F64_ROW_BLOCK", 16)
    rng = np.random.default_rng(rows)
    op = jnp.asarray(rng.standard_normal((rows, 40)))
    x = jnp.asarray(rng.standard_normal(40))
    assert "dynamic_slice" in str(jax.make_jaxpr(peri._apply_operator)(op, x))
    np.testing.assert_allclose(np.asarray(peri._apply_operator(op, x)),
                               np.asarray(op) @ np.asarray(x), rtol=1e-13)
    for plain in ((op.astype(jnp.float32), x.astype(jnp.float32)),
                  (op[:32], x)):
        assert "dynamic_slice" not in str(
            jax.make_jaxpr(peri._apply_operator)(*plain))
