"""Dtype discipline: an f32 state must stay f32 end-to-end under jax_enable_x64.

Round-2 regression: the NumPy-f64 `FibMats` constants promoted every downstream
op to f64 (`fd_fiber.py`), so a float32 `SimState` produced float64 `A_bc`/LU —
and TPU XLA's `LuDecomposition` is f32-only, killing the on-device solve
(BENCH_r02 tail). The suite runs with x64 enabled (conftest), exactly the
configuration every run uses on the TPU, so these assertions catch any new
f64 constant closed over f32 jit code.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skellysim_tpu.fibers import container as fc
from skellysim_tpu.fibers import fd_fiber
from skellysim_tpu.fibers.matrices import get_mats, typed
from skellysim_tpu.params import Params
from skellysim_tpu.system import System


def _line_group(dtype, nf=2, n=32):
    t = np.linspace(0, 1, n)
    x = np.stack([np.zeros(n), np.zeros(n), t], axis=-1)
    xs = np.stack([x + np.array([3.0 * i, 0, 0]) for i in range(nf)])
    return fc.make_group(xs, lengths=1.0, bending_rigidity=0.01, radius=0.0125,
                         dtype=dtype)


def test_typed_mats_cast():
    m64 = get_mats(32)
    m32 = typed(m64, jnp.float32)
    assert m32.D1.dtype == np.float32
    assert m32.P_down.dtype == np.float32
    assert m32.weights0.dtype == np.float32
    # f64 request returns the original f64 set
    assert typed(m64, jnp.float64) is m64
    # cached: same object on repeat calls
    assert typed(m64, jnp.float32) is m32


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_fiber_caches_keep_dtype(dtype):
    assert jax.config.jax_enable_x64  # the promotion only bites with x64 on
    group = _line_group(dtype)
    caches = fc.update_cache(group, 0.1, 1.0)
    assert caches.xs.dtype == dtype
    assert caches.stokeslet.dtype == dtype
    assert caches.force_op.dtype == dtype

    nf, n = group.n_fibers, group.n_nodes
    v = jnp.zeros((nf, n, 3), dtype=dtype)
    f = jnp.zeros((nf, n, 3), dtype=dtype)
    caches = fc.update_rhs_and_bc(group, caches, 0.1, 1.0, v, f, f)
    assert caches.A_bc.dtype == dtype, "A_bc promoted — FibMats leak is back"
    assert caches.RHS.dtype == dtype
    assert caches.lu.dtype == dtype, "LU must match state dtype (TPU LU is f32-only)"

    x = jnp.zeros((nf, 4 * n), dtype=dtype)
    assert fc.apply_preconditioner(group, caches, x).dtype == dtype
    vb = jnp.zeros((nf, 7), dtype=dtype)
    assert fc.matvec(group, caches, x, v, vb).dtype == dtype
    assert fc.fiber_error(group).dtype == dtype


def test_single_fiber_solve_stays_f32():
    dtype = jnp.float32
    group = _line_group(dtype, nf=1, n=32)
    params = Params(eta=1.0, dt_initial=0.1, t_final=1.0, gmres_tol=1e-6,
                    adaptive_timestep_flag=False)
    system = System(params)
    from skellysim_tpu.system.sources import BackgroundFlow

    bg = BackgroundFlow.make(uniform=[0.0, 0.0, 1.0], dtype=dtype)
    state = system.make_state(fibers=group, background=bg)
    new_state, solution, info = system.step(state)
    assert solution.dtype == dtype
    assert new_state.fibers.x.dtype == dtype
    assert bool(info.converged)


def test_force_operator_and_error_f32():
    mats = get_mats(16)
    x = jnp.asarray(np.linspace(0, 1, 16)[:, None] * np.array([0.0, 0, 1.0]),
                    dtype=jnp.float32)
    xs, xss, _, _ = fd_fiber.derivatives(x, jnp.float32(1.0), mats)
    assert xs.dtype == jnp.float32
    sc = fd_fiber.FiberScalars(*[jnp.float32(v) for v in
                                 (1.0, 1.0, 0.01, 0.0125, 500.0, 1.0, 0.0)])
    fop = fd_fiber.force_operator(xs, xss, 1.0, sc, mats)
    assert fop.dtype == jnp.float32
    assert fd_fiber.fiber_error(x, jnp.float32(1.0), mats).dtype == jnp.float32


def test_fiberless_f32_state_stays_f32():
    """Shell/bodies-only f32 states must not up-cast in the matvec (the
    lo_dtype seam must be a no-op without a lo triple)."""
    from skellysim_tpu.testing import make_coupled_parts

    shell, shape, bodies = make_coupled_parts(96, 64, jnp.float32)
    params = Params(dt_initial=0.1, t_final=1.0, gmres_tol=1e-6,
                    adaptive_timestep_flag=False)
    system = System(params, shell_shape=shape)
    state = system.make_state(shell=shell, bodies=bodies)
    assert state.time.dtype == jnp.float32

    state2, caches, body_caches, _, _ = system._prep(state)
    n = shell.solution_size + bodies.solution_size
    x = jnp.ones(n, dtype=jnp.float32)
    out = system._apply_matvec(state2, caches, body_caches, x)
    assert out.dtype == jnp.float32
    new_state, solution, info = system.step(state)
    assert solution.dtype == jnp.float32
    assert bool(info.converged)


def _lint_dtype(relpath):
    import os

    from skellysim_tpu.lint import lint_paths

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return lint_paths([os.path.join(root, relpath)],
                      rules=["dtype-discipline"])


def test_gmres_dtype_lint_clean():
    """Pins the skelly-lint dtype audit of the solver: the `_icgs` mask and
    back-substitution index aranges are int32 (not x64-following int64)."""
    assert _lint_dtype("skellysim_tpu/solver/gmres.py") == []


def test_container_dtype_lint_clean():
    """Pins the skelly-lint dtype audit of the fiber container (every array
    constructor derives its dtype from the state — the FibMats-leak file)."""
    assert _lint_dtype("skellysim_tpu/fibers/container.py") == []


@pytest.mark.slow  # drives the full mixed solve through the DF tier: ~1 min on the CPU tier
def test_df_tier_kernel_impl_preserves_f32_solve_dtype():
    """The DF tiles return float64 internally; the evaluator seam must cast
    back so an f32 solve with kernel_impl="df"/"pallas_df" stays f32 end to
    end (round 5: the unconverted f64 flow promoted the whole Krylov
    pipeline)."""
    import dataclasses

    from __graft_entry__ import _make_system

    for impl in ("df", "pallas_df"):
        system, state = _make_system(n_fibers=2, n_nodes=16,
                                     dtype=jnp.float32)
        system.params = dataclasses.replace(system.params, kernel_impl=impl)
        _, solution, info = jax.jit(system._solve_impl)(state)
        assert solution.dtype == jnp.float32, impl
        assert bool(info.converged), impl
