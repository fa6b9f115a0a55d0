"""GMRES solver tests against dense numpy solves."""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from skellysim_tpu.solver import gmres

#: the module (the package re-exports the function under the same name)
gmres_mod = importlib.import_module("skellysim_tpu.solver.gmres")


def _system(n, seed, cond_boost=0.0):
    rng = np.random.default_rng(seed)
    A = rng.standard_normal((n, n)) / np.sqrt(n) + (2.0 + cond_boost) * np.eye(n)
    b = rng.standard_normal(n)
    return A, b


def test_gmres_unpreconditioned():
    A, b = _system(60, 0)
    res = gmres(lambda v: jnp.asarray(A) @ v, jnp.asarray(b), tol=1e-12, restart=60)
    assert bool(res.converged)
    np.testing.assert_allclose(np.asarray(res.x), np.linalg.solve(A, b), rtol=1e-9, atol=1e-10)


def test_gmres_right_preconditioned_fewer_iters():
    A, b = _system(80, 1)
    M = np.linalg.inv(A + 0.05 * np.random.default_rng(2).standard_normal((80, 80)))
    plain = gmres(lambda v: jnp.asarray(A) @ v, jnp.asarray(b), tol=1e-10, restart=80)
    prec = gmres(lambda v: jnp.asarray(A) @ v, jnp.asarray(b),
                 precond=lambda v: jnp.asarray(M) @ v, tol=1e-10, restart=80)
    assert bool(prec.converged)
    assert int(prec.iters) < int(plain.iters)
    np.testing.assert_allclose(np.asarray(prec.x), np.linalg.solve(A, b), rtol=1e-7, atol=1e-8)


def test_gmres_restarted():
    A, b = _system(100, 3, cond_boost=2.0)
    res = gmres(lambda v: jnp.asarray(A) @ v, jnp.asarray(b), tol=1e-10, restart=25, maxiter=400)
    assert bool(res.converged)
    np.testing.assert_allclose(np.asarray(res.x), np.linalg.solve(A, b), rtol=1e-7, atol=1e-8)


def test_gmres_zero_rhs():
    A, _ = _system(20, 4)
    res = gmres(lambda v: jnp.asarray(A) @ v, jnp.zeros(20), tol=1e-12)
    assert bool(res.converged)
    assert int(res.iters) == 0
    np.testing.assert_allclose(np.asarray(res.x), 0.0)


def test_gmres_exact_in_n_iterations():
    # Krylov exactness: an n-dim system converges within n inner iterations
    A, b = _system(30, 5)
    res = gmres(lambda v: jnp.asarray(A) @ v, jnp.asarray(b), tol=1e-13, restart=30)
    assert int(res.iters) <= 30
    explicit = np.linalg.norm(A @ np.asarray(res.x) - b) / np.linalg.norm(b)
    assert explicit < 1e-11


def test_gmres_explicit_residual_agrees_with_implicit():
    """The post-solve explicit residual (`solver_hydro.cpp:81-92` analogue)
    must agree with the implicit Givens residual to ~10x tol on a conditioned
    problem, and must equal a hand-computed ||b - Ax|| / ||b||."""
    A, b = _system(120, 3, cond_boost=3.0)
    M = np.linalg.inv(np.diag(np.diag(A)))
    res = gmres(lambda v: jnp.asarray(A) @ v, jnp.asarray(b),
                precond=lambda v: jnp.asarray(M) @ v, tol=1e-10, restart=40,
                maxiter=400)
    assert bool(res.converged)
    hand = np.linalg.norm(A @ np.asarray(res.x) - b) / np.linalg.norm(b)
    np.testing.assert_allclose(float(res.residual_true), hand, rtol=1e-6)
    assert float(res.residual_true) <= 10.0 * 1e-10
    # implicit and explicit agree to within an order of magnitude
    assert float(res.residual_true) <= 10.0 * max(float(res.residual), 1e-16)


def test_step_info_carries_true_residual():
    from skellysim_tpu.params import Params
    from skellysim_tpu.system import BackgroundFlow, System
    from skellysim_tpu.fibers import container as fc

    t = np.linspace(0, 1, 16)
    x = np.array([2.0, 0.0, 0.0])[None, :] + t[:, None] * np.array([0.0, 0.0, 1.0])
    fibers = fc.make_group(x[None], lengths=1.0, bending_rigidity=0.01,
                           radius=0.0125, dtype=jnp.float64)
    system = System(Params(eta=1.0, dt_initial=1e-3, t_final=1e-2,
                           gmres_tol=1e-10, adaptive_timestep_flag=False))
    state = system.make_state(
        fibers=fibers,
        background=BackgroundFlow.make(uniform=(1.0, 0.0, 0.0), dtype=jnp.float64))
    _, _, info = system.step(state)
    assert np.isfinite(float(info.residual_true))
    assert float(info.residual_true) <= 10.0 * 1e-10
    assert not bool(info.loss_of_accuracy)


# ---------------------------------------------------- s-step (block) GMRES

def test_gmres_block_s1_bitwise_default():
    """block_s=1 routes through the EXACT sequential cycle: the result is
    bit-identical to the default call (the pre-s-step solver — the parity
    every golden-trajectory / unroll-ensemble / serve pin rides on)."""
    A, b = _system(60, 8)
    base = gmres(lambda v: jnp.asarray(A) @ v, jnp.asarray(b), tol=1e-12,
                 restart=25, maxiter=200)
    s1 = gmres(lambda v: jnp.asarray(A) @ v, jnp.asarray(b), tol=1e-12,
               restart=25, maxiter=200, block_s=1)
    assert np.array_equal(np.asarray(base.x), np.asarray(s1.x))
    assert int(base.iters) == int(s1.iters)
    assert float(base.residual) == float(s1.residual)


def test_gmres_block_matches_sequential_iterations():
    """s > 1 reaches the same explicit-residual tolerance with iteration
    count within 10% of the sequential cycle (the ISSUE 8 acceptance pin),
    on a conditioned and a restarted problem."""
    for n, seed, restart, boost in ((80, 1, 80, 0.0), (100, 3, 12, 2.0)):
        A, b = _system(n, seed, cond_boost=boost)
        mv = lambda v: jnp.asarray(A) @ v
        r1 = gmres(mv, jnp.asarray(b), tol=1e-10, restart=restart,
                   maxiter=600)
        assert bool(r1.converged)
        for s in (2, 4):
            rs = gmres(mv, jnp.asarray(b), tol=1e-10, restart=restart,
                       maxiter=600, block_s=s)
            assert bool(rs.converged), (n, s)
            explicit = (np.linalg.norm(A @ np.asarray(rs.x) - b)
                        / np.linalg.norm(b))
            assert explicit <= 1e-9, (n, s, explicit)
            # an s-step round can only stop on round boundaries mid-cycle,
            # so allow the ceil-to-s slack on top of the 10%
            assert int(rs.iters) <= int(np.ceil(1.1 * int(r1.iters) / s) * s), \
                (n, s, int(rs.iters), int(r1.iters))


def test_gmres_block_history_and_cycles_semantics():
    """The convergence ring buffer keeps its one-row-per-restart contract
    under block_s (skelly-scope decode invariant: rows written ==
    result.cycles)."""
    from skellysim_tpu.solver.gmres import history_rows

    A, b = _system(100, 5, cond_boost=2.0)
    res = gmres(lambda v: jnp.asarray(A) @ v, jnp.asarray(b), tol=1e-11,
                restart=12, maxiter=400, history=8, block_s=4)
    assert bool(res.converged)
    rows = history_rows(res.history, res.cycles)
    assert len(rows) == min(int(res.cycles), 8)
    assert rows[-1][0] == int(res.iters)          # cumulative iters
    assert rows[-1][2] == float(res.residual_true)


def test_gmres_block_two_gram_rounds_per_cycle_body():
    """The communication-avoiding claim, pinned at trace level: the s-step
    loop body performs exactly TWO batched (matrix-operand) reductions
    through the rdot seam per s iterations — the sequential body's three
    vector reductions per iteration are gone. Per restart cycle of m
    iterations that is 2*(m/s) rounds vs 3*m, a 6x drop at s=4 (the >= 3x
    acceptance bound follows arithmetically)."""
    A, b = _system(40, 2)

    def make_counting_rdot(log):
        def rdot(Av, w):
            log.append(getattr(w, "ndim", 1))
            return Av @ w
        return rdot

    log_s1, log_s4 = [], []
    gmres(lambda v: jnp.asarray(A) @ v, jnp.asarray(b), tol=1e-10,
          restart=16, maxiter=64, rdot=make_counting_rdot(log_s1))
    gmres(lambda v: jnp.asarray(A) @ v, jnp.asarray(b), tol=1e-10,
          restart=16, maxiter=64, rdot=make_counting_rdot(log_s4), block_s=4)
    # sequential trace: no matrix-operand reductions anywhere
    assert log_s1.count(2) == 0
    # block trace: exactly 2 batched Gram reductions in the (once-traced)
    # round body, covering s=4 iterations each
    assert log_s4.count(2) == 2
    # and the block path introduces no NEW vector reductions beyond the
    # sequential path's outer-loop norms (entry beta, b_norm, explicit
    # residual): the 3-per-iteration ICGS/norm reductions are gone
    assert log_s4.count(1) < log_s1.count(1)


def test_collective_rounds_formula():
    """`collective_rounds` (the obs-summarize metrics derivation): >= 3x
    fewer dot-product rounds at s=4 for any realistic iteration count."""
    from skellysim_tpu.solver.gmres import collective_rounds

    assert collective_rounds(10, 1, 1) == 32          # 3*10 + 2
    assert collective_rounds(10, 1, 4) == 8           # 2*ceil(10/4) + 2
    for iters, cycles in ((4, 1), (30, 1), (100, 2), (400, 5)):
        r1 = collective_rounds(iters, cycles, 1)
        r4 = collective_rounds(iters, cycles, 4)
        assert r1 >= 3 * r4, (iters, cycles, r1, r4)
    # gmres_ir results carry cycles=SWEEPS: restart= floors the boundary
    # count at ceil(iters/restart), so an inner restart blow-up (300 inner
    # iterations across only 2 sweeps at restart=30) still moves the metric
    assert collective_rounds(300, 2, 1, restart=30) == 3 * 300 + 2 * 10
    assert collective_rounds(10, 2, 1, restart=100) == 3 * 10 + 2 * 2
    # with the solve's gram_rows the Gram rounds are exact: one reduction a
    # chunk of live rows a pass. 20 iterations of one cycle walk 1 chunk a
    # pass while k + 1 <= C and 2 after
    C = gmres_mod._GRAM_CHUNK
    rows = sum(2 * C * (-(-(k + 1) // C)) for k in range(C + 4))
    assert (collective_rounds(C + 4, 1, 1, restart=100, gram_rows=rows)
            == 2 * C + 4 * 4 + (C + 4) + 2)
    # a basis shorter than a chunk is one chunk of restart + 1 rows
    assert collective_rounds(3, 1, 1, restart=4, gram_rows=3 * 2 * 5) == 11


def test_gmres_ir_block_reaches_tol():
    """Mixed-precision refinement with the s-step inner solve: same f64
    explicit-residual contract as the sequential inner loop."""
    from skellysim_tpu.solver import gmres_ir

    rng = np.random.default_rng(9)
    n = 96
    A = rng.standard_normal((n, n)) / np.sqrt(n) + 3.0 * np.eye(n)
    b = rng.standard_normal(n)
    A32 = jnp.asarray(A, dtype=jnp.float32)
    res = gmres_ir(lambda v: jnp.asarray(A) @ v,
                   lambda v: (A32 @ v.astype(jnp.float32)).astype(v.dtype),
                   jnp.asarray(b), tol=1e-10, inner_tol=1e-5, restart=48,
                   maxiter=200, block_s=4)
    assert bool(res.converged)
    explicit = np.linalg.norm(A @ np.asarray(res.x) - b) / np.linalg.norm(b)
    assert explicit <= 1e-9


# --------------------------------------------- live-row basis products

def _twin_icgs(V, w, k, n_restart, rdot):
    """The full-basis orthogonalisation the live-row walk replaced, verbatim:
    one masked product over all ``n_restart + 1`` rows a pass."""
    keep = jnp.arange(n_restart + 1, dtype=jnp.int32) <= k
    h = jnp.zeros(n_restart + 1, dtype=w.dtype)
    for _ in range(2):
        proj = jnp.where(keep, rdot(V, w), 0.0)   # [m+1] masked <v_i, w>
        w = w - proj @ V
        h = h + proj
    return w, h


def _twin_back_substitute(H, g, k):
    """The m-trip masked back-substitution the live-row one replaced,
    verbatim."""
    m = H.shape[1]
    dtype = H.dtype
    idx = jnp.arange(m, dtype=jnp.int32)
    active = idx < k

    def back_sub(i, y):
        j = m - 1 - i
        hjj = H[j, j]
        rhs = g[j] - jnp.dot(H[j, :], y)
        yj = jnp.where(active[j], rhs / jnp.where(hjj != 0.0, hjj, 1.0), 0.0)
        return y.at[j].set(yj)

    return lax.fori_loop(0, m, back_sub, jnp.zeros(m, dtype=dtype))


def _full_basis_twin(monkeypatch):
    """Route `gmres` through the twins above: every basis product over all
    rows. `gmres` is jitted on its (static) callables, so a solve with a
    fresh matvec traces the patched helpers."""
    monkeypatch.setattr(
        gmres_mod, "_icgs", lambda V, w, k, rdot: (
            *_twin_icgs(V, w, k, V.shape[0] - 1, rdot), 2 * V.shape[0]))
    monkeypatch.setattr(gmres_mod, "_back_substitute", _twin_back_substitute)
    monkeypatch.setattr(gmres_mod, "_combine_live",
                        lambda y, V, k: y @ V[:y.shape[0]])


def _shift_system(n, k, seed):
    """A = blockdiag(cyclic shift of order k, I): the minimal polynomial of
    A is x^k - 1, so GMRES from a generic b makes little progress for k - 1
    iterations and is exact at the k-th — a cycle that ends at k."""
    A = np.eye(n)
    A[:k, :k] = np.roll(np.eye(k), 1, axis=0)
    return A, np.random.default_rng(seed).standard_normal(n)


def _live_cases():
    C = gmres_mod._GRAM_CHUNK
    cases = [pytest.param(("shift", k), id=f"k={name}")
             for name, k in (("1", 1), ("C-1", C - 1), ("C", C),
                             ("C+1", C + 1), ("2C", 2 * C))]
    return cases + [pytest.param(("restart", 25), id="restart=25")]


@pytest.mark.parametrize("case", _live_cases())
def test_gmres_live_rows_match_full_basis_twin(case, monkeypatch):
    """The live-row walk is the full-basis solver less the zero rows: `x`,
    `iters`, `residual` agree with the twin to 1e-13 for cycles that end
    below, at and above a chunk boundary and for full cycles with restarts
    (a basis of 26 rows: the last chunk starts early); the Gram passes
    contract no more than the chunk-padded live rows, and `gram_rows`
    is what a counting rdot saw."""
    C = gmres_mod._GRAM_CHUNK
    kind, arg = case
    if kind == "shift":
        n = 3 * C
        A, b = _shift_system(n, arg, seed=arg)
        kw = dict(tol=1e-10, restart=2 * C + 8, maxiter=200)
    else:
        A, b = _system(100, 3, cond_boost=-0.6)
        kw = dict(tol=1e-10, restart=arg, maxiter=400)
    A, b = jnp.asarray(A), jnp.asarray(b)

    seen = []

    def rdot(Av, w):
        if Av.ndim == 2:          # a Gram product (norms pass a vector)
            jax.debug.callback(lambda: seen.append(Av.shape[0]))
        return Av @ w

    live = gmres(lambda v: A @ v, b, rdot=rdot, **kw)
    jax.effects_barrier()
    assert bool(live.converged)
    if kind == "shift":
        assert int(live.iters) == arg and int(live.cycles) == 1
        # two passes an iteration over ceil((j + 1) / C) chunks of C rows
        assert len(seen) == sum(2 * (-(-(j + 1) // C)) for j in range(arg))
    else:
        assert int(live.cycles) > 1 and int(live.iters) > arg
    assert set(seen) == {min(C, kw["restart"] + 1)}
    assert int(live.gram_rows) == sum(seen)
    assert sum(seen) < 2 * int(live.iters) * (kw["restart"] + 1)

    _full_basis_twin(monkeypatch)
    twin = gmres(lambda v: A @ v, b, **kw)
    assert int(twin.gram_rows) == 2 * int(twin.iters) * (kw["restart"] + 1)
    assert int(live.iters) == int(twin.iters)
    scale = float(jnp.linalg.norm(twin.x))
    assert float(jnp.linalg.norm(live.x - twin.x)) <= 1e-13 * scale
    # a residual that is exact to roundoff (the shift systems': a few eps
    # of ||b||) has no digits to agree on: the floor is absolute
    np.testing.assert_allclose(float(live.residual), float(twin.residual),
                               rtol=1e-13, atol=1e-15)


@pytest.mark.parametrize("k", [0, 1, 7, 30])
def test_back_substitution_bitwise_equals_full_trip_twin(k):
    """Skipping the rows from k on changes no bit of `y`."""
    m = 30
    rng = np.random.default_rng(k)
    H = jnp.asarray(np.triu(rng.standard_normal((m + 1, m)))
                    + 3.0 * np.eye(m + 1, m))
    g = jnp.asarray(rng.standard_normal(m + 1))
    y = gmres_mod._back_substitute(H, g, jnp.int32(k))
    assert np.array_equal(np.asarray(y),
                          np.asarray(_twin_back_substitute(H, g, jnp.int32(k))))
    assert not np.asarray(y)[k:].any()


def test_gmres_vmap_members_with_different_live_rows():
    """Under vmap the chunk walk runs to the longest member's count with
    masked carries: each member keeps its solo `x`, `iters` and `gram_rows`
    (beside test_ensemble.py::test_gmres_vmap_masked_convergence)."""
    C = gmres_mod._GRAM_CHUNK
    n = 3 * C
    ks = (3, C + 4)
    systems = [_shift_system(n, k, seed=k) for k in ks]
    As = jnp.stack([jnp.asarray(A) for A, _ in systems])
    bs = jnp.stack([jnp.asarray(b) for _, b in systems])

    def solve(A, b):
        return gmres(lambda v: A @ v, b, tol=1e-10, restart=2 * C + 8,
                     maxiter=200)

    batched = jax.jit(jax.vmap(solve))(As, bs)
    for i, k in enumerate(ks):
        solo = solve(As[i], bs[i])
        assert int(solo.iters) == k == int(batched.iters[i])
        assert int(batched.gram_rows[i]) == int(solo.gram_rows)
        np.testing.assert_allclose(np.asarray(batched.x[i]),
                                   np.asarray(solo.x), rtol=1e-13, atol=1e-13)
