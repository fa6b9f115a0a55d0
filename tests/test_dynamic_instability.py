"""RNG + dynamic instability tests.

Statistical oracles follow the reference's dynamic-instability probe
(`tests/core/dynamic_instability_test.cpp:18-50` records count/length
trajectories) plus exact catastrophe/nucleation probabilities from
`dynamic_instability.cpp:83-84,115-116`.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from skellysim_tpu.bodies import bodies as bd
from skellysim_tpu.fibers import container as fc
from skellysim_tpu.params import DynamicInstability, Params
from skellysim_tpu.periphery.precompute import precompute_body
from skellysim_tpu.system import System, apply_dynamic_instability
from skellysim_tpu.system.dynamic_instability import _grow_capacity
from skellysim_tpu.utils.rng import SimRNG


def make_body_with_sites(n_sites=20, radius=0.5):
    pre = precompute_body("sphere", 200, radius=radius)
    rng = np.random.default_rng(7)
    sites = rng.standard_normal((n_sites, 3))
    sites = radius * sites / np.linalg.norm(sites, axis=1, keepdims=True)
    return bd.make_group(pre["node_positions_ref"], pre["node_normals_ref"],
                         pre["node_weights"], nucleation_sites_ref=sites[None],
                         radius=radius)


def di_params(**kw):
    base = dict(n_nodes=16, v_growth=0.5, f_catastrophe=1.0,
                nucleation_rate=10.0, min_length=0.4,
                radius=0.0125, bending_rigidity=0.01)
    base.update(kw)
    di = DynamicInstability(**base)
    return Params(eta=1.0, dt_initial=0.05, t_final=1.0, gmres_tol=1e-8,
                  adaptive_timestep_flag=False, dynamic_instability=di)


def make_state(params, bodies=None, fibers=None):
    system = System(params)
    return system, system.make_state(fibers=fibers, bodies=bodies)


# ------------------------------------------------------------------------ RNG

def test_rng_dump_restore_reproduces_sequence():
    a = SimRNG(seed=42)
    _ = a.distributed.uniform(size=5)
    state = a.dump_state()
    seq1 = [a.distributed.uniform(), a.distributed.poisson_int(3.0),
            a.distributed.uniform_int(0, 100)]
    b = SimRNG.from_state(state)
    seq2 = [b.distributed.uniform(), b.distributed.poisson_int(3.0),
            b.distributed.uniform_int(0, 100)]
    assert seq1 == seq2
    # streams are independent
    c = SimRNG(seed=42)
    assert c.shared.uniform() != c.distributed.uniform()


# -------------------------------------------------------------- catastrophe

def test_catastrophe_survival_fraction():
    """Survival probability over one step must be exp(-dt * f_cat)."""
    nf, n = 2000, 16
    x = np.tile(np.linspace(0, 1, n)[None, :, None], (nf, 1, 3))
    fibers = fc.make_group(x, lengths=1.0, bending_rigidity=0.01, radius=0.0125)
    params = di_params(nucleation_rate=0.0)
    system, state = make_state(params, fibers=fibers,
                               bodies=make_body_with_sites())
    rng = SimRNG(seed=0)
    out = apply_dynamic_instability(state, params, rng)
    frac = float(np.asarray(out.fibers.active).mean())
    expected = np.exp(-0.05 * 1.0)
    assert frac == pytest.approx(expected, abs=3 * np.sqrt(expected / nf))
    # survivors grew, dead fibers kept their length
    grown = np.asarray(out.fibers.length)[np.asarray(out.fibers.active)]
    assert np.allclose(grown, 1.0 + 0.05 * 0.5)


def test_plus_pinned_scales_rates():
    nf, n = 4000, 16
    x = np.tile(np.linspace(0, 1, n)[None, :, None], (nf, 1, 3))
    fibers = fc.make_group(x, lengths=1.0, bending_rigidity=0.01, radius=0.0125)
    fibers = fibers._replace(plus_pinned=jnp.ones(nf, dtype=bool))
    params = di_params(nucleation_rate=0.0)
    system, state = make_state(params, fibers=fibers,
                               bodies=make_body_with_sites())
    out = apply_dynamic_instability(state, params, SimRNG(seed=1))
    frac = float(np.asarray(out.fibers.active).mean())
    # f_cat doubled by default collision scale
    expected = np.exp(-0.05 * 2.0)
    assert frac == pytest.approx(expected, abs=3 * np.sqrt(expected / nf))
    grown = np.asarray(out.fibers.length)[np.asarray(out.fibers.active)]
    assert np.allclose(grown, 1.0 + 0.05 * 0.5 * 0.5)  # v_growth halved


# --------------------------------------------------------------- nucleation

def test_nucleation_fills_free_sites():
    params = di_params(f_catastrophe=0.0, nucleation_rate=1e3)
    bodies = make_body_with_sites(n_sites=12)
    system, state = make_state(params, bodies=bodies)
    rng = SimRNG(seed=3)
    out = apply_dynamic_instability(state, params, rng)
    fibers = out.fibers
    assert fibers is not None
    active = np.asarray(fibers.active)
    assert active.sum() > 0
    # no duplicate sites
    bb = np.asarray(fibers.binding_body)[active]
    bs = np.asarray(fibers.binding_site)[active]
    assert len(set(zip(bb.tolist(), bs.tolist()))) == active.sum()
    # fibers point radially from the body's position at min_length
    _, _, sites = bd.place(out.bodies)
    sites = np.asarray(sites)[0]
    x = np.asarray(fibers.x)[active]
    for k in range(x.shape[0]):
        d = np.linalg.norm(x[k, -1] - x[k, 0])
        assert d == pytest.approx(params.dynamic_instability.min_length)
        np.testing.assert_allclose(x[k, 0], sites[bs[k]], atol=1e-12)
    assert np.all(np.asarray(fibers.minus_clamped)[active])

    # a second application must not nucleate onto occupied sites
    out2 = apply_dynamic_instability(out, params, rng)
    active2 = np.asarray(out2.fibers.active)
    bb2 = np.asarray(out2.fibers.binding_body)[active2]
    bs2 = np.asarray(out2.fibers.binding_site)[active2]
    assert len(set(zip(bb2.tolist(), bs2.tolist()))) == active2.sum()
    assert active2.sum() <= 12


def test_nucleation_rate_statistics():
    """Mean nucleations ~= dt * rate * n_free over many trials."""
    params = di_params(f_catastrophe=0.0, nucleation_rate=2.0)
    bodies = make_body_with_sites(n_sites=50)
    system, state = make_state(params, bodies=bodies)
    rng = SimRNG(seed=9)
    counts = []
    for _ in range(300):
        out = apply_dynamic_instability(state, params, rng)
        counts.append(int(np.asarray(out.fibers.active).sum())
                      if out.fibers is not None else 0)
    mean = np.mean(counts)
    lam = 0.05 * 2.0 * 50
    assert mean == pytest.approx(lam, abs=4 * np.sqrt(lam / 300))


def test_capacity_growth_preserves_state():
    x = np.tile(np.linspace(0, 1, 16)[None, :, None], (3, 1, 3))
    fibers = fc.make_group(x, lengths=1.0, bending_rigidity=0.01, radius=0.0125)
    grown = _grow_capacity(fibers, 8)
    assert grown.n_fibers == 8
    assert np.asarray(grown.active).sum() == 3
    np.testing.assert_array_equal(np.asarray(grown.x)[:3], x)
    assert np.all(np.asarray(grown.binding_body)[3:] == -1)


def test_capacity_growth_padding_is_finite_in_flow():
    """Regression: zero-padded slots (length=0) made the fiber cache NaN and
    0-weight * NaN leaked through the stokeslet sum, poisoning all targets."""
    import jax.numpy as jnp
    x = np.tile(np.linspace(0, 1, 16)[None, :, None], (2, 1, 3)) \
        + np.array([[[1.0, 0, 0]], [[-1.0, 0, 0]]])
    fibers = fc.make_group(x, lengths=1.0, bending_rigidity=0.01, radius=0.0125)
    grown = _grow_capacity(fibers, 5)
    # device round-trip of every ARRAY leaf (optional fields — rt_mats,
    # absent metadata — stay as-is: jnp.asarray(None) is NaN-bound)
    grown = grown._replace(**{
        name: jnp.asarray(leaf)
        for name, leaf in zip(grown._fields, grown)
        if name != "rt_mats" and leaf is not None})
    caches = fc.update_cache(grown, dt=0.01, eta=1.0)
    # (the block preconditioner's fields are None until `update_rhs_and_bc`)
    for leaf in caches:
        assert leaf is None or np.all(np.isfinite(np.asarray(leaf))), \
            "NaN in fiber cache"
    r_trg = jnp.asarray(np.random.default_rng(0).uniform(-2, 2, (7, 3)))
    forces = jnp.zeros_like(grown.x)
    u = fc.flow(grown, caches, r_trg, forces, eta=1.0, subtract_self=False)
    assert np.all(np.isfinite(np.asarray(u)))


# ------------------------------------------------------------- integration

def test_run_loop_with_dynamic_instability():
    """End-to-end: nucleate, solve, grow; solver must stay convergent."""
    params = Params(eta=1.0, dt_initial=0.02, t_final=0.08, gmres_tol=1e-8,
                    adaptive_timestep_flag=False,
                    dynamic_instability=DynamicInstability(
                        n_nodes=16, v_growth=0.2, f_catastrophe=0.5,
                        nucleation_rate=50.0, min_length=0.4,
                        radius=0.0125, bending_rigidity=0.01))
    bodies = make_body_with_sites(n_sites=8, radius=0.5)
    system = System(params)
    state = system.make_state(bodies=bodies)
    rng = SimRNG(seed=11)
    final = system.run(state, rng=rng)
    assert final.fibers is not None
    assert np.asarray(final.fibers.active).sum() > 0
    assert float(final.time) >= params.t_final
    # bound fibers still rooted on their (possibly moved) nucleation sites
    _, _, sites = bd.place(final.bodies)
    sites = np.asarray(sites)[0]
    act = np.asarray(final.fibers.active)
    bs = np.asarray(final.fibers.binding_site)[act]
    x0 = np.asarray(final.fibers.x)[act][:, 0]
    np.testing.assert_allclose(x0, sites[bs], atol=1e-8)


def test_nucleation_into_grown_slots_keeps_fd_defaults():
    """Slots created by capacity growth must get real penalty/beta_tstep."""
    from skellysim_tpu.fibers import fd_fiber

    params = di_params(f_catastrophe=0.0, nucleation_rate=1e4)
    bodies = make_body_with_sites(n_sites=30)
    # a full 2-slot group of unbound fibers: nucleation must grow capacity
    x = np.tile(np.linspace(0, 1, 16)[None, :, None], (2, 1, 3)) + 3.0
    fibers = fc.make_group(x, lengths=1.0, bending_rigidity=0.01, radius=0.0125)
    system, state = make_state(params, bodies=bodies, fibers=fibers)
    out2 = apply_dynamic_instability(state, params, SimRNG(seed=21))
    active2 = np.asarray(out2.fibers.active)
    assert out2.fibers.n_fibers > 2 and active2.sum() > 2  # capacity grew
    assert np.all(np.asarray(out2.fibers.penalty)[active2]
                  == fd_fiber.DEFAULT_PENALTY)
    assert np.all(np.asarray(out2.fibers.beta_tstep)[active2]
                  == fd_fiber.DEFAULT_BETA_TSTEP)
