"""Pallas double-float tiles: accuracy + seam routing (interpret on CPU).

Mirrors the XLA DF kernel pins (`test_df_kernels.py`): the fused Pallas
tiles must deliver the same ~1e-14-class relative accuracy from pure f32
pair arithmetic, drop self pairs, survive padding, and ride the
`kernels.*_direct(impl="pallas_df")` seam. The real-hardware authority is
`chip_smoke.py`'s pallas_df gate (interpret mode runs XLA:CPU arithmetic,
not Mosaic's); `tests/test_chip_compile.py` compiles the tiles for the chip.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from skellysim_tpu.ops import kernels
from skellysim_tpu.ops.df_kernels import stokeslet_direct_df, stresslet_direct_df
from skellysim_tpu.ops.pallas_df import stokeslet_pallas_df, stresslet_pallas_df

RNG = np.random.default_rng(11)


def _cloud(n_src, n_trg, overlap=0):
    r_src = RNG.uniform(-5, 5, (n_src, 3))
    r_trg = RNG.uniform(-5, 5, (n_trg, 3))
    if overlap:
        r_trg[:overlap] = r_src[:overlap]  # exercise self-pair dropping
    f = RNG.standard_normal((n_src, 3))
    return r_src, r_trg, f


def _oracle_stokeslet(r_src, r_trg, f_src, eta=1.0):
    d = r_trg[:, None, :] - r_src[None, :, :]
    r2 = np.sum(d * d, axis=-1)
    rinv = np.where(r2 > 0, 1.0 / np.sqrt(np.where(r2 > 0, r2, 1.0)), 0.0)
    df = np.einsum("tsk,sk->ts", d, f_src)
    u = np.einsum("ts,sk->tk", rinv, f_src) + np.einsum("ts,tsk->tk",
                                                        df * rinv**3, d)
    return u / (8 * np.pi * eta)


def _oracle_stresslet(r_dl, r_trg, S, eta=1.0):
    d = r_trg[:, None, :] - r_dl[None, :, :]
    r2 = np.sum(d * d, axis=-1)
    rinv = np.where(r2 > 0, 1.0 / np.sqrt(np.where(r2 > 0, r2, 1.0)), 0.0)
    dSd = np.einsum("tsi,sij,tsj->ts", d, S, d)
    return np.einsum("ts,tsk->tk", -3.0 * dSd * rinv**5, d) / (8 * np.pi * eta)


@pytest.mark.slow  # interpret-mode pallas: minutes-class on the 1-core CPU tier
def test_stokeslet_pallas_df_f64_accuracy():
    r_src, r_trg, f = _cloud(300, 200, overlap=40)
    got = np.asarray(stokeslet_pallas_df(jnp.asarray(r_src), jnp.asarray(r_trg),
                                         jnp.asarray(f), 1.3, interpret=True))
    assert got.dtype == np.float64
    ref = _oracle_stokeslet(r_src, r_trg, f, 1.3)
    assert np.linalg.norm(got - ref) / np.linalg.norm(ref) < 5e-13


@pytest.mark.slow  # interpret-mode pallas: minutes-class on the 1-core CPU tier
def test_stokeslet_pallas_df_matches_xla_df_twin():
    r_src, r_trg, f = _cloud(520, 140)  # src spans >1 source tile (512)
    a = np.asarray(stokeslet_pallas_df(jnp.asarray(r_src), jnp.asarray(r_trg),
                                       jnp.asarray(f), 1.0, interpret=True))
    b = np.asarray(stokeslet_direct_df(jnp.asarray(r_src), jnp.asarray(r_trg),
                                       jnp.asarray(f), 1.0))
    assert np.linalg.norm(a - b) / np.linalg.norm(b) < 1e-13


@pytest.mark.slow  # interpret-mode pallas: minutes-class on the 1-core CPU tier
def test_stokeslet_pallas_df_f32_inputs():
    """f32 inputs pass through with zero lo words — still DF-accurate
    relative to the f64 evaluation of the same f32 points."""
    r_src, r_trg, f = _cloud(130, 90)
    r32s, r32t, f32 = (a.astype(np.float32) for a in (r_src, r_trg, f))
    got = np.asarray(stokeslet_pallas_df(jnp.asarray(r32s), jnp.asarray(r32t),
                                         jnp.asarray(f32), 1.0,
                                         interpret=True))
    ref = _oracle_stokeslet(r32s.astype(np.float64), r32t.astype(np.float64),
                            f32.astype(np.float64))
    assert np.linalg.norm(got - ref) / np.linalg.norm(ref) < 5e-13


@pytest.mark.slow  # interpret-mode pallas: minutes-class on the 1-core CPU tier
def test_stresslet_pallas_df_accuracy():
    r_dl = RNG.uniform(-3, 3, (300, 3))
    r_trg = np.concatenate([r_dl[:50], RNG.uniform(-3, 3, (100, 3))], axis=0)
    S = RNG.standard_normal((300, 3, 3))
    got = np.asarray(stresslet_pallas_df(jnp.asarray(r_dl), jnp.asarray(r_trg),
                                         jnp.asarray(S), 0.7, interpret=True))
    ref = _oracle_stresslet(r_dl, r_trg, S, 0.7)
    assert np.linalg.norm(got - ref) / np.linalg.norm(ref) < 5e-13
    twin = np.asarray(stresslet_direct_df(jnp.asarray(r_dl),
                                          jnp.asarray(r_trg),
                                          jnp.asarray(S), 0.7))
    assert np.linalg.norm(got - twin) / np.linalg.norm(twin) < 1e-13


@pytest.mark.slow  # heavy coupled-solve integration; sibling fast tests keep the seam covered (ISSUE-9 870s-budget re-triage)
def test_empty_and_seam_routing():
    assert stokeslet_pallas_df(jnp.zeros((0, 3)), jnp.zeros((5, 3)),
                               jnp.zeros((0, 3)), 1.0,
                               interpret=True).shape == (5, 3)
    # the evaluator seam: impl="pallas_df" routes here (interpret on CPU)
    r_src, r_trg, f = _cloud(64, 48)
    via_seam = np.asarray(kernels.stokeslet_direct(
        jnp.asarray(r_src), jnp.asarray(r_trg), jnp.asarray(f), 1.0,
        impl="pallas_df"))
    ref = _oracle_stokeslet(r_src, r_trg, f)
    assert np.linalg.norm(via_seam - ref) / np.linalg.norm(ref) < 5e-13


@pytest.mark.slow  # interpret-mode pallas: minutes-class on the 1-core CPU tier
def test_mixed_solver_accepts_pallas_df():
    """refine_pair_impl="pallas_df": the mixed solve converges to 1e-10 with
    the Pallas DF residual tiles (interpret mode on this CPU suite)."""
    from __graft_entry__ import _make_system

    system, state = _make_system(n_fibers=2, n_nodes=16, dtype=jnp.float64,
                                 solver_precision="mixed",
                                 refine_pair_impl="pallas_df")
    import jax

    _, _, info = jax.jit(system._solve_impl)(state)
    assert float(info.residual_true) <= 1e-10
