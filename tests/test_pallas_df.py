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


# (tile_t, tile_s, strip_w): the defaults (one block, clamped to the cloud),
# then blocks small enough that a test-sized cloud spans several target
# tiles, source tiles, strips and chunks of every strip width
TILES = [None, (16, 256, 128), (32, 512, 256), (8, 1024, 512)]


def _tile_kw(tile):
    if tile is None:
        return {}
    return dict(zip(("tile_t", "tile_s", "strip_w"), tile))


@pytest.mark.parametrize("tile", TILES)
def test_stokeslet_pallas_df_f64_accuracy(tile):
    r_src, r_trg, f = _cloud(300, 200, overlap=40)
    got = np.asarray(stokeslet_pallas_df(jnp.asarray(r_src), jnp.asarray(r_trg),
                                         jnp.asarray(f), 1.3, interpret=True,
                                         **_tile_kw(tile)))
    assert got.dtype == np.float64
    ref = _oracle_stokeslet(r_src, r_trg, f, 1.3)
    assert np.linalg.norm(got - ref) / np.linalg.norm(ref) < 5e-13


@pytest.mark.parametrize("tile", TILES)
def test_stokeslet_pallas_df_matches_xla_df_twin(tile):
    r_src, r_trg, f = _cloud(1100, 140)  # src spans several source tiles
    a = np.asarray(stokeslet_pallas_df(jnp.asarray(r_src), jnp.asarray(r_trg),
                                       jnp.asarray(f), 1.0, interpret=True,
                                       **_tile_kw(tile)))
    b = np.asarray(stokeslet_direct_df(jnp.asarray(r_src), jnp.asarray(r_trg),
                                       jnp.asarray(f), 1.0))
    assert np.linalg.norm(a - b) / np.linalg.norm(b) < 1e-13


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


@pytest.mark.parametrize("tile", TILES)
def test_stresslet_pallas_df_accuracy(tile):
    r_dl = RNG.uniform(-3, 3, (300, 3))
    r_trg = np.concatenate([r_dl[:50], RNG.uniform(-3, 3, (100, 3))], axis=0)
    S = RNG.standard_normal((300, 3, 3))
    got = np.asarray(stresslet_pallas_df(jnp.asarray(r_dl), jnp.asarray(r_trg),
                                         jnp.asarray(S), 0.7, interpret=True,
                                         **_tile_kw(tile)))
    ref = _oracle_stresslet(r_dl, r_trg, S, 0.7)
    assert np.linalg.norm(got - ref) / np.linalg.norm(ref) < 5e-13
    twin = np.asarray(stresslet_direct_df(jnp.asarray(r_dl),
                                          jnp.asarray(r_trg),
                                          jnp.asarray(S), 0.7))
    assert np.linalg.norm(got - twin) / np.linalg.norm(twin) < 1e-13


@pytest.mark.parametrize("kind", ["stokeslet", "stresslet"])
def test_pallas_df_fewer_sources_than_a_tile(kind):
    """The walkthrough's shape in small: many targets against 64 sources,
    fewer than one strip. The block shrinks to the next 128 * 2^k over the
    source count (`_strip_width`) and the padded lanes add exactly zero."""
    from skellysim_tpu.ops.pallas_df import DF_STRIP_W, _strip_width

    assert _strip_width(64, DF_STRIP_W) == 128
    assert _strip_width(400, 512) == 512 and _strip_width(400, 256) == 256
    assert _strip_width(6000, DF_STRIP_W) == DF_STRIP_W
    r_src = RNG.uniform(-5, 5, (64, 3))
    r_trg = np.concatenate([r_src[:10], RNG.uniform(-5, 5, (303, 3))])
    if kind == "stokeslet":
        pay, fn, oracle, twin = (RNG.standard_normal((64, 3)),
                                 stokeslet_pallas_df, _oracle_stokeslet,
                                 stokeslet_direct_df)
    else:
        pay, fn, oracle, twin = (RNG.standard_normal((64, 3, 3)),
                                 stresslet_pallas_df, _oracle_stresslet,
                                 stresslet_direct_df)
    args = (jnp.asarray(r_src), jnp.asarray(r_trg), jnp.asarray(pay), 1.0)
    got = np.asarray(fn(*args, interpret=True))
    ref = oracle(r_src, r_trg, pay)
    assert np.linalg.norm(got - ref) / np.linalg.norm(ref) < 5e-13
    b = np.asarray(twin(*args))
    assert np.linalg.norm(got - b) / np.linalg.norm(b) < 1e-13


@pytest.mark.parametrize("kw", [dict(strip_w=384), dict(strip_w=64),
                                dict(tile_s=384, strip_w=256),
                                dict(tile_t=12)])
def test_pallas_df_refuses_shapes_its_reduction_cannot_sum(kw):
    """The lane reduction (halving tree, then 7 lane rolls) is only correct
    for a strip of 128 * 2^k lanes, and a block is whole strips."""
    r_src, r_trg, f = _cloud(20, 10)
    with pytest.raises(ValueError, match="strip_w"):
        stokeslet_pallas_df(jnp.asarray(r_src), jnp.asarray(r_trg),
                            jnp.asarray(f), 1.0, interpret=True, **kw)


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


def _tiny_system(dtype=jnp.float64, **params):
    from __graft_entry__ import _make_system

    return _make_system(n_fibers=2, n_nodes=16, dtype=dtype, **params)


@pytest.mark.parametrize("requested", ["auto", "exact", "df", "pallas_df"])
@pytest.mark.parametrize("backend,auto", [("tpu", "pallas_df"), ("gpu", "df"),
                                          ("cpu", "exact")])
def test_refine_impl_follows_the_backend(monkeypatch, backend, auto,
                                         requested):
    """refine_pair_impl="auto": the fused Pallas tile on a TPU, the XLA
    double-float blocks on any other accelerator, native f64 on a CPU; an
    explicit value always wins."""
    import jax

    system, _ = _tiny_system(refine_pair_impl=requested)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert system._refine_impl == (auto if requested == "auto" else requested)


def test_refine_tile_is_announced(monkeypatch, caplog):
    """Tracing the mixed solve names the resolved refinement tile once, in
    the log and as a `refine_tile` event; no fault where the flows take it."""
    import logging

    import jax

    from skellysim_tpu.obs import tracer as obs_tracer

    system, state = _tiny_system(solver_precision="mixed")
    # a TPU in name only: tracing needs no chip, and nothing is lowered
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tr = obs_tracer.Tracer()
    with obs_tracer.use(tr), caplog.at_level(logging.INFO, "skellysim_tpu"):
        jax.eval_shape(system._solve_impl, state)
    (ev,) = [e for e in tr.events if e["ev"] == "refine_tile"]
    assert (ev["impl"], ev["requested"], ev["backend"]) == ("pallas_df",
                                                            "auto", "tpu")
    assert "refine_tile impl=pallas_df requested=auto backend=tpu" in caplog.text
    assert not [e for e in tr.events if e["ev"] == "fault"]


def test_refine_tile_mismatch_is_a_fault():
    """A run that resolved to the Pallas tile and takes another says so: an
    f32 state has no f64 flows, so its mixed solve runs `kernel_impl`."""
    import jax

    from skellysim_tpu.obs import tracer as obs_tracer

    system, state = _tiny_system(jnp.float32, solver_precision="mixed",
                                 refine_pair_impl="pallas_df")
    tr = obs_tracer.Tracer()
    with obs_tracer.use(tr):
        jax.eval_shape(system._solve_impl, state)
    (ev,) = [e for e in tr.events if e["ev"] == "refine_tile"]
    assert ev["impl"] == "exact"
    (fault,) = [e for e in tr.events if e["ev"] == "fault"]
    assert fault["kind"] == "refine_tile_mismatch"
    assert (fault["resolved"], fault["taken"]) == ("pallas_df", "exact")
