"""Pallas kernels vs XLA direct kernels (interpret mode on CPU).

Extends the backend-consistency matrix (SURVEY.md §4.1) to the Pallas
backend; on real TPU hardware the same comparisons run compiled.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from skellysim_tpu.ops import kernels
from skellysim_tpu.ops.pallas_kernels import stokeslet_pallas, stresslet_pallas

GATE_F64 = 5e-9   # `kernel_test.cpp:93`
GATE_F32 = 2e-4   # f32 accumulation over ~1k sources

#: the strip geometry's edges, each (n_src, n_trg, tile keywords) over the
#: module's cloud: "cloud" 700 sources (no multiple of a 256-lane strip) onto
#: 300 targets (no multiple of 8 or of the 128-row tile) in three source
#: blocks, so the lane sums are carried across the grid; "n64" 64 sources
#: (one 128-lane strip, half of it sentinel padding) onto 75 targets at the
#: default tile; "strips" 16-row tiles and 128-lane strips, two chunks a
#: block; "self" the first 300 sources as the targets, so every target sits
#: on a source (its own pair contributes zero), at the default tile;
#: "balanced" two blocks of 384 lanes where 512-lane blocks would pad 700
#: sources to 1,024, three 128-lane chunks a loop trip
GEOMETRY = {
    "cloud": (700, 300, dict(tile_t=128, tile_s=256)),
    "n64": (64, 75, {}),
    "strips": (700, 300, dict(tile_t=16, tile_s=256, strip_w=128)),
    "self": (700, None, {}),
    "balanced": (700, 300, dict(tile_s=512, strip_w=128)),
}


def _case(cloud, case, dtype):
    n_src, n_trg, tile = GEOMETRY[case]
    r_src, r_trg, f = cloud
    r_trg = r_src[:300] if n_trg is None else r_trg[:n_trg]
    return ([jnp.asarray(a, dtype=dtype) for a in (r_src[:n_src], r_trg,
                                                   f[:n_src])], tile)


@pytest.fixture(scope="module")
def cloud():
    rng = np.random.default_rng(21)
    n_src, n_trg = 700, 300   # deliberately not tile multiples
    r_src = rng.uniform(-2, 2, (n_src, 3))
    r_trg = rng.uniform(-2, 2, (n_trg, 3))
    f = rng.standard_normal((n_src, 3))
    return r_src, r_trg, f


def _rel_err(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return np.abs(a - b).max() / max(np.abs(b).max(), 1e-300)


@pytest.mark.parametrize("dtype,gate,case", [
    pytest.param(jnp.float64, GATE_F64, "cloud", id="float64-5e-09"),
    pytest.param(jnp.float32, GATE_F32, "cloud", id="float32-0.0002"),
    *(pytest.param(jnp.float32, GATE_F32, case, id=f"float32-{case}")
      for case in ("n64", "strips", "self", "balanced"))])
def test_stokeslet_pallas_matches_direct(cloud, dtype, gate, case):
    (r_src, r_trg, f), tile = _case(cloud, case, dtype)
    u_p = stokeslet_pallas(r_src, r_trg, f, 1.3, interpret=True, **tile)
    u_d = kernels.stokeslet_direct(r_src, r_trg, f, 1.3)
    assert np.all(np.isfinite(np.asarray(u_p)))
    assert _rel_err(u_p, u_d) < gate


def test_stokeslet_pallas_self_term(cloud):
    """Coincident points drop; padded sources contribute exactly zero."""
    r_src, _, f = cloud
    pts = jnp.asarray(r_src, dtype=jnp.float64)
    ff = jnp.asarray(f, dtype=jnp.float64)
    u_p = stokeslet_pallas(pts, pts, ff, 1.0, tile_t=128, tile_s=256,
                           interpret=True)
    u_d = kernels.stokeslet_direct(pts, pts, ff, 1.0)
    assert np.all(np.isfinite(np.asarray(u_p)))
    assert _rel_err(u_p, u_d) < GATE_F64


@pytest.mark.parametrize("dtype,gate,case", [
    pytest.param(jnp.float64, GATE_F64, "cloud", id="float64-5e-09"),
    pytest.param(jnp.float32, 5e-4, "cloud", id="float32-0.0005"),
    *(pytest.param(jnp.float32, 5e-4, case, id=f"float32-{case}")
      for case in ("n64", "strips", "self", "balanced"))])
def test_stresslet_pallas_matches_direct(cloud, dtype, gate, case):
    """The 9-component payload: 3 + 9 source rows padded to 16."""
    (r_src, r_trg, _), tile = _case(cloud, case, dtype)
    rng = np.random.default_rng(33)
    S = jnp.asarray(rng.standard_normal((r_src.shape[0], 3, 3)), dtype=dtype)
    u_p = stresslet_pallas(r_src, r_trg, S, 0.8, interpret=True, **tile)
    u_d = kernels.stresslet_direct(r_src, r_trg, S, 0.8)
    assert np.all(np.isfinite(np.asarray(u_p)))
    assert _rel_err(u_p, u_d) < gate


@pytest.mark.parametrize("kind", ["stokeslet", "stresslet"])
def test_fused_ring_block_sums_match_the_strip_tile(cloud, kind):
    """The fused ring's whole-block sums (`stokeslet_tile_sums` /
    `stresslet_tile_sums`) and the gridded strip tile on one block: the
    same per-pair expression, summed in another order."""
    from skellysim_tpu.ops import pallas_kernels as pk

    r_src, r_trg, f = (jnp.asarray(a[:n], jnp.float32)
                       for a, n in zip(cloud, (384, 96, 384)))
    if kind == "stresslet":
        f = jnp.asarray(np.random.default_rng(5).standard_normal((384, 3, 3)),
                        jnp.float32)
    sums = getattr(pk, f"{kind}_tile_sums")(r_trg.T, r_src.T,
                                            f.reshape(384, -1).T)
    block = getattr(pk, f"{kind}_pallas_block")(r_trg, r_src, f,
                                                interpret=True)
    assert _rel_err(jnp.stack(sums, axis=1), block) < 1e-6


@pytest.mark.parametrize("n_src,n_trg,tile,expect", [
    (64, 6464, (256, 2048, 512), (256, 128, 128, 6656, 128)),
    (400, 6064, (256, 2048, 512), (256, 512, 512, 6144, 512)),
    (6000, 464, (256, 2048, 512), (256, 2048, 512, 512, 6144)),
    (16384, 16384, (256, 2048, 1024), (256, 2048, 1024, 16384, 16384)),
    (700, 5, (128, 256, 512), (8, 256, 256, 8, 768)),
    (6431, 32768, (128, 4096, 512), (128, 3584, 512, 32768, 7168)),
    (6000, 464, (128, 4096, 512), (128, 3072, 512, 512, 6144)),
    (39199, 32768, (128, 4096, 512), (128, 4096, 512, 32768, 40960))])
def test_geometry_follows_the_call(n_src, n_trg, tile, expect):
    """(tile_t, tile_s, strip, padded targets, padded sources): the strip
    the next 128 * 2^k lanes over the sources up to the strip and the
    block, the sources in the fewest blocks that hold them, each of equal
    whole strips, the block no taller than the padded targets."""
    from skellysim_tpu.ops.pallas_kernels import _geometry

    assert _geometry(n_trg, n_src, *tile) == expect


@pytest.mark.parametrize("tile", [(12, 2048, 512), (256, 384, 128),
                                  (256, 2048, 320)])
def test_geometry_refuses_what_the_strip_cannot_walk(tile):
    from skellysim_tpu.ops.pallas_kernels import _geometry

    with pytest.raises(ValueError):
        _geometry(300, 700, *tile)


def test_f32_tile_event_names_the_geometry(cloud, caplog):
    """Each shape a build traces says which geometry its pair sum took, in
    the log and as an ``f32_tile`` event `obs summarize` lists under the
    pair tile's section."""
    import logging

    import jax

    from skellysim_tpu.obs import tracer as obs_tracer
    from skellysim_tpu.obs.summarize import Summary

    r_src, r_trg, f = (jnp.asarray(a, jnp.float32) for a in cloud)
    tr = obs_tracer.Tracer()
    with obs_tracer.use(tr), caplog.at_level(logging.INFO, "skellysim_tpu"):
        jax.eval_shape(lambda s, t, p: stokeslet_pallas(
            s, t, p, 1.0, tile_t=64, tile_s=512, strip_w=256,
            interpret=True), r_src[:333], r_trg[:77], f[:333])
    (ev,) = [e for e in tr.events if e["ev"] == "f32_tile"]
    assert {k: ev[k] for k in ("kind", "n_trg", "n_src", "tile", "strip_w",
                               "grid")} == dict(
        kind="stokeslet", n_trg=77, n_src=333, tile="64x512", strip_w=256,
        grid="2x1")
    assert "f32_tile kind=stokeslet n_trg=77 n_src=333" in caplog.text
    report = Summary()
    report.add_record(ev)
    report.add_record({"ev": "pair_tile", "impl": "pallas"})
    text = report.render()
    assert text.count("== pair tile (Krylov loop) ==") == 1
    assert ("f32_tile kind=stokeslet n_trg=77 n_src=333 tile=64x512 "
            "strip_w=256 grid=2x1") in text


def test_pallas_tile_under_vmap_matches_each_member(cloud):
    """Two members in one call, as the ensemble's vmapped step makes
    them: `pallas_call`'s batching rule gives the grid a member axis, and
    each member's lane sums still start at its own first source block."""
    import jax

    r_src, r_trg, f = (jnp.asarray(a, jnp.float32) for a in cloud)
    members = (jnp.stack([r_src[:300], r_src[300:600]]),
               jnp.stack([r_trg[:100], r_trg[100:200]]),
               jnp.stack([f[:300], 2.0 * f[300:600]]))
    both = jax.vmap(lambda s, t, p: stokeslet_pallas(
        s, t, p, 1.0, tile_t=64, tile_s=256, strip_w=128,
        interpret=True))(*members)
    for m in range(2):
        one = kernels.stokeslet_direct(*(a[m] for a in members), 1.0)
        assert _rel_err(both[m], one) < GATE_F32


def test_pallas_reachable_through_kernel_seam():
    """kernel_impl="pallas" dispatches through the production seam
    (round-3 verdict: no unreachable production code path) — interpret
    mode off-TPU, Mosaic on real chips."""
    import numpy as np

    from skellysim_tpu.ops import kernels
    from skellysim_tpu.params import Params
    from skellysim_tpu.system import System

    rng = np.random.default_rng(3)
    r = jnp.asarray(rng.uniform(-2, 2, (96, 3)), dtype=jnp.float32)
    f = jnp.asarray(rng.standard_normal((96, 3)), dtype=jnp.float32)
    u = np.asarray(kernels.stokeslet_direct(r, r, f, 1.3, impl="pallas"))
    ref = np.asarray(kernels.stokeslet_direct(r, r, f, 1.3))
    assert np.linalg.norm(u - ref) / np.linalg.norm(ref) < 1e-5
    S = jnp.asarray(rng.standard_normal((96, 3, 3)), dtype=jnp.float32)
    uS = np.asarray(kernels.stresslet_direct(r, r, S, 1.3, impl="pallas"))
    refS = np.asarray(kernels.stresslet_direct(r, r, S, 1.3))
    assert np.linalg.norm(uS - refS) / np.linalg.norm(refS) < 1e-5
    # the Params knob validates (typos rejected at System construction)
    System(Params(kernel_impl="pallas", adaptive_timestep_flag=False))
    import pytest

    with pytest.raises(ValueError):
        System(Params(kernel_impl="palas", adaptive_timestep_flag=False))


def test_pallas_seam_f64_falls_back_to_exact():
    """The pallas tier is f32-only by contract: f64 inputs through the
    dispatch take the exact XLA path bit-for-bit (Mosaic has no f64 on
    TPU; the accuracy tiers are "exact"/"df")."""
    rng = np.random.default_rng(7)
    r = jnp.asarray(rng.uniform(-2, 2, (64, 3)), dtype=jnp.float64)
    f = jnp.asarray(rng.standard_normal((64, 3)), dtype=jnp.float64)
    u = np.asarray(kernels.stokeslet_direct(r, r, f, 1.1, impl="pallas"))
    ref = np.asarray(kernels.stokeslet_direct(r, r, f, 1.1))
    np.testing.assert_array_equal(u, ref)
    S = jnp.asarray(rng.standard_normal((64, 3, 3)), dtype=jnp.float64)
    uS = np.asarray(kernels.stresslet_direct(r, r, S, 1.1, impl="pallas"))
    refS = np.asarray(kernels.stresslet_direct(r, r, S, 1.1))
    np.testing.assert_array_equal(uS, refS)
