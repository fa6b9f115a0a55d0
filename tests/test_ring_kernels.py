"""Ring-pass kernels vs dense direct kernels on the 8-device virtual mesh.

The TPU analogue of the reference's kernel-backend consistency matrix
(`/root/reference/tests/core/kernel_test.cpp:1-120`): every backend must agree
with the ground-truth direct evaluation to tight tolerance (the reference
gates at 5e-9 in f64).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skellysim_tpu.ops import kernels
from skellysim_tpu.parallel import (make_mesh, ring_oseen_contract,
                                    ring_stokeslet, ring_stresslet)

N_DEV = 8
GATE = 5e-9  # `kernel_test.cpp:93`


@pytest.fixture(scope="module")
def mesh():
    assert jax.device_count() >= N_DEV
    return make_mesh(N_DEV)


@pytest.fixture(scope="module")
def cloud():
    rng = np.random.default_rng(7)
    n_src, n_trg = 4 * N_DEV * 3, 4 * N_DEV * 2
    r_src = jnp.asarray(rng.uniform(-1, 1, (n_src, 3)))
    r_trg = jnp.asarray(rng.uniform(-1, 1, (n_trg, 3)))
    f = jnp.asarray(rng.standard_normal((n_src, 3)))
    return r_src, r_trg, f


def _rel_err(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return np.abs(a - b).max() / max(np.abs(b).max(), 1e-300)


def test_ring_stokeslet_matches_direct(mesh, cloud):
    r_src, r_trg, f = cloud
    u_ring = ring_stokeslet(r_src, r_trg, f, 1.7, mesh=mesh)
    u_direct = kernels.stokeslet_direct(r_src, r_trg, f, 1.7)
    assert _rel_err(u_ring, u_direct) < GATE


def test_ring_stokeslet_self_term_masked(mesh):
    """Coincident source/target pairs must drop even across ring blocks."""
    rng = np.random.default_rng(3)
    pts = jnp.asarray(rng.uniform(-1, 1, (2 * N_DEV, 3)))
    f = jnp.asarray(rng.standard_normal((2 * N_DEV, 3)))
    u_ring = ring_stokeslet(pts, pts, f, 1.0, mesh=mesh)
    u_direct = kernels.stokeslet_direct(pts, pts, f, 1.0)
    assert np.all(np.isfinite(np.asarray(u_ring)))
    assert _rel_err(u_ring, u_direct) < GATE


def test_ring_stresslet_matches_direct(mesh, cloud):
    r_src, r_trg, _ = cloud
    rng = np.random.default_rng(11)
    S = jnp.asarray(rng.standard_normal((r_src.shape[0], 3, 3)))
    u_ring = ring_stresslet(r_src, r_trg, S, 0.9, mesh=mesh)
    u_direct = kernels.stresslet_direct(r_src, r_trg, S, 0.9)
    assert _rel_err(u_ring, u_direct) < GATE


def test_ring_oseen_contract_matches_direct(mesh, cloud):
    r_src, r_trg, f = cloud
    u_ring = ring_oseen_contract(r_src, r_trg, f, 1.2, mesh=mesh)
    u_direct = kernels.oseen_contract(r_src, r_trg, f, 1.2)
    assert _rel_err(u_ring, u_direct) < GATE


def test_ring_output_sharding(mesh, cloud):
    """The result stays sharded over the mesh (no implicit gather)."""
    r_src, r_trg, f = cloud
    u = ring_stokeslet(r_src, r_trg, f, 1.0, mesh=mesh)
    assert len(u.sharding.device_set) == N_DEV


def test_ring_mxu_impl_matches_single_program():
    """Ring evaluation with the MXU tiles agrees with the single-program
    exact kernels on well-separated points."""
    import numpy as np

    from skellysim_tpu.ops import kernels
    from skellysim_tpu.parallel import make_mesh
    from skellysim_tpu.parallel.ring import ring_stokeslet, ring_stresslet

    mesh = make_mesh(N_DEV)
    rng = np.random.default_rng(41)
    n = 8 * 16
    r = jnp.asarray(rng.uniform(-10, 10, (n, 3)))
    f = jnp.asarray(rng.standard_normal((n, 3)))
    S = jnp.asarray(rng.standard_normal((n, 3, 3)))
    ref = kernels.stokeslet_direct(r, r, f, 1.2)
    out = ring_stokeslet(r, r, f, 1.2, mesh=mesh, impl="mxu")
    err = np.linalg.norm(np.asarray(out - ref)) / np.linalg.norm(np.asarray(ref))
    assert err < 1e-9, err
    ref_s = kernels.stresslet_direct(r, r, S, 1.2)
    out_s = ring_stresslet(r, r, S, 1.2, mesh=mesh, impl="mxu")
    err = np.linalg.norm(np.asarray(out_s - ref_s)) / np.linalg.norm(np.asarray(ref_s))
    assert err < 1e-9, err


def test_ring_df_fast_agreement(mesh):
    """Non-slow DF-ring coverage on the 8-device virtual mesh (the slow twin
    below adds the pallas_df interpret tiles): the mixed solver's refinement
    matvec path must be exercised in the per-commit tier."""
    from skellysim_tpu.parallel.ring import (ring_stokeslet_df,
                                             ring_stresslet_df)

    rng = np.random.default_rng(47)
    n = 8 * 4
    r = jnp.asarray(rng.uniform(-3, 3, (n, 3)), dtype=jnp.float64)
    f = jnp.asarray(rng.standard_normal((n, 3)), dtype=jnp.float64)
    S = jnp.asarray(rng.standard_normal((n, 3, 3)), dtype=jnp.float64)

    out = ring_stokeslet_df(r, r, f, 1.3, mesh=mesh)
    assert out.dtype == jnp.float64
    ref = kernels.stokeslet_direct(r, r, f, 1.3)
    err = np.linalg.norm(np.asarray(out - ref)) / np.linalg.norm(
        np.asarray(ref))
    assert err < 1e-12, err

    out_s = ring_stresslet_df(r, r, S, 1.3, mesh=mesh)
    ref_s = kernels.stresslet_direct(r, r, S, 1.3)
    err = (np.linalg.norm(np.asarray(out_s - ref_s))
           / np.linalg.norm(np.asarray(ref_s)))
    assert err < 1e-12, err


@pytest.mark.slow
def test_ring_df_tiles_match_f64_direct():
    """Double-float ring tiles (the mixed solver's refinement matvec on a
    mesh) reach DF-class agreement with native-f64 dense kernels — f32
    inputs, f64 output, no emulated f64 in the pair arithmetic."""
    from skellysim_tpu.parallel.ring import (ring_stokeslet_df,
                                             ring_stresslet_df)

    mesh = make_mesh(N_DEV)
    rng = np.random.default_rng(43)
    n = 8 * 16
    r64 = rng.uniform(-3, 3, (n, 3))
    f64 = rng.standard_normal((n, 3))
    S64 = rng.standard_normal((n, 3, 3))
    r, f, S = (jnp.asarray(a, dtype=jnp.float64) for a in (r64, f64, S64))

    ref = kernels.stokeslet_direct(r, r, f, 1.2)
    out = ring_stokeslet_df(r, r, f, 1.2, mesh=mesh)
    assert out.dtype == jnp.float64
    err = np.linalg.norm(np.asarray(out - ref)) / np.linalg.norm(np.asarray(ref))
    assert err < 1e-12, err

    ref_s = kernels.stresslet_direct(r, r, S, 1.2)
    out_s = ring_stresslet_df(r, r, S, 1.2, mesh=mesh)
    err = (np.linalg.norm(np.asarray(out_s - ref_s))
           / np.linalg.norm(np.asarray(ref_s)))
    assert err < 1e-12, err

    # the fused Pallas DF tiles ride the same ring (interpret mode here):
    # same DF-class agreement against the native-f64 dense kernels
    out_p = ring_stokeslet_df(r, r, f, 1.2, mesh=mesh, impl="pallas_df")
    err = np.linalg.norm(np.asarray(out_p - ref)) / np.linalg.norm(
        np.asarray(ref))
    assert err < 1e-12, err
    out_ps = ring_stresslet_df(r, r, S, 1.2, mesh=mesh, impl="pallas_df")
    err = (np.linalg.norm(np.asarray(out_ps - ref_s))
           / np.linalg.norm(np.asarray(ref_s)))
    assert err < 1e-12, err


def test_ring_pallas_impl_matches_single_program():
    """Ring evaluation with the Pallas VMEM tiles (interpret mode on the CPU
    test mesh) agrees with the single-program exact kernels; f64 operands
    fall back to the exact tile like the `ops.kernels` seam."""
    import numpy as np

    from skellysim_tpu.ops import kernels
    from skellysim_tpu.parallel import make_mesh
    from skellysim_tpu.parallel.ring import ring_stokeslet, ring_stresslet

    mesh = make_mesh(N_DEV)
    rng = np.random.default_rng(43)
    n = 8 * 8
    r = jnp.asarray(rng.uniform(-10, 10, (n, 3)), dtype=jnp.float32)
    f = jnp.asarray(rng.standard_normal((n, 3)), dtype=jnp.float32)
    S = jnp.asarray(rng.standard_normal((n, 3, 3)), dtype=jnp.float32)
    ref = kernels.stokeslet_direct(r, r, f, 1.2)
    out = ring_stokeslet(r, r, f, 1.2, mesh=mesh, impl="pallas")
    err = np.linalg.norm(np.asarray(out - ref)) / np.linalg.norm(np.asarray(ref))
    assert err < 1e-5, err
    ref_s = kernels.stresslet_direct(r, r, S, 1.2)
    out_s = ring_stresslet(r, r, S, 1.2, mesh=mesh, impl="pallas")
    err = np.linalg.norm(np.asarray(out_s - ref_s)) / np.linalg.norm(np.asarray(ref_s))
    assert err < 1e-5, err

    # f64 operands route to the exact tile bit-for-bit
    r64 = jnp.asarray(np.asarray(r), dtype=jnp.float64)
    f64 = jnp.asarray(np.asarray(f), dtype=jnp.float64)
    out64 = ring_stokeslet(r64, r64, f64, 1.2, mesh=mesh, impl="pallas")
    ref64 = ring_stokeslet(r64, r64, f64, 1.2, mesh=mesh, impl="exact")
    np.testing.assert_array_equal(np.asarray(out64), np.asarray(ref64))


# ------------------------------------------------------- fused ring (ISSUE 8)

def test_fused_ring_traces_with_correct_shapes():
    """The fused Pallas ring kernel (`parallel.ring_fused`) abstract-evals
    inside shard_map with the ring contract's shapes — compiled execution
    is TPU-only (`python chip_smoke.py --chips 4`; the compile itself is
    pinned in tests/test_chip_compile.py), but shape/trace regressions
    must fail on CPU CI too."""
    from jax.sharding import PartitionSpec as P

    from skellysim_tpu.parallel.ring_fused import fused_ring_block_sum

    mesh = make_mesh(4)
    st = jax.ShapeDtypeStruct((64, 3), jnp.float32)
    out = jax.eval_shape(
        jax.shard_map(lambda r, s, f: fused_ring_block_sum(
            "stokeslet", r, s, f, axis_name="fib", n_dev=4),
            mesh=mesh, in_specs=(P("fib"),) * 3, out_specs=P("fib"),
            check_vma=False), st, st, st)
    assert out.shape == (64, 3) and out.dtype == jnp.float32
    # stresslet family: [ns, 3, 3] payload
    out = jax.eval_shape(
        jax.shard_map(lambda r, s, f: fused_ring_block_sum(
            "stresslet", r, s, f, axis_name="fib", n_dev=4),
            mesh=mesh,
            in_specs=(P("fib"), P("fib"), P("fib", None, None)),
            out_specs=P("fib"), check_vma=False),
        st, st, jax.ShapeDtypeStruct((64, 3, 3), jnp.float32))
    assert out.shape == (64, 3)


@pytest.mark.parametrize("kind", ["stokeslet", "stresslet"])
def test_fused_ring_interpret_matches_ppermute_ring(monkeypatch, kind):
    """The fused RDMA ring EXECUTES off the chip on the TPU interpreter
    (`pltpu.InterpretParams`: remote DMA + semaphores emulated on the
    virtual CPU devices), padded comm slots and all, and agrees with the
    `lax.ppermute` ring on the same Pallas tile math."""
    n_dev, n = 4, 4 * 40           # 40 rows per shard: not a tile multiple
    mesh = make_mesh(n_dev)
    rng = np.random.default_rng(7)
    r = jnp.asarray(rng.uniform(-10, 10, (n, 3)), dtype=jnp.float32)
    tail = (3,) if kind == "stokeslet" else (3, 3)
    pay = jnp.asarray(rng.standard_normal((n,) + tail), dtype=jnp.float32)
    ring = ring_stokeslet if kind == "stokeslet" else ring_stresslet

    def run(mode):
        monkeypatch.setenv("SKELLY_FUSED_RING", mode)
        jax.clear_caches()          # the mode is read at trace time
        return np.asarray(ring(r, r, pay, 1.2, mesh=mesh, impl="pallas"))

    fused, ppermute = run("interpret"), run("ppermute")
    err = np.linalg.norm(fused - ppermute) / np.linalg.norm(ppermute)
    assert err < 1e-5, err
    assert not np.array_equal(fused, np.zeros_like(fused))


def test_fused_ring_fits_budget():
    # the budget constant moved to the audit analyzer (single source of
    # truth shared by this build-time gate and the `dma` audit check)
    from skellysim_tpu.audit.dmaflow import VMEM_PAIR_BUDGET
    from skellysim_tpu.parallel.ring_fused import fused_ring_fits

    assert fused_ring_fits("stokeslet", 64, 64, 8)
    assert fused_ring_fits("stresslet", 512, 2048, 8)
    # beyond the whole-block VMEM budget: bandwidth-bound, keep ppermute
    assert not fused_ring_fits("stokeslet", 4096, 4096, 8)
    assert 4096 * 4096 > VMEM_PAIR_BUDGET
    # the n_dev-slot comm buffer has its own budget (slots are never
    # reused within an instance — the ring-safety scheme)
    assert not fused_ring_fits("stresslet", 8, 2048, 256)
    # unknown kernel families never take the fused path
    assert not fused_ring_fits("oseen", 8, 8, 8)


def test_ring_cpu_build_selects_ppermute(mesh, cloud):
    """On the CPU backend the build-time seam keeps every ring on
    ppermute — results bit-match a build with the fused path explicitly
    disabled (i.e. the dispatch really did not take the fused branch)."""
    r_src, r_trg, f = cloud
    rs, rt, f32 = (r_src.astype(jnp.float32), r_trg.astype(jnp.float32),
                   f.astype(jnp.float32))
    u_default = ring_stokeslet(rs, rt, f32, 1.0, mesh=mesh, impl="exact")
    import os

    os.environ["SKELLY_FUSED_RING"] = "0"
    try:
        jax.clear_caches()
        u_off = ring_stokeslet(rs, rt, f32, 1.0, mesh=mesh, impl="exact")
    finally:
        os.environ.pop("SKELLY_FUSED_RING", None)
    assert np.array_equal(np.asarray(u_default), np.asarray(u_off))
