"""The chip's compiler, without the chip: the Pallas kernels of the main
path compiled at real widths for a DESCRIBED TPU v5e 2x2 topology.

Interpret mode hides what Mosaic refuses (a 6-sublane comm-slot slice, an
i64 rotate shift); these compiles do not. Nothing runs — a pass says the
kernel compiles for the chip, never that it computes the right thing
(`chip_smoke.py` is the run). The topology is described inside a
module-scoped fixture, never at import: only one process may load the TPU
library, and every xdist worker imports this file. All chip compiles live
in THIS file for the same reason.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

F32 = jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *structs):
    return jax.jit(fn).lower(*structs).compile().as_text()


def _cloud(n_src, n_trg, payload_tail, dtype, sharding):
    def st(shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return st((n_src, 3)), st((n_trg, 3)), st((n_src,) + payload_tail)


@pytest.mark.parametrize("kind,payload_tail", [("stokeslet", (3,)),
                                               ("stresslet", (3, 3))])
def test_f32_pallas_tile_compiles(one_chip, kind, payload_tail):
    """The f32 VMEM tiles at the 1,024-fiber smoke scene's width (65,536^2
    pairs) — under x64, as every CLI runs them."""
    from skellysim_tpu.ops import pallas_kernels

    fn = getattr(pallas_kernels, f"{kind}_pallas")
    r_src, r_trg, pay = _cloud(65536, 65536, payload_tail, F32, one_chip)
    assert jax.config.jax_enable_x64
    text = _compile(lambda s, t, p: fn(s, t, p, 1.0), r_src, r_trg, pay)
    assert "tpu_custom_call" in text


# every cell's f32 pair sum: the fiber cells' square sum and a ring block,
# the ellipsoid's fibers onto all nodes and its shell onto the fibers (one
# chip, a ring block of four), oocyte's both ways, the walkthrough's three
@pytest.mark.parametrize("kind,n_src,n_trg", [
    ("stokeslet", 16384, 16384), ("stokeslet", 16384, 24384),
    ("stokeslet", 16384, 18384), ("stokeslet", 32768, 39199),
    ("stokeslet", 64, 6464), ("stresslet", 8000, 16384),
    ("stresslet", 2000, 16384), ("stresslet", 6431, 32768),
    ("stresslet", 6000, 464), ("stresslet", 400, 6064)])
def test_f32_pallas_tile_compiles_at_the_cells_shapes(one_chip, kind, n_src,
                                                      n_trg):
    """The f32 strip tile at the geometry each cell's shape gives it (the
    strip and the block clipped to the shapes), under x64."""
    from skellysim_tpu.ops import pallas_kernels

    fn = getattr(pallas_kernels, f"{kind}_pallas")
    tail = (3,) if kind == "stokeslet" else (3, 3)
    r_src, r_trg, pay = _cloud(n_src, n_trg, tail, F32, one_chip)
    text = _compile(lambda s, t, p: fn(s, t, p, 1.0), r_src, r_trg, pay)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kind,payload_tail", [("stokeslet", (3,)),
                                               ("stresslet", (3, 3))])
def test_f32_pallas_tile_compiles_under_vmap(one_chip, kind, payload_tail):
    """Two members' sums in one call, as the ensemble's vmapped step makes
    them: the grid grows a member axis and keeps its scratch."""
    from skellysim_tpu.ops import pallas_kernels

    fn = getattr(pallas_kernels, f"{kind}_pallas")

    def st(shape):
        return jax.ShapeDtypeStruct((2,) + shape, F32, sharding=one_chip)

    text = _compile(jax.vmap(lambda s, t, p: fn(s, t, p, 1.0)),
                    st((4096, 3)), st((4160, 3)), st((4096,) + payload_tail))
    assert "tpu_custom_call" in text


# the shapes the benchmark's cells run: the fiber cell's square sum, then
# the walkthrough's 6,464 nodes against its shell, body and fiber sources
@pytest.mark.parametrize("kind,payload_tail,n_src,n_trg", [
    ("stokeslet", (3,), 16384, 16384), ("stresslet", (3, 3), 16384, 16384),
    ("stresslet", (3, 3), 6000, 6464), ("stresslet", (3, 3), 400, 6464),
    ("stokeslet", (3,), 64, 6464)])
def test_pallas_df_tile_compiles(one_chip, kind, payload_tail, n_src, n_trg):
    """The double-float Pallas tiles, f64 in / f64 out under x64 (Mosaic
    refused the lane-roll's i64 shift before PR 22, and an i64 strip
    counter and a 12-row source block in PR 27)."""
    from skellysim_tpu.ops import pallas_df

    fn = getattr(pallas_df, f"{kind}_pallas_df")
    r_src, r_trg, pay = _cloud(n_src, n_trg, payload_tail, jnp.float64,
                               one_chip)
    text = _compile(lambda s, t, p: fn(s, t, p, 1.0), r_src, r_trg, pay)
    assert "tpu_custom_call" in text


def test_pallas_df_tile_compiles_under_vmap(one_chip):
    """Two members' sums in one call, as the ensemble's vmapped step issues
    them: the `pallas_call` grows a grid axis and keeps its scratch."""
    from skellysim_tpu.ops.pallas_df import stokeslet_pallas_df

    def st(shape):
        return jax.ShapeDtypeStruct((2,) + shape, jnp.float64,
                                    sharding=one_chip)

    text = _compile(jax.vmap(lambda s, t, p: stokeslet_pallas_df(s, t, p,
                                                                 1.0)),
                    st((4096, 3)), st((4096, 3)), st((4096, 3)))
    assert "tpu_custom_call" in text


def test_pallas_df_ring_compiles_on_four_chips(topo, monkeypatch):
    """`ring_stokeslet_df(impl="pallas_df")`, the tile `step_spmd` takes on
    a TPU mesh, 4,096 nodes a shard. The ring asks the backend whether to
    interpret; here it is told what the described chips are."""
    from skellysim_tpu.parallel.mesh import FIBER_AXIS
    from skellysim_tpu.parallel.ring import ring_stokeslet_df

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n_dev, rows = 4, 4096
    mesh = Mesh(topo.devices[:n_dev], (FIBER_AXIS,))
    r_src, r_trg, f = _cloud(n_dev * rows, n_dev * rows, (3,), jnp.float64,
                             NamedSharding(mesh, P(FIBER_AXIS)))
    text = _compile(lambda s, t, p: ring_stokeslet_df(s, t, p, 1.0, mesh=mesh,
                                                      impl="pallas_df"),
                    r_src, r_trg, f)
    assert "tpu_custom_call" in text


# a chip's share of `ellipsoid_mesh4.run`: the fibers' Stokeslet from 16,384
# sources a ring block onto its 16,384 fiber + 2,000 shell rows, the shell's
# double layer from 2,000 sources a block onto its 16,384 fiber nodes; and of
# `oocyte_mesh4.run`: 24,000 fiber sources onto 24,000 + 1,608 padded shell
# rows, 1,608 shell sources onto 24,000 fiber nodes; then the one-chip sums
# of `ellipsoid_256.run` and of the walkthrough
@pytest.mark.parametrize("kind,payload_tail,src_rows,trg_rows", [
    ("stokeslet", (3,), 16384, 18384), ("stresslet", (3, 3), 2000, 16384),
    ("stokeslet", (3,), 24000, 25608), ("stresslet", (3, 3), 1608, 24000)])
def test_auto_ring_takes_the_pallas_tile_on_four_chips(
        topo, monkeypatch, kind, payload_tail, src_rows, trg_rows):
    """`ring_flow_local(impl="auto")`, as the mesh step calls it inside its
    `shard_map`, told that the described chips are a TPU: f32 operands take
    the Mosaic tile in the `ppermute` ring (the shapes are past the fused
    ring's budget)."""
    from skellysim_tpu.parallel.mesh import FIBER_AXIS
    from skellysim_tpu.parallel.ring import ring_flow_local

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n_dev = 4
    mesh = Mesh(topo.devices[:n_dev], (FIBER_AXIS,))
    sharded = NamedSharding(mesh, P(FIBER_AXIS))
    r_src, _, pay = _cloud(n_dev * src_rows, 0, payload_tail, F32, sharded)
    r_trg = jax.ShapeDtypeStruct((n_dev * trg_rows, 3), F32, sharding=sharded)
    spec = P(FIBER_AXIS)

    def flow(trg, src, p):
        return jax.shard_map(
            lambda t, s, q: ring_flow_local(kind, "auto", t, s, q, 1.0,
                                            axis_name=FIBER_AXIS,
                                            n_dev=n_dev),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)(trg, src,
                                                                    p)

    text = _compile(flow, r_trg, r_src, pay)
    assert "tpu_custom_call" in text and "collective-permute" in text


@pytest.mark.parametrize("kind,payload_tail,n_src,n_trg", [
    ("stokeslet", (3,), 16384, 24384), ("stresslet", (3, 3), 8000, 16384),
    ("stresslet", (3, 3), 6000, 464)])
def test_auto_direct_seam_takes_the_pallas_tile(one_chip, monkeypatch, kind,
                                                payload_tail, n_src, n_trg):
    """`kernels.*_direct(impl="auto")` on f32 operands, the backend read as
    a TPU: the Mosaic tile, at sizes that are no multiples of it."""
    from skellysim_tpu.ops import kernels

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn = getattr(kernels, f"{kind}_direct")
    r_src, r_trg, pay = _cloud(n_src, n_trg, payload_tail, F32, one_chip)
    text = _compile(lambda s, t, p: fn(s, t, p, 1.0, impl="auto"), r_src,
                    r_trg, pay)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kind,payload_tail", [("stokeslet", (3,)),
                                               ("stresslet", (3, 3))])
def test_fused_ring_compiles_on_four_chips(topo, kind, payload_tail):
    """The fused RDMA ring under shard_map on the four described chips,
    1,024 rows per shard (Mosaic refused the 6/12-sublane comm slots
    before PR 22), at a shape `fused_ring_fits` accepts."""
    from skellysim_tpu.parallel.mesh import FIBER_AXIS
    from skellysim_tpu.parallel.ring_fused import (fused_ring_block_sum,
                                                   fused_ring_fits)

    n_dev, rows = 4, 1024
    assert fused_ring_fits(kind, rows, rows, n_dev)
    mesh = Mesh(topo.devices[:n_dev], (FIBER_AXIS,))
    spec = NamedSharding(mesh, P(FIBER_AXIS))
    r_src, r_trg, pay = _cloud(n_dev * rows, n_dev * rows, payload_tail, F32,
                               spec)
    ring = jax.shard_map(
        lambda t, s, p: fused_ring_block_sum(kind, t, s, p,
                                             axis_name=FIBER_AXIS,
                                             n_dev=n_dev),
        mesh=mesh, in_specs=(P(FIBER_AXIS),) * 3, out_specs=P(FIBER_AXIS))
    text = _compile(ring, r_trg, r_src, pay)
    assert "tpu_custom_call" in text


def test_df_direct_tile_compiles(one_chip):
    """The XLA double-float tile (the refinement tile of accelerators other
    than a TPU, and the twin the Pallas tile is tested against) at 8,192^2."""
    from skellysim_tpu.ops.df_kernels import stokeslet_direct_df

    r_src, r_trg, f = _cloud(8192, 8192, (3,), jnp.float64, one_chip)
    _compile(lambda s, t, p: stokeslet_direct_df(s, t, p, 1.0), r_src, r_trg,
             f)


def test_f64_direct_tile_compiles(one_chip):
    """The native-f64 exact tile (emulated on the chip) at 8,192^2 — the
    tile `resolve_impl` swaps in for f64 operands."""
    from skellysim_tpu.ops.kernels import stokeslet_direct

    r_src, r_trg, f = _cloud(8192, 8192, (3,), jnp.float64, one_chip)
    _compile(lambda s, t, p: stokeslet_direct(s, t, p, 1.0), r_src, r_trg, f)


@pytest.mark.parametrize("blocks,width", [(256, 256), (1, 1206),
                                          (1024, 128), (750, 128)])
def test_block_inverse_compiles(one_chip, blocks, width):
    """The mixed tier's block preconditioner (`ops.block_precond`) at the
    benchmark's widths — 256 fiber blocks of 256^2, the walkthrough body's
    1,206^2, `oocyte_1024.run`'s 1,024 blocks of 128^2 and a chip's 750 of
    them in `oocyte_mesh4.run`: the triangular solves (XLA's `InvertDiagBlocks*` custom calls
    on a TPU) stay where the inverse is formed, once a step; an application
    holds none."""
    from collections import namedtuple

    from skellysim_tpu.ops import block_precond

    def st(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    formed = _compile(lambda a: block_precond.factor(a, F32)[2],
                      st((blocks, width, width), jnp.float64))
    assert "LuDecomposition" in formed and "InvertDiagBlocks" in formed
    stored = namedtuple("Stored", "lu piv inv")
    applied = _compile(
        lambda inv, x: block_precond.solve(stored(None, None, inv), x),
        st((blocks, width, width), F32), st((blocks, width), jnp.float64))
    assert "InvertDiagBlocks" not in applied
    assert "triangular" not in applied.lower()


@pytest.mark.parametrize("blocks,rows,cols,shared,vmapped", [
    (256, 256, 256, False, False), (256, 192, 256, False, False),
    (256, 248, 256, True, False), (256, 64, 128, True, False),
    (1, 256, 256, False, False), (16, 256, 256, False, True),
    (1024, 128, 128, False, False), (1024, 96, 128, False, False),
    (1024, 120, 128, True, False), (1024, 32, 128, True, False),
    (750, 128, 128, False, False)])
def test_block_df_tile_compiles(one_chip, blocks, rows, cols, shared, vmapped):
    """The double-float block matvec (`ops.block_df`) at the fiber cells'
    shapes — 256 blocks of `A_bc` 256^2 and of the force operator 192 x 256,
    the shared `P_down` (242 rows, split to 248) and `D1` (64 columns, split
    to 128 lanes) — the walkthrough's one block, under a `vmap` of two
    members as the ensemble calls it, and the 32-node bucket of
    `oocyte_1024.run` (1,024 blocks of 128^2, 32 a grid step; the force
    operator 96 x 128, `P_down` 114 rows split to 120, `D1` 32 x 32 split
    to 32 x 128), and a chip's 750 of those blocks in `oocyte_mesh4.run`
    (30 a grid step): Mosaic takes the strips, the 128 x 128 transposes and
    the sublane rolls."""
    from skellysim_tpu.ops import block_df

    def st(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lead = (2,) if vmapped else ()
    m_shape = lead + ((rows, cols) if shared else (blocks, rows, cols))

    def apply(hi, lo, x):
        return block_df.block_matvec_df((hi, lo), x, n_rows=rows)

    text = _compile(jax.vmap(apply) if vmapped else apply,
                    st(m_shape, F32), st(m_shape, F32),
                    st(lead + (blocks, cols), jnp.float64))
    assert "tpu_custom_call" in text


def test_block_df_tile_compiles_inside_the_mesh_step(topo):
    """The tile where `step_spmd` calls it: inside a `shard_map` over the
    fiber axis of four described chips, 256 blocks of 256^2 a chip."""
    from skellysim_tpu.ops import block_df
    from skellysim_tpu.parallel.mesh import FIBER_AXIS

    n_dev, blocks = 4, 256
    mesh = Mesh(topo.devices[:n_dev], (FIBER_AXIS,))
    sharded = NamedSharding(mesh, P(FIBER_AXIS))

    def st(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharded)

    def local(hi, lo, x):
        return block_df.block_matvec_df((hi, lo), x, n_rows=256)

    step = jax.shard_map(local, mesh=mesh, in_specs=P(FIBER_AXIS),
                         out_specs=P(FIBER_AXIS))
    text = _compile(step, st((n_dev * blocks, 256, 256), F32),
                    st((n_dev * blocks, 256, 256), F32),
                    st((n_dev * blocks, 256), jnp.float64))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n_dev", [1, 4])
def test_gmres_live_row_walk_compiles(topo, n_dev):
    """The Krylov loop's chunk walk at the fiber cells' size (16,384 float64
    unknowns a chip, a basis of 101 rows): a `dynamic-slice` of the basis
    under a trip count computed from `k`, and on four chips the `psum` of
    `parallel.spmd._make_rdot` INSIDE that loop."""
    from skellysim_tpu.parallel.mesh import FIBER_AXIS
    from skellysim_tpu.parallel.spmd import _make_rdot
    from skellysim_tpu.solver import gmres

    rows = 16384
    mesh = Mesh(topo.devices[:n_dev], (FIBER_AXIS,))
    sharded = NamedSharding(mesh, P(FIBER_AXIS))

    def local(d, b):
        return gmres(lambda v: d * v, b, tol=1e-5, restart=100, maxiter=1000,
                     rdot=_make_rdot(FIBER_AXIS, rows)).x

    step = jax.shard_map(local, mesh=mesh, in_specs=P(FIBER_AXIS),
                         out_specs=P(FIBER_AXIS), check_vma=False)
    st = jax.ShapeDtypeStruct((n_dev * rows,), jnp.float64, sharding=sharded)
    text = _compile(step, st, st)
    assert "dynamic-slice" in text
    assert ("all-reduce" in text) == (n_dev > 1)


@pytest.mark.parametrize("rows,cols", [(24000, 24000), (6000, 24000),
                                       (19293, 19293), (4824, 19296)])
def test_streamed_shell_tile_compiles(one_chip, rows, cols):
    """The streamed double-float tile (`ops.block_df.matvec_df_streamed`)
    at `ellipsoid_256.run`'s whole 24,000^2 shell operator, at a chip's
    6,000 rows of it in `ellipsoid_mesh4.run`, at `oocyte_1024.run`'s
    19,293^2 (odd, no multiple of 8): ragged blocks both ways, and at a
    chip's 4,824 of the 19,296 padded rows in `oocyte_mesh4.run`."""
    from skellysim_tpu.ops import block_df

    def st(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = _compile(lambda h, l, x: block_df.matvec_df_streamed((h, l), x),
                    st((rows, cols), F32), st((rows, cols), F32),
                    st((cols,), jnp.float64))
    assert "tpu_custom_call" in text


def test_streamed_shell_tile_compiles_vmapped(one_chip):
    """The tile under `jax.vmap`, as the ensemble's batched step runs it:
    `pallas_call`'s batching rule adds a member axis to its grid (two
    members of the walkthrough's 18,000^2 words)."""
    from skellysim_tpu.ops import block_df

    def st(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    batched = jax.vmap(lambda h, l, x: block_df.matvec_df_streamed((h, l),
                                                                   x))
    text = _compile(batched, st((2, 18000, 18000), F32),
                    st((2, 18000, 18000), F32), st((2, 18000), jnp.float64))
    assert "tpu_custom_call" in text


def test_mixed_step_at_the_walkthroughs_shell_has_no_f64_operator_dot(
        one_chip, monkeypatch):
    """The mixed step on a 6,000-node shell (the walkthrough's 18,000^2
    operator) and a 400-node body, the backend read as a TPU: the shell
    holds its words, the residual's product is the streamed tile, and the
    step's program has no float64 ``dot_general`` with an 18,000-wide
    operand — where the float64 array (the parent's) has its row blocks."""
    from skellysim_tpu.params import Params
    from skellysim_tpu.periphery import periphery as peri
    from skellysim_tpu.system import System
    from skellysim_tpu.testing import make_coupled_parts

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, rows = 6000, 18000
    shell, shape, bodies = make_coupled_parts(40, 400, jnp.float64)
    system = System(Params(), shell_shape=shape)
    assert system.shell_product(jnp.float64) == "df_tile"
    state = system.make_state(shell=shell._replace(
        M_inv=shell.M_inv.astype(F32),
        stresslet_plus_complementary=peri.split_operator(
            shell.stresslet_plus_complementary)), bodies=bodies)

    def st(a, shape=None):
        return jax.ShapeDtypeStruct(a.shape if shape is None else shape,
                                    a.dtype, sharding=one_chip)

    big = state.shell._replace(
        nodes=st(state.shell.nodes, (n, 3)),
        normals=st(state.shell.normals, (n, 3)),
        weights=st(state.shell.weights, (n,)),
        M_inv=st(state.shell.M_inv, (rows, rows)),
        stresslet_plus_complementary=peri.OperatorWords(
            *(st(w, (rows, rows))
              for w in state.shell.stresslet_plus_complementary)),
        density=st(state.shell.density, (rows,)))
    abstract = jax.tree_util.tree_map(st, state._replace(shell=None))
    words = abstract._replace(shell=big)
    lowered = jax.jit(system._solve_impl).lower(words)
    wide = f"{rows}xf64"
    assert not [line for line in lowered.as_text().splitlines()
                if "dot_general" in line and wide in line]
    assert "tpu_custom_call" in lowered.compile().as_text()
    # the control: the float64 array the parent held
    array = abstract._replace(shell=big._replace(
        stresslet_plus_complementary=jax.ShapeDtypeStruct(
            (rows, rows), jnp.float64, sharding=one_chip)))
    assert [line for line in jax.jit(system._solve_impl).lower(
        array).as_text().splitlines()
        if "dot_general" in line and wide in line]


def test_streamed_shell_tile_compiles_inside_the_mesh_step(topo):
    """The streamed tile where `step_spmd` calls it in `ellipsoid_mesh4.run`:
    inside a `shard_map` over four described chips, each multiplying its
    6,000 rows of the 24,000^2 words by the all-gathered density."""
    from skellysim_tpu.ops import block_df
    from skellysim_tpu.parallel.mesh import FIBER_AXIS

    n_dev, rows = 4, 24000
    mesh = Mesh(topo.devices[:n_dev], (FIBER_AXIS,))
    by_rows = NamedSharding(mesh, P(FIBER_AXIS))

    def local(hi, lo, x):
        full = jax.lax.all_gather(x, FIBER_AXIS, tiled=True)
        return block_df.matvec_df_streamed((hi, lo), full)

    step = jax.shard_map(local, mesh=mesh, in_specs=P(FIBER_AXIS),
                         out_specs=P(FIBER_AXIS))
    words = jax.ShapeDtypeStruct((rows, rows), F32, sharding=by_rows)
    text = _compile(step, words, words,
                    jax.ShapeDtypeStruct((rows,), jnp.float64,
                                         sharding=by_rows))
    # the compiler may lower the density's gather as an all-reduce (PR 36)
    assert "tpu_custom_call" in text
    assert "all-gather" in text or "all-reduce" in text
