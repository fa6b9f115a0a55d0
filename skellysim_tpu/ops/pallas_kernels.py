"""Pallas TPU kernels for the hot pairwise Stokes sums.

The XLA path (`ops.kernels`) materializes [block, n_src] displacement tensors
in HBM between fused ops; these kernels keep the whole interaction in VMEM
and vector registers. Coordinates live transposed as [3, N], so the source
axis is the 128-wide lane dimension. Each grid cell holds a (tile_t, tile_s)
block and walks it in strips of 8 targets x ``w`` sources, whose values fit
in vregs: each lane accumulates its pair terms over the whole source axis of
the grid in a VMEM scratch, and the lanes reduce once a target tile. That is
the double-float tile's structure (`ops.pallas_df`) with one f32 word in
place of two: 30 VPU operations a Stokeslet pair and 40 a stresslet pair,
plus one rsqrt (with its Newton step, ~8 more), with no MXU dependency.

Numerics follow `ops.kernels.stokeslet_block` exactly: coincident pairs (r == 0)
contribute zero. Padded sources contribute exactly zero because their
force/stresslet densities are zero-padded (every additive term carries a
density factor); the large-but-finite coordinate sentinel only guarantees the
intermediate r^2/rsqrt stay finite so no NaN/Inf can propagate into real rows.
A kernel added on this pattern MUST keep every term density-scaled.

These kernels are float32 (the TPU-resident hot path); the f64 accuracy-gated
path stays on the XLA kernels. `interpret=True` runs them on CPU for the
backend-consistency tests (SURVEY.md §4.1).
"""

from __future__ import annotations

import logging
import math
from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

logger = logging.getLogger("skellysim_tpu")

# sentinel for padded source coordinates: far enough that rinv underflows to
# exactly 0 in f32, small enough that r^2 stays finite
_PAD_SENTINEL = 1e18

# Block, strip and unroll of both tiles, each the largest a call may take
# (`_geometry` and `_unroll` clip them to the call's shapes), swept on a v5e
# (PERF.md section 6): a strip of 8 x 512 lanes, 8 chunks a loop trip, in
# 128 x 4,096 blocks takes 2.88 ms for the f32 Stokeslet at 16,384^2 and
# 9.97 ms at 32,768 x 39,199 on the host clock (the whole-block tile it
# replaced: 4.02 / 16.17 ms), 2.09 ms for the stresslet at 8,000 -> 16,384
# (2.69). Strips of 256 or 1,024 lanes and other blocks were 1-24 % slower;
# a chunk a trip (no unroll) leaves the rsqrt's latency exposed: 58 bundles
# a chunk of 4 vregs in the compiled loop, against 41 unrolled.
TILE_T = 128
TILE_S = 4096
STRIP_W = 512
UNROLL = 8


def _vma(*arrays):
    """Union of the operands' varying-mesh-axes: pallas_call under shard_map
    must declare which mesh axes its output varies over (check_vma);
    outside shard_map every vma is empty and this is a no-op."""
    out = frozenset()
    for a in arrays:
        out |= jax.typeof(a).vma
    return out


def _out_struct(shape, dtype, *arrays):
    """`jax.ShapeDtypeStruct` carrying the operands' vma union."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=_vma(*arrays))


def _pad_to(a, n, axis, value=0.0):
    pad = n - a.shape[axis]
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths, constant_values=value)


def _rinv(d):
    """1/r of each pair of the displacement ``d``; zero for a coincident
    pair (the select drops rsqrt(0) = inf, and a select does not carry the
    value it drops)."""
    r2 = d[0] * d[0] + d[1] * d[1] + d[2] * d[2]
    return jnp.where(r2 > 0.0, lax.rsqrt(r2), 0.0)


def stokeslet_pair_terms(d, f):
    """The unscaled Stokeslet terms of each pair, elementwise: ``d`` the
    three components of target - source, ``f(k)`` force row k, broadcast
    against them -> ``(ux, uy, uz)``. The ONE per-pair expression of the
    gridded strip tile below and of the fused ring kernel's block sums
    (`stokeslet_tile_sums`, `parallel.ring_fused`), so the two cannot
    drift."""
    rinv = _rinv(d)
    rinv3 = rinv * rinv * rinv
    common = (d[0] * f(0) + d[1] * f(1) + d[2] * f(2)) * rinv3
    return tuple(rinv * f(k) + common * d[k] for k in range(3))


def stresslet_pair_terms(d, s):
    """The stresslet terms (d.S.d) d_k / r^5 of each pair, elementwise, as
    `stokeslet_pair_terms`: ``s(m)`` is component m of the row-major 3 x 3
    source (Sxx..Szz). d.S.d is contracted row by row,
    (S_i0 dx + S_i1 dy + S_i2 dz) d_i summed over i. The kernel's -3 goes
    on once a sum (`_KINDS`), as in the double-float tile."""
    rinv = _rinv(d)
    rinv2 = rinv * rinv
    rinv5 = rinv2 * rinv2 * rinv
    row = [(s(3 * i) * d[0] + s(3 * i + 1) * d[1] + s(3 * i + 2) * d[2])
           * d[i] for i in range(3)]
    common = (row[0] + row[1] + row[2]) * rinv5
    return tuple(common * d[k] for k in range(3))


class _Kind(NamedTuple):
    terms: Callable     # the per-pair expression
    scale: float        # what the pair sums take once, besides 1/(8 pi eta)
    flops: int          # f32 operations a pair of the strip body (the
                        # difference, the terms, the add into the lane's
                        # sum), for the cost estimate


_KINDS = {"stokeslet": _Kind(stokeslet_pair_terms, 1.0, 30),
          "stresslet": _Kind(stresslet_pair_terms, -3.0, 40)}


def _tile_sums(kind, trg_T, src_T, pay_T):
    """Row sums of ``kind``'s terms over one whole transposed-layout
    block."""
    terms, scale, _ = _KINDS[kind]
    d = [trg_T[k, :][:, None] - src_T[k, :][None, :] for k in range(3)]
    u = terms(d, lambda m: pay_T[m, :][None, :])
    return tuple(scale * jnp.sum(uk, axis=1) for uk in u)


def stokeslet_tile_sums(trg_T, src_T, f_T):
    """Unscaled Stokeslet pair sums for one transposed-layout block:
    ``[3, nt]`` targets x ``[3, ns]`` sources/forces -> ``(ux, uy, uz)``
    row sums; the fused ring kernel's (`parallel.ring_fused`)."""
    return _tile_sums("stokeslet", trg_T, src_T, f_T)


def stresslet_tile_sums(trg_T, src_T, s_T):
    """Unscaled stresslet pair sums for one transposed-layout block:
    ``[3, nt]`` targets x ``[3, ns]`` sources + ``[9, ns]`` row-major
    stresslet components -> ``(ux, uy, uz)``, like `stokeslet_tile_sums`."""
    return _tile_sums("stresslet", trg_T, src_T, s_T)


def _strip_kernel(kind, unroll, trg_ref, src_ref, out_ref, tb_ref, acc_ref):
    """One (tile_t, tile_s) block of a pair sum, walked in strips.

    ``trg_ref`` [3, tile_t] holds the targets; ``src_ref`` [tile_s / 128,
    rows, 128] the sources in 128-lane groups, position rows then payload
    rows. ``tb_ref`` holds the targets broadcast along the lanes and
    ``acc_ref`` the lane-wise partial sums, both [3, tile_t, w] and alive
    across the source axis of the grid: the lane reduction runs once a
    target tile, not once a block. A strip of 8 targets walks the block's
    sources ``w`` lanes a chunk and ``unroll`` chunks a loop trip, so the
    scheduler can overlap one chunk's dependency chain (the rsqrt's above
    all) with the next one's.
    """
    terms = _KINDS[kind].terms
    j = pl.program_id(1)
    tile_t, w = tb_ref.shape[1:]
    groups = w // 128
    trips = src_ref.shape[0] // (groups * unroll)
    # loop bounds as int32 ARRAYS: Python and NumPy ints give the loop an i64
    # counter under x64, which Mosaic refuses
    i32 = jnp.int32

    @pl.when(j == 0)
    def _():
        for k in range(3):
            tb_ref[k] = jnp.broadcast_to(trg_ref[k, :][:, None], (tile_t, w))
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def strip(r, _):
        rows = pl.ds(pl.multiple_of(r * i32(8), 8), 8)
        t = [tb_ref[k, rows, :] for k in range(3)]

        def chunk(g, acc):
            def row(m):
                # row m of the chunk's sources on all 8 sublanes, a 128-lane
                # group at a time; Mosaic lowers each broadcast to one
                # sublane-stride-0 load. Compiled for a v5e, the inner loop
                # is 333 bundles a strip of 8 chunks for the Stokeslet (330
                # with that load written out, which the interpreter cannot
                # run) and 410 for the stresslet (410 both ways)
                return jnp.concatenate(
                    [jnp.broadcast_to(src_ref[g + q, pl.ds(m, 1), :],
                                      (8, 128)) for q in range(groups)],
                    axis=1)

            u = terms([t[k] - row(k) for k in range(3)],
                      lambda m: row(3 + m))
            return tuple(a + b for a, b in zip(acc, u))

        def trip(c, acc):
            for q in range(unroll):
                acc = chunk((c * i32(unroll) + q) * i32(groups), acc)
            return acc

        acc = tuple(acc_ref[k, rows, :] for k in range(3))
        acc = lax.fori_loop(i32(0), i32(trips), trip, acc)
        for k in range(3):
            acc_ref[k, rows, :] = acc[k]

    lax.fori_loop(i32(0), i32(tile_t // 8), strip, None)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        for k in range(3):
            out_ref[k, :] = jnp.sum(acc_ref[k], axis=1)


def _round_up(n, m):
    return -(-n // m) * m


def _unroll(n_chunks):
    """Chunks a loop trip: the largest divisor of the block's chunks up to
    `UNROLL`."""
    return max(u for u in range(1, UNROLL + 1) if n_chunks % u == 0)


def _geometry(n_trg, n_src, tile_t, tile_s, strip_w):
    """``(tile_t, tile_s, w, nt, ns)`` of one call. The strip is the next
    128 * 2^k lanes over the sources, up to ``strip_w`` and the block (64
    fiber nodes pad to 128, not to a whole strip). The sources split into
    the fewest blocks of at most ``tile_s`` lanes, each the whole strips of
    an equal share (6,431 shell nodes: two blocks of 3,584, not 8,192
    lanes); the block is no taller than the padded targets."""
    for name, v in (("tile_s", tile_s), ("strip_w", strip_w)):
        if v < 128 or v % 128 or (v // 128) & (v // 128 - 1):
            raise ValueError(f"{name} must be 128 * 2^k, got {v}")
    if tile_t < 8 or tile_t % 8:
        raise ValueError(f"tile_t must be a multiple of 8, got {tile_t}")
    w = 128
    while w < min(strip_w, tile_s, n_src):
        w *= 2
    blocks = -(-n_src // tile_s)
    tile_s = _round_up(-(-n_src // blocks), w)
    tile_t = min(tile_t, _round_up(n_trg, 8))
    return tile_t, tile_s, w, _round_up(n_trg, tile_t), blocks * tile_s


def _strip_call(kind, r_src, r_trg, payload, eta, tile_t, tile_s, strip_w,
                interpret):
    """The f32 tiles' one pallas_call: [n_src, 3] sources,
    [n_trg, 3] targets, [n_src, ...] payload -> [n_trg, 3] velocities,
    scaled by 1/(8 pi eta), in the targets' dtype."""
    n_trg, n_src = r_trg.shape[0], r_src.shape[0]
    if n_trg == 0 or n_src == 0:
        return jnp.zeros_like(r_trg)
    dtype = r_trg.dtype
    tile_t, tile_s, w, nt, ns = _geometry(n_trg, n_src, tile_t, tile_s,
                                          strip_w)
    grid = (nt // tile_t, ns // tile_s)
    _announce(kind, n_trg, n_src, tile_t, tile_s, w, grid)

    trg_T = _pad_to(r_trg.T, nt, axis=1)
    pay_T = payload.reshape(n_src, -1).T
    # rows a multiple of the 8-sublane tiling (Mosaic refuses a 6- or
    # 12-row source block): Stokeslet 3 + 3 -> 8, stresslet 3 + 9 -> 16
    rows = _round_up(3 + pay_T.shape[0], 8)
    src = jnp.concatenate([_pad_to(r_src.T.astype(dtype), ns, axis=1,
                                   value=_PAD_SENTINEL),
                           _pad_to(pay_T.astype(dtype), ns, axis=1)], axis=0)
    # [rows, ns] -> [ns / 128, rows, 128]: a chunk indexes its 128-lane
    # groups on the leading axis
    src = _pad_to(src, rows, axis=0).reshape(rows, ns // 128, 128)
    src = src.transpose(1, 0, 2)

    # index-map zeros must be np.int32: under jax_enable_x64 a literal 0
    # traces as i64 while grid indices stay i32, and Mosaic rejects the
    # mixed-type index map (remote-compile HTTP 500 on this backend)
    z = np.int32(0)
    u_T = pl.pallas_call(
        partial(_strip_kernel, kind, _unroll(tile_s // w)),
        # vma: inside shard_map (the ring evaluator's tile) the output varies
        # over whatever mesh axes the operands do; outside it's frozenset()
        out_shape=_out_struct((3, nt), dtype, trg_T, src),
        grid=grid,
        in_specs=[
            pl.BlockSpec((3, tile_t), lambda i, j: (z, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile_s // 128, rows, 128), lambda i, j: (j, z, z),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((3, tile_t), lambda i, j: (z, i),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((3, tile_t, w), dtype),
                        pltpu.VMEM((3, tile_t, w), dtype)],
        cost_estimate=pl.CostEstimate(
            flops=_KINDS[kind].flops * nt * ns,
            bytes_accessed=dtype.itemsize * (6 * nt + rows * ns),
            transcendentals=nt * ns),
        interpret=interpret,
    )(trg_T, src)

    factor = _KINDS[kind].scale / (8.0 * math.pi)
    return u_T.T[:n_trg] * (factor / eta)


def _announce(kind, n_trg, n_src, tile_t, tile_s, w, grid):
    """Trace-time, once for each shape and tile a process traces (a later
    build of the same shapes hits the jit cache and says nothing): the
    geometry the call takes, in the log and as an ``f32_tile`` event (`obs
    summarize`, ``== pair tile (Krylov loop) ==``)."""
    from ..obs import tracer as obs_tracer

    fields = dict(kind=kind, n_trg=n_trg, n_src=n_src,
                  tile=f"{tile_t}x{tile_s}", strip_w=w,
                  grid=f"{grid[0]}x{grid[1]}")
    logger.info("f32_tile kind=%(kind)s n_trg=%(n_trg)s n_src=%(n_src)s "
                "tile=%(tile)s strip_w=%(strip_w)s grid=%(grid)s", fields)
    obs_tracer.emit("f32_tile", **fields)


@partial(jax.jit, static_argnames=("tile_t", "tile_s", "strip_w",
                                   "interpret"))
def stokeslet_pallas(r_src, r_trg, f_src, eta, *, tile_t: int = TILE_T,
                     tile_s: int = TILE_S, strip_w: int = STRIP_W,
                     interpret: bool = False):
    """Singular Stokeslet sum as a fused Pallas kernel.

    Same contract as `ops.kernels.stokeslet_direct`: [n_src, 3] sources,
    [n_trg, 3] targets, [n_src, 3] forces -> [n_trg, 3] velocities.
    """
    return _strip_call("stokeslet", r_src, r_trg, f_src, eta, tile_t, tile_s,
                       strip_w, interpret)


@partial(jax.jit, static_argnames=("tile_t", "tile_s", "strip_w",
                                   "interpret"))
def stresslet_pallas(r_dl, r_trg, f_dl, eta, *, tile_t: int = TILE_T,
                     tile_s: int = TILE_S, strip_w: int = STRIP_W,
                     interpret: bool = False):
    """Singular stresslet sum as a fused Pallas kernel.

    Same contract as `ops.kernels.stresslet_direct`: ``f_dl`` is [n_src, 3, 3].
    """
    return _strip_call("stresslet", r_dl, r_trg, f_dl, eta, tile_t, tile_s,
                       strip_w, interpret)


# eta chosen so stokeslet_pallas's trailing (1/(8 pi))/eta scale is exactly
# 1.0: these block entry points return the UNSCALED pair sum, matching the
# `ops.kernels.stokeslet_block` contract (the caller — the ring evaluator —
# applies 1/(8 pi eta) once at the end).
_UNIT_ETA = 1.0 / (8.0 * math.pi)


def stokeslet_pallas_block(r_trg, r_src, f_src, *, interpret: bool = False):
    """Unscaled Stokeslet interaction block — the ring evaluator's Pallas
    tile (`parallel.ring.ring_stokeslet(impl="pallas")`). Same signature
    order as `ops.kernels.stokeslet_block` (targets first)."""
    return stokeslet_pallas(r_src, r_trg, f_src, _UNIT_ETA,
                            interpret=interpret)


def stresslet_pallas_block(r_trg, r_dl, f_dl, *, interpret: bool = False):
    """Unscaled stresslet interaction block for the ring evaluator."""
    return stresslet_pallas(r_dl, r_trg, f_dl, _UNIT_ETA,
                            interpret=interpret)


def auditable_kernels():
    """The gridded tile kernels' entries for the ``dma`` audit check:
    each traced at its default multi-tile grid (2x2, so the block specs —
    not degenerate whole-array blocks — are what the VMEM accounting
    walks). No DMA/semaphore traffic here; the check pins exactly that
    (zero comm slots, zero semaphores) plus the tile footprint against
    the shared budget. Defining this seam licenses this module for the
    ``raw-dma`` lint rule."""
    from ..audit.dmaflow import pallas_calls
    from ..audit.registry import AuditKernel, BuiltKernel

    specs = [("stokeslet_pallas_tiles", stokeslet_pallas, (3,)),
             ("stresslet_pallas_tiles", stresslet_pallas, (3, 3))]

    def build(fn, pay):
        def _build():
            n_trg, n_src = 2 * TILE_T, 2 * TILE_S
            closed = jax.make_jaxpr(
                lambda r_s, r_t, f: fn(r_s, r_t, f, _UNIT_ETA))(
                    jnp.zeros((n_src, 3), jnp.float32),
                    jnp.zeros((n_trg, 3), jnp.float32),
                    jnp.zeros((n_src,) + pay, jnp.float32))
            (kernel_jaxpr, grid_mapping), = pallas_calls(closed.jaxpr)
            return BuiltKernel(kernel_jaxpr=kernel_jaxpr,
                               grid_mapping=grid_mapping, n_dev=1,
                               scene={})
        return _build

    return [
        AuditKernel(name=name, layer="ops",
                    summary=(f"gridded {name.split('_')[0]} pair kernel: "
                             f"{TILE_T}x{TILE_S} VMEM tiles in strips of "
                             f"8x{STRIP_W}"),
                    build=build(fn, pay))
        for name, fn, pay in specs
    ]
