"""Pallas TPU kernels for the hot pairwise Stokes sums.

The XLA path (`ops.kernels`) materializes [block, n_src] displacement tensors
in HBM between fused ops; these kernels keep the whole interaction tile in
VMEM: coordinates live transposed as [3, N] so the source axis is the 128-wide
lane dimension, each grid cell computes a [TILE_T, TILE_S] interaction block
with pure VPU arithmetic (~20 flops/pair, no MXU dependency), and target tiles
accumulate across the sequential source-tile grid axis.

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

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# sentinel for padded source coordinates: far enough that rinv underflows to
# exactly 0 in f32, small enough that r^2 stays finite
_PAD_SENTINEL = 1e18

# Tile shapes swept on a v5 lite chip (round 5): stokeslet peaks at
# (256, 1024) ~53 Gpairs/s, stresslet at (128, 2048) ~48 Gpairs/s — the
# stresslet's 9-row source tile wants a wider lane dim at a shorter target
# tile to fit VMEM. Larger source tiles (512x2048+) exceed VMEM and fail to
# compile.
DEFAULT_TILE_T = 256
DEFAULT_TILE_S = 1024
STRESSLET_TILE_T = 128
STRESSLET_TILE_S = 2048


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


def stokeslet_tile_sums(trg_T, src_T, f_T):
    """Unscaled Stokeslet pair sums for one transposed-layout tile:
    ``[3, nt]`` targets x ``[3, ns]`` sources/forces -> ``(ux, uy, uz)``
    row sums. Pure jnp math on values already loaded from refs — the ONE
    definition shared by the gridded VMEM tile below and the fused ring
    kernel (`parallel.ring_fused`), so the two cannot drift."""
    tx, ty, tz = trg_T[0, :], trg_T[1, :], trg_T[2, :]
    sx, sy, sz = src_T[0, :], src_T[1, :], src_T[2, :]
    fx, fy, fz = f_T[0, :], f_T[1, :], f_T[2, :]

    dx = tx[:, None] - sx[None, :]
    dy = ty[:, None] - sy[None, :]
    dz = tz[:, None] - sz[None, :]
    r2 = dx * dx + dy * dy + dz * dz
    mask = r2 > 0.0
    rinv = jnp.where(mask, lax.rsqrt(jnp.where(mask, r2, 1.0)), 0.0)
    rinv3 = rinv * rinv * rinv

    df = dx * fx[None, :] + dy * fy[None, :] + dz * fz[None, :]
    common = df * rinv3

    ux = jnp.sum(rinv * fx[None, :] + common * dx, axis=1)
    uy = jnp.sum(rinv * fy[None, :] + common * dy, axis=1)
    uz = jnp.sum(rinv * fz[None, :] + common * dz, axis=1)
    return ux, uy, uz


def _stokeslet_kernel(trg_ref, src_ref, f_ref, out_ref):
    """One [TILE_T, TILE_S] interaction tile; accumulates over grid axis 1."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    ux, uy, uz = stokeslet_tile_sums(trg_ref[:], src_ref[:], f_ref[:])
    out_ref[0, :] += ux
    out_ref[1, :] += uy
    out_ref[2, :] += uz


@partial(jax.jit, static_argnames=("tile_t", "tile_s", "interpret"))
def stokeslet_pallas(r_src, r_trg, f_src, eta, *, tile_t: int = DEFAULT_TILE_T,
                     tile_s: int = DEFAULT_TILE_S, interpret: bool = False):
    """Singular Stokeslet sum as a fused Pallas kernel.

    Same contract as `ops.kernels.stokeslet_direct`: [n_src, 3] sources,
    [n_trg, 3] targets, [n_src, 3] forces -> [n_trg, 3] velocities.
    """
    n_trg, n_src = r_trg.shape[0], r_src.shape[0]
    if n_trg == 0 or n_src == 0:
        return jnp.zeros_like(r_trg)
    dtype = r_trg.dtype

    nt = pl.cdiv(n_trg, tile_t) * tile_t
    ns = pl.cdiv(n_src, tile_s) * tile_s

    trg_T = _pad_to(r_trg.T, nt, axis=1)
    src_T = _pad_to(r_src.T, ns, axis=1, value=_PAD_SENTINEL)
    f_T = _pad_to(f_src.T, ns, axis=1)

    grid = (nt // tile_t, ns // tile_s)
    # index-map zeros must be np.int32: under jax_enable_x64 a literal 0
    # traces as i64 while grid indices stay i32, and Mosaic rejects the
    # mixed-type index map (remote-compile HTTP 500 on this backend)
    z = np.int32(0)
    u_T = pl.pallas_call(
        _stokeslet_kernel,
        # vma: inside shard_map (the ring evaluator's tile) the output varies
        # over whatever mesh axes the operands do; outside it's frozenset()
        out_shape=_out_struct((3, nt), dtype, trg_T, src_T, f_T),
        grid=grid,
        in_specs=[
            pl.BlockSpec((3, tile_t), lambda i, j: (z, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((3, tile_s), lambda i, j: (z, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((3, tile_s), lambda i, j: (z, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((3, tile_t), lambda i, j: (z, i),
                               memory_space=pltpu.VMEM),
        cost_estimate=pl.CostEstimate(
            flops=22 * nt * ns, bytes_accessed=4 * 3 * (nt + 2 * ns + nt),
            transcendentals=nt * ns),
        interpret=interpret,
    )(trg_T, src_T, f_T)

    factor = 1.0 / (8.0 * math.pi)
    return u_T.T[:n_trg] * (factor / eta)


def stresslet_tile_sums(trg_T, src_T, s_T):
    """Unscaled stresslet pair sums for one transposed-layout tile:
    ``[3, nt]`` targets x ``[3, ns]`` sources + ``[9, ns]`` row-major
    stresslet components -> ``(ux, uy, uz)``. Shared by the gridded tile
    and the fused ring kernel like `stokeslet_tile_sums`."""
    tx, ty, tz = trg_T[0, :], trg_T[1, :], trg_T[2, :]
    sx, sy, sz = src_T[0, :], src_T[1, :], src_T[2, :]

    dx = tx[:, None] - sx[None, :]
    dy = ty[:, None] - sy[None, :]
    dz = tz[:, None] - sz[None, :]
    r2 = dx * dx + dy * dy + dz * dz
    mask = r2 > 0.0
    rinv = jnp.where(mask, lax.rsqrt(jnp.where(mask, r2, 1.0)), 0.0)
    rinv2 = rinv * rinv
    rinv5 = rinv2 * rinv2 * rinv

    # d^T S d over the 9 components (S row-major: Sxx..Szz)
    dSd = (dx * dx * s_T[0, :][None, :] + dx * dy * s_T[1, :][None, :]
           + dx * dz * s_T[2, :][None, :] + dy * dx * s_T[3, :][None, :]
           + dy * dy * s_T[4, :][None, :] + dy * dz * s_T[5, :][None, :]
           + dz * dx * s_T[6, :][None, :] + dz * dy * s_T[7, :][None, :]
           + dz * dz * s_T[8, :][None, :])
    common = -3.0 * dSd * rinv5

    return (jnp.sum(common * dx, axis=1), jnp.sum(common * dy, axis=1),
            jnp.sum(common * dz, axis=1))


def _stresslet_kernel(trg_ref, src_ref, s_ref, out_ref):
    """Stresslet tile: s_ref holds the 9 source components [9, TILE_S]."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    ux, uy, uz = stresslet_tile_sums(trg_ref[:], src_ref[:], s_ref[:])
    out_ref[0, :] += ux
    out_ref[1, :] += uy
    out_ref[2, :] += uz


@partial(jax.jit, static_argnames=("tile_t", "tile_s", "interpret"))
def stresslet_pallas(r_dl, r_trg, f_dl, eta, *, tile_t: int = STRESSLET_TILE_T,
                     tile_s: int = STRESSLET_TILE_S, interpret: bool = False):
    """Singular stresslet sum as a fused Pallas kernel.

    Same contract as `ops.kernels.stresslet_direct`: ``f_dl`` is [n_src, 3, 3].
    """
    n_trg, n_src = r_trg.shape[0], r_dl.shape[0]
    if n_trg == 0 or n_src == 0:
        return jnp.zeros_like(r_trg)
    dtype = r_trg.dtype

    nt = pl.cdiv(n_trg, tile_t) * tile_t
    ns = pl.cdiv(n_src, tile_s) * tile_s

    trg_T = _pad_to(r_trg.T, nt, axis=1)
    src_T = _pad_to(r_dl.T, ns, axis=1, value=_PAD_SENTINEL)
    s_T = _pad_to(f_dl.reshape(n_src, 9).T, ns, axis=1)

    grid = (nt // tile_t, ns // tile_s)
    z = np.int32(0)  # see stokeslet_pallas: i64/i32 index-map mix breaks Mosaic
    u_T = pl.pallas_call(
        _stresslet_kernel,
        out_shape=_out_struct((3, nt), dtype, trg_T, src_T, s_T),
        grid=grid,
        in_specs=[
            pl.BlockSpec((3, tile_t), lambda i, j: (z, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((3, tile_s), lambda i, j: (z, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((9, tile_s), lambda i, j: (z, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((3, tile_t), lambda i, j: (z, i),
                               memory_space=pltpu.VMEM),
        cost_estimate=pl.CostEstimate(
            flops=40 * nt * ns, bytes_accessed=4 * (3 * nt + 12 * ns + 3 * nt),
            transcendentals=nt * ns),
        interpret=interpret,
    )(trg_T, src_T, s_T)

    factor = 1.0 / (8.0 * math.pi)
    return u_T.T[:n_trg] * (factor / eta)


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

    specs = [
        ("stokeslet_pallas_tiles", stokeslet_pallas,
         DEFAULT_TILE_T, DEFAULT_TILE_S, (3,)),
        ("stresslet_pallas_tiles", stresslet_pallas,
         STRESSLET_TILE_T, STRESSLET_TILE_S, (3, 3)),
    ]

    def build(fn, tile_t, tile_s, pay):
        def _build():
            n_trg, n_src = 2 * tile_t, 2 * tile_s
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
                             f"{tile_t}x{tile_s} VMEM tiles"),
                    build=build(fn, tile_t, tile_s, pay))
        for name, fn, tile_t, tile_s, pay in specs
    ]
