"""Pallas double-float (compensated f32) pairwise kernels.

Fuses the `ops.df_kernels` arithmetic — Dekker/Knuth error-free
transformations giving ~1e-14-class relative accuracy from pure f32 VPU ops
— into VMEM interaction tiles like `ops.pallas_kernels`. The XLA DF path
measures ~0.34 Gpairs/s on a v5e chip (the per-pair chain is ~15x the exact
kernel's flops and XLA spends it through HBM-staged fusions); keeping the
whole chain on-tile removes the HBM round trips, the same transformation
that took the exact kernel 14.6 -> 53 Gpairs/s.

Numerics: per-pair arithmetic is double-float (every value an unevaluated
(hi, lo) f32 pair); in-tile reduction is a compensated halving tree down to
one 128-lane vreg, then a lane-roll log-reduction — no f32-rounded sum
anywhere between the pair terms and the final hi+lo -> f64 reconstruction
on the host side of the kernel. Cross-tile accumulation along the source
grid axis is a DF add into a (hi, lo) output pair.

FMA-contraction hardening: the inexact-product-feeding-add sites are
`_mbar`-wrapped exactly like `ops.df_kernels` (see the long analysis
there). On real TPUs the Mosaic pipeline evaluates each kernel value once
into a vreg (no XLA-style cross-fusion cloning), so the hazard class that
motivated the hardening cannot arise; in `interpret=True` mode the kernel
body runs through XLA:CPU where LLVM's FMA contraction is live, and the
`select` hardening keeps the compensation intact there. The on-chip
agreement gate (`chip_smoke.py`, `gate_kernels`: both tiles against a
NumPy f64 oracle) is the authority for real-hardware accuracy, mirroring
the exact-kernel gate.

Reference parity: same evaluator contract as `kernels.{stokeslet,
stresslet}_direct` (self pairs drop, factor 1/(8 pi eta); stresslet factor
-3 on the double-layer sum) — the backend-agreement threshold for every
evaluator is ||err|| <= 5e-9 (`/root/reference/tests/core/kernel_test.cpp:93`);
these tiles sit ~5 orders under it.
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

from .pallas_kernels import _out_struct, _pad_to

__all__ = ["stokeslet_pallas_df", "stresslet_pallas_df",
           "stokeslet_pallas_df_block", "stresslet_pallas_df_block"]

# DF tiles hold ~3x the live [tile_t, tile_s] temporaries of the exact
# kernels; smaller defaults keep the working set inside VMEM
DF_TILE_T = 128
DF_TILE_S = 512

#: Dekker split constant for f32 (2^ceil(24/2) + 1)
_SPLIT_F32 = 4097.0


def _mbar(x):
    """Value barrier on a rounded intermediate.

    `df_kernels` uses `lax.optimization_barrier` for these sites, but a
    barrier has no guaranteed Mosaic lowering inside a Pallas kernel; this
    select is value-preserving (operands are non-NaN), cannot be folded
    without NaN reasoning, and lowers on every path (Mosaic, interpret/XLA).
    Without it the compiler algebraically collapses the error-extraction
    expressions — measured 2.7e-8 instead of 1e-14 on this very kernel
    (round 5), the same failure class `df_kernels` documents.
    """
    return jnp.where(x == x, x, jnp.zeros_like(x))


def _two_sum(a, b):
    """Error-free a + b = s + e (Knuth; no magnitude ordering required)."""
    s = _mbar(a + b)
    bb = _mbar(s - a)
    e = (a - _mbar(s - bb)) + (b - bb)
    return s, e


def _quick_two_sum(a, b):
    """Error-free a + b = s + e assuming |a| >= |b|."""
    s = _mbar(a + b)
    e = b - (s - a)
    return s, e


def _two_prod(a, b):
    """Error-free a * b = p + e via Dekker splitting (no FMA dependency)."""
    p = _mbar(a * b)
    a_big = _mbar(_SPLIT_F32 * a)
    a_hi = _mbar(a_big - _mbar(a_big - a))
    a_lo = a - a_hi
    b_big = _mbar(_SPLIT_F32 * b)
    b_hi = _mbar(b_big - _mbar(b_big - b))
    b_lo = b - b_hi
    e = ((a_hi * b_hi - p) + a_hi * b_lo + a_lo * b_hi) + a_lo * b_lo
    return p, e


def _df_add(xh, xl, yh, yl):
    s, e = _two_sum(xh, yh)
    e = e + (xl + yl)
    return _quick_two_sum(s, e)


def _df_mul(xh, xl, yh, yl):
    p, e = _two_prod(xh, yh)
    e = e + (_mbar(xh * yl) + _mbar(xl * yh))
    return _quick_two_sum(p, e)


def _df_rsqrt(xh, xl):
    """1/sqrt(x) as DF: f32 hardware seed + one DF Newton step (doubles the
    accurate bits to full DF precision). Assumes x > 0 (callers mask)."""
    y0 = lax.rsqrt(xh)
    z = jnp.zeros_like(y0)
    th, tl = _df_mul(xh, xl, y0, z)
    th, tl = _df_mul(th, tl, y0, z)
    rh, rl = _df_add(jnp.full_like(th, 3.0), z, -th, -tl)
    yh, yl = _df_mul(rh, rl, y0, z)
    return 0.5 * yh, 0.5 * yl


def _df_reduce_lanes(h, l):
    """Compensated sum along the lane axis of [t, s] -> [t] DF pairs.

    Halving slices keep full 128-lane vregs down to one vreg width; the
    final 128 lanes reduce by lane rolls (full-shape ops Mosaic handles
    natively — no sub-128 slicing). The rolled-in lanes make every lane k
    hold sum(lanes k..k+2^m-1 mod 128); lane 0 is the true total, selected
    by the caller's final [:, 0].
    """
    while h.shape[1] > 128:
        m = h.shape[1] // 2
        h, l = _df_add(h[:, :m], l[:, :m], h[:, m:], l[:, m:])
    w = 64
    while w >= 1:
        # rotation direction is irrelevant for a log-reduce (pltpu.roll
        # requires non-negative shifts): after all steps every lane holds
        # the full 128-lane total
        # the shift must be 32-bit: a Python int traces as i64 under x64
        # (which `_require_x64` demands) and Mosaic's dynamic_rotate refuses it
        hr = pltpu.roll(h, np.int32(w), 1)
        lr = pltpu.roll(l, np.int32(w), 1)
        h, l = _df_add(h, l, hr, lr)
        w //= 2
    return h[:, 0], l[:, 0]


def _df_diff(t_hi, t_lo, s_hi, s_lo):
    """DF displacement component t - s with full two_sum (nearly coincident
    f64 points can have lo-word differences exceeding |hi difference|)."""
    dh, de = _two_sum(t_hi[:, None], -s_hi[None, :])
    return _two_sum(dh, de + (t_lo[:, None] - s_lo[None, :]))


def _stokeslet_df_kernel(trg_ref, src_ref, f_ref, out_ref):
    """One DF interaction tile; trg/src/f refs carry hi rows then lo rows."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    d = [_df_diff(trg_ref[k, :], trg_ref[3 + k, :],
                  src_ref[k, :], src_ref[3 + k, :]) for k in range(3)]

    r2h, r2l = _df_mul(*d[0], *d[0])
    r2h, r2l = _df_add(r2h, r2l, *_df_mul(*d[1], *d[1]))
    r2h, r2l = _df_add(r2h, r2l, *_df_mul(*d[2], *d[2]))

    mask = r2h > 0.0
    rih, ril = _df_rsqrt(jnp.where(mask, r2h, 1.0), jnp.where(mask, r2l, 0.0))
    rih = jnp.where(mask, rih, 0.0)
    ril = jnp.where(mask, ril, 0.0)
    r3h, r3l = _df_mul(rih, ril, rih, ril)
    r3h, r3l = _df_mul(r3h, r3l, rih, ril)

    fs = [(f_ref[k, :][None, :], f_ref[3 + k, :][None, :]) for k in range(3)]
    dfh, dfl = _df_mul(*d[0], *fs[0])
    dfh, dfl = _df_add(dfh, dfl, *_df_mul(*d[1], *fs[1]))
    dfh, dfl = _df_add(dfh, dfl, *_df_mul(*d[2], *fs[2]))
    ch, cl = _df_mul(dfh, dfl, r3h, r3l)

    for k in range(3):
        uh, ul = _df_mul(rih, ril, *fs[k])
        uh, ul = _df_add(uh, ul, *_df_mul(ch, cl, *d[k]))
        sh, sl = _df_reduce_lanes(uh, ul)
        ah, al = _df_add(out_ref[k, :], out_ref[3 + k, :], sh, sl)
        out_ref[k, :] = ah
        out_ref[3 + k, :] = al


def _stresslet_df_kernel(trg_ref, src_ref, s_ref, out_ref):
    """DF stresslet tile: u_k = sum -3 (d.S.d) d_k / r^5, self pairs drop.
    s_ref carries the 9 hi rows then the 9 lo rows of S (row-major)."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    d = [_df_diff(trg_ref[k, :], trg_ref[3 + k, :],
                  src_ref[k, :], src_ref[3 + k, :]) for k in range(3)]

    r2h, r2l = _df_mul(*d[0], *d[0])
    r2h, r2l = _df_add(r2h, r2l, *_df_mul(*d[1], *d[1]))
    r2h, r2l = _df_add(r2h, r2l, *_df_mul(*d[2], *d[2]))

    mask = r2h > 0.0
    rih, ril = _df_rsqrt(jnp.where(mask, r2h, 1.0), jnp.where(mask, r2l, 0.0))
    rih = jnp.where(mask, rih, 0.0)
    ril = jnp.where(mask, ril, 0.0)
    r2ih, r2il = _df_mul(rih, ril, rih, ril)
    r4ih, r4il = _df_mul(r2ih, r2il, r2ih, r2il)
    r5h, r5l = _df_mul(r4ih, r4il, rih, ril)

    dSdh = dSdl = None
    for i in range(3):
        zh, zl = _df_mul(s_ref[3 * i, :][None, :], s_ref[9 + 3 * i, :][None, :],
                         *d[0])
        zh, zl = _df_add(zh, zl, *_df_mul(s_ref[3 * i + 1, :][None, :],
                                          s_ref[9 + 3 * i + 1, :][None, :],
                                          *d[1]))
        zh, zl = _df_add(zh, zl, *_df_mul(s_ref[3 * i + 2, :][None, :],
                                          s_ref[9 + 3 * i + 2, :][None, :],
                                          *d[2]))
        th, tl = _df_mul(*d[i], zh, zl)
        dSdh, dSdl = (th, tl) if dSdh is None else _df_add(dSdh, dSdl, th, tl)

    ch, cl = _df_mul(dSdh, dSdl, r5h, r5l)

    for k in range(3):
        uh, ul = _df_mul(ch, cl, *d[k])
        sh, sl = _df_reduce_lanes(uh, ul)
        ah, al = _df_add(out_ref[k, :], out_ref[3 + k, :], sh, sl)
        out_ref[k, :] = ah
        out_ref[3 + k, :] = al


def _df_split_T(a):
    """[n, c...] f64/f32 array -> [2c, n] rows (hi, then lo) via the shared
    `df_kernels._df_split` (one split implementation for both DF tiers)."""
    from .df_kernels import _df_split

    return _hl_to_rows(_df_split(a))


def _pallas_df_call(kernel, trg_hl, src_hl, payload_hl, n_trg, tile_t, tile_s,
                    interpret, flops_per_pair):
    """Shared pallas_call driver for the DF kernels; returns [n_trg, 3] f64."""
    # the lane reduction's halving tree + 128-lane roll reduce is only
    # correct for tile_s = 128 * 2^k (e.g. 384 leaves 96 lanes where the
    # roll offsets double-count; 64 makes roll-by-64 the identity)
    if tile_s < 128 or (tile_s // 128) & (tile_s // 128 - 1) or tile_s % 128:
        raise ValueError(f"tile_s must be 128 * 2^k, got {tile_s}")
    if tile_t < 1:
        raise ValueError(f"tile_t must be positive, got {tile_t}")
    rows_p = payload_hl.shape[0]
    nt = pl.cdiv(n_trg, tile_t) * tile_t
    ns = pl.cdiv(src_hl.shape[1], tile_s) * tile_s

    # zero padding everywhere — NOT the exact tiles' 1e18 sentinel: the
    # Dekker split multiplies by 4097, and (sentinel^2)*4097 overflows f32
    # to inf inside _df_rsqrt (NaN via inf - inf). Zero-pad sources are safe
    # here for the same reason as the XLA DF driver: every additive term
    # carries a payload factor (zero-padded), and an exactly-coincident
    # pad/target pair is dropped by the r2 > 0 mask.
    trg_p = _pad_to(trg_hl, nt, axis=1)
    src_p = _pad_to(src_hl, ns, axis=1)
    pay_p = _pad_to(payload_hl, ns, axis=1)

    grid = (nt // tile_t, ns // tile_s)
    z = np.int32(0)  # i64/i32 index-map mix breaks Mosaic (pallas_kernels)
    out = pl.pallas_call(
        kernel,
        out_shape=_out_struct((6, nt), jnp.float32, trg_p, src_p, pay_p),
        grid=grid,
        in_specs=[
            pl.BlockSpec((6, tile_t), lambda i, j: (z, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((6, tile_s), lambda i, j: (z, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((rows_p, tile_s), lambda i, j: (z, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((6, tile_t), lambda i, j: (z, i),
                               memory_space=pltpu.VMEM),
        cost_estimate=pl.CostEstimate(
            flops=flops_per_pair * nt * ns,
            bytes_accessed=4 * (6 * nt + (6 + rows_p) * ns + 6 * nt),
            transcendentals=nt * ns),
        interpret=interpret,
    )(trg_p, src_p, pay_p)

    # hi + lo is exactly representable in f64: one conversion per target
    u = (out[:3].astype(jnp.float64) + out[3:].astype(jnp.float64))
    return u.T[:n_trg]


def _require_x64(what):
    if not jax.config.jax_enable_x64:
        raise RuntimeError(
            f"{what} needs jax_enable_x64 for its float64 output "
            "(the pair arithmetic itself is f32)")


def _hl_to_rows(hl):
    """((hi, lo)) pair of [n, c...] arrays -> [2c, n] rows (hi, then lo)."""
    hi, lo = hl
    return jnp.concatenate([hi.reshape(hi.shape[0], -1).T,
                            lo.reshape(lo.shape[0], -1).T], axis=0)


def stokeslet_pallas_df_block(trg_hl, src_hl, f_hl, *, interpret: bool = False):
    """Unscaled DF Stokeslet partial sum for the ring evaluator.

    Same contract as `df_kernels._stokeslet_block_df`: operands are (hi, lo)
    f32 pairs of [n, 3] arrays (the `parallel.ring._ring_df` split), result
    is the UNSCALED [t, 3] float64 partial — the ring driver applies
    1/(8 pi eta) once at the end.
    """
    n_trg = trg_hl[0].shape[0]
    return _pallas_df_call(_stokeslet_df_kernel, _hl_to_rows(trg_hl),
                           _hl_to_rows(src_hl), _hl_to_rows(f_hl), n_trg,
                           DF_TILE_T, DF_TILE_S, interpret,
                           flops_per_pair=320)


def stresslet_pallas_df_block(trg_hl, src_hl, s_hl, *, interpret: bool = False):
    """Unscaled DF stresslet partial (includes the kernel's -3, like
    `df_kernels._stresslet_block_df`); ``s_hl`` is the (hi, lo) pair of the
    [n, 3, 3] double-layer source."""
    n_trg = trg_hl[0].shape[0]
    u = _pallas_df_call(_stresslet_df_kernel, _hl_to_rows(trg_hl),
                        _hl_to_rows(src_hl), _hl_to_rows(s_hl), n_trg,
                        DF_TILE_T, DF_TILE_S, interpret, flops_per_pair=420)
    return -3.0 * u


@partial(jax.jit, static_argnames=("tile_t", "tile_s", "interpret"))
def stokeslet_pallas_df(r_src, r_trg, f_src, eta, *, tile_t: int = DF_TILE_T,
                        tile_s: int = DF_TILE_S, interpret: bool = False):
    """Fused double-float Stokeslet sum (same contract as
    `kernels.stokeslet_direct`; f32/f64 inputs, float64 output)."""
    _require_x64("stokeslet_pallas_df")
    n_trg = r_trg.shape[0]
    if n_trg == 0 or r_src.shape[0] == 0:
        return jnp.zeros((n_trg, 3), dtype=jnp.float64)
    u = _pallas_df_call(_stokeslet_df_kernel, _df_split_T(r_trg),
                        _df_split_T(r_src), _df_split_T(f_src), n_trg,
                        tile_t, tile_s, interpret, flops_per_pair=320)
    return u / (8.0 * math.pi) / jnp.asarray(eta, dtype=jnp.float64)


@partial(jax.jit, static_argnames=("tile_t", "tile_s", "interpret"))
def stresslet_pallas_df(r_dl, r_trg, f_dl, eta, *, tile_t: int = DF_TILE_T,
                        tile_s: int = DF_TILE_S, interpret: bool = False):
    """Fused double-float stresslet sum (same contract as
    `kernels.stresslet_direct`: ``f_dl`` is [n_src, 3, 3]; float64 output).

    The -3 scale applies on the f64 reconstruction (scaling the (hi, lo)
    words by a non-power-of-two would round each word separately and
    destroy the compensation — `df_kernels` measured 2.7e-8 doing that).
    """
    _require_x64("stresslet_pallas_df")
    n_trg = r_trg.shape[0]
    if n_trg == 0 or r_dl.shape[0] == 0:
        return jnp.zeros((n_trg, 3), dtype=jnp.float64)
    u = _pallas_df_call(_stresslet_df_kernel, _df_split_T(r_trg),
                        _df_split_T(r_dl), _df_split_T(f_dl), n_trg,
                        tile_t, tile_s, interpret, flops_per_pair=420)
    return -3.0 * u / (8.0 * math.pi) / jnp.asarray(eta, dtype=jnp.float64)
