"""Pallas double-float (compensated f32) pairwise kernels.

Fuses the `ops.df_kernels` arithmetic — Dekker/Knuth error-free
transformations giving ~1e-14-class relative accuracy from pure f32 VPU ops
— into VMEM interaction tiles like `ops.pallas_kernels`. Inside the step the
XLA DF path measures 0.65 Gpairs/s on a v5e chip (the per-pair chain is ~15x
the exact kernel's flops and XLA spends it through HBM-staged fusions);
these tiles keep the whole chain in registers and measure 10 Gpairs/s
(Stokeslet) and 7.6 Gpairs/s (stresslet) at 16,384^2 (PERF.md, PR 27).

Numerics: per-pair arithmetic is double-float (every value an unevaluated
(hi, lo) f32 pair); each lane of a strip accumulates its pair terms by DF
adds across the whole source axis, and once a target tile the lanes reduce
by a compensated halving tree down to one 128-lane vreg, then a lane-roll
log-reduction — no f32-rounded sum anywhere between the pair terms and the
final hi+lo -> f64 reconstruction on the host side of the kernel.

FMA-contraction hardening: the inexact-product-feeding-add sites are
barrier-wrapped (`_DF.bar`) exactly like `ops.df_kernels` (see the long analysis
there) — in `interpret=True` mode only, where the kernel body runs through
XLA:CPU and LLVM's FMA contraction is live. On real TPUs the Mosaic
pipeline evaluates each kernel value once into a vreg (no XLA-style
cross-fusion cloning), so the hazard class that motivated the hardening
cannot arise, and the selects are left out (`_DF`). The on-chip
agreement gate (`chip_smoke.py`, `gate_kernels`: both tiles against a
NumPy f64 oracle) is the authority for real-hardware accuracy, mirroring
the exact-kernel gate; `scripts/sweep_pallas_df.py` reads the same error
at the benchmark's shapes.

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

# Tile shapes, swept on a v5e (PERF.md section 6, PR 27). The kernels keep a
# (tile_t, tile_s) block in VMEM and walk it in strips of 8 targets x
# DF_STRIP_W sources, a value being DF_STRIP_W / 128 vregs. The strip's width
# is what the rate follows (Stokeslet at 16,384^2: 5.3 / 8.6 / 10.0 / 8.8
# Gpairs/s at 128 / 256 / 512 / 1,024 lanes); tile_t and tile_s move it by
# under 2 %, so the block is the small one that compiles in under a second.
DF_TILE_T = 128
DF_TILE_S = 2048
DF_STRIP_W = 512

#: Dekker split constant for f32 (2^ceil(24/2) + 1)
_SPLIT_F32 = 4097.0

class _DF:
    """Double-float arithmetic on (hi, lo) f32 word pairs.

    ``barrier`` says whether each rounded intermediate goes through a value
    barrier (`bar`). `df_kernels` uses `lax.optimization_barrier` for these
    sites, but that has no Mosaic lowering inside a Pallas kernel; the
    select here is value-preserving (operands are non-NaN), cannot be
    folded without NaN reasoning, and lowers on every path. XLA needs it:
    without, XLA:CPU algebraically collapses the error-extraction
    expressions — measured 2.7e-8 instead of 1e-14 on this very kernel in
    interpret mode (round 5), the failure class `df_kernels` documents.
    Mosaic does not: it evaluates each kernel value once into a vreg and
    reassociates nothing, and on a v5e the tiles read the same 2.4e-14 /
    1.1e-13 against the f64 oracle without the selects as with them, at
    every shape swept, and run 1.5x as fast (PERF.md, PR 27) — a compare
    and a select at some 170 sites a pair were 40 % of the VPU work. So
    the barrier is on exactly where XLA compiles the body: interpret mode.
    """

    def __init__(self, barrier: bool):
        self.barrier = barrier

    def bar(self, x):
        if not self.barrier:
            return x
        return jnp.where(x == x, x, jnp.zeros_like(x))

    def two_sum(self, a, b):
        """Error-free a + b = s + e (Knuth; no magnitude ordering required)."""
        s = self.bar(a + b)
        bb = self.bar(s - a)
        e = (a - self.bar(s - bb)) + (b - bb)
        return s, e

    def quick_two_sum(self, a, b):
        """Error-free a + b = s + e assuming |a| >= |b|."""
        s = self.bar(a + b)
        e = b - (s - a)
        return s, e

    def split(self, a):
        """Dekker split a = hi + lo, each half fitting 12 bits. A word that
        is an operand of several products is split once and the halves
        handed to `mul` (`x_sp` / `y_sp`): the same values, a third fewer
        operations."""
        big = self.bar(_SPLIT_F32 * a)
        hi = self.bar(big - self.bar(big - a))
        return hi, a - hi

    def two_prod(self, a, b, a_sp=None, b_sp=None):
        """Error-free a * b = p + e via Dekker splitting (no FMA dependency)."""
        p = self.bar(a * b)
        a_hi, a_lo = a_sp or self.split(a)
        b_hi, b_lo = b_sp or self.split(b)
        e = ((a_hi * b_hi - p) + a_hi * b_lo + a_lo * b_hi) + a_lo * b_lo
        return p, e

    def add(self, xh, xl, yh, yl):
        s, e = self.two_sum(xh, yh)
        e = e + (xl + yl)
        return self.quick_two_sum(s, e)

    def mul(self, xh, xl, yh, yl, x_sp=None, y_sp=None):
        """DF product; ``yl=None`` is a single-word ``y`` (an exact zero low
        word: its cross product is dropped, not multiplied out)."""
        p, e = self.two_prod(xh, yh, x_sp, y_sp)
        cross = self.bar(xl * yh)
        if yl is not None:
            cross = self.bar(xh * yl) + cross
        return self.quick_two_sum(p, e + cross)

    def rsqrt(self, xh, xl):
        """1/sqrt(x) as DF: f32 hardware seed + one DF Newton step (doubles
        the accurate bits to full DF precision). Assumes x > 0 (callers
        mask)."""
        y0 = lax.rsqrt(xh)
        y_sp = self.split(y0)
        th, tl = self.mul(xh, xl, y0, None, y_sp=y_sp)
        th, tl = self.mul(th, tl, y0, None, y_sp=y_sp)
        rh, rl = self.add(jnp.full_like(th, 3.0), jnp.zeros_like(th), -th, -tl)
        yh, yl = self.mul(rh, rl, y0, None, y_sp=y_sp)
        return 0.5 * yh, 0.5 * yl

    def reduce_lanes(self, h, l):
        """Compensated sum along the lane axis of [t, s] -> [t] DF pairs.

        Halving slices keep full 128-lane vregs down to one vreg width; the
        final 128 lanes reduce by lane rolls (full-shape ops Mosaic handles
        natively — no sub-128 slicing). The rolled-in lanes make every lane
        k hold sum(lanes k..k+2^m-1 mod 128); lane 0 is the true total,
        selected by the final [:, 0]. Correct for a width of 128 * 2^k only
        (384 leaves 96 lanes where the roll offsets double-count; 64 makes
        roll-by-64 the identity): `_strip_width` hands it no other.
        """
        while h.shape[1] > 128:
            m = h.shape[1] // 2
            h, l = self.add(h[:, :m], l[:, :m], h[:, m:], l[:, m:])
        w = 64
        while w >= 1:
            # rotation direction is irrelevant for a log-reduce (pltpu.roll
            # requires non-negative shifts): after all steps every lane
            # holds the full 128-lane total
            # the shift must be 32-bit: a Python int traces as i64 under x64
            # (which `_require_x64` demands) and Mosaic's dynamic_rotate
            # refuses it
            hr = pltpu.roll(h, np.int32(w), 1)
            lr = pltpu.roll(l, np.int32(w), 1)
            h, l = self.add(h, l, hr, lr)
            w //= 2
        return h[:, 0], l[:, 0]

    def diff(self, t_hi, t_lo, s_hi, s_lo):
        """DF displacement component t - s with full two_sum (nearly
        coincident f64 points can have lo-word differences exceeding |hi
        difference|)."""
        dh, de = self.two_sum(t_hi, -s_hi)
        return self.two_sum(dh, de + (t_lo - s_lo))

    def rinv(self, d, d_sp):
        """DF 1/r of the displacement ``d`` (three DF components, their hi
        words split in ``d_sp``); exactly zero for a coincident pair."""
        r2h, r2l = self.mul(*d[0], *d[0], d_sp[0], d_sp[0])
        for k in (1, 2):
            r2h, r2l = self.add(r2h, r2l,
                                *self.mul(*d[k], *d[k], d_sp[k], d_sp[k]))
        mask = r2h > 0.0
        rih, ril = self.rsqrt(jnp.where(mask, r2h, 1.0),
                              jnp.where(mask, r2l, 0.0))
        return jnp.where(mask, rih, 0.0), jnp.where(mask, ril, 0.0)


def _stokeslet_df_terms(df, d, d_sp, f, f_sp):
    """The three DF velocity terms of one strip: ``d`` the DF displacement,
    ``f(k)`` / ``f_sp(k)`` the DF force row k and the split of its hi word."""
    ri = df.rinv(d, d_sp)
    ri_sp = df.split(ri[0])
    r3 = df.mul(*ri, *ri, ri_sp, ri_sp)
    r3 = df.mul(*r3, *ri, y_sp=ri_sp)

    dfh, dfl = df.mul(*d[0], *f(0), d_sp[0], f_sp(0))
    for k in (1, 2):
        dfh, dfl = df.add(dfh, dfl, *df.mul(*d[k], *f(k), d_sp[k], f_sp(k)))
    c = df.mul(dfh, dfl, *r3)
    c_sp = df.split(c[0])

    out = []
    for k in range(3):
        uh, ul = df.mul(*ri, *f(k), ri_sp, f_sp(k))
        out.append(df.add(uh, ul, *df.mul(*c, *d[k], c_sp, d_sp[k])))
    return out


def _stresslet_df_terms(df, d, d_sp, S, S_sp):
    """DF stresslet terms u_k = (d.S.d) d_k / r^5 of one strip (the -3 goes
    on at the f64 reconstruction); ``S(m)`` is row m of the 9 (row-major)."""
    ri = df.rinv(d, d_sp)
    ri_sp = df.split(ri[0])
    r2i = df.mul(*ri, *ri, ri_sp, ri_sp)
    r4i = df.mul(*r2i, *r2i)
    r5 = df.mul(*r4i, *ri, y_sp=ri_sp)

    dSdh = dSdl = None
    for i in range(3):
        zh, zl = df.mul(*S(3 * i), *d[0], S_sp(3 * i), d_sp[0])
        for k in (1, 2):
            zh, zl = df.add(zh, zl, *df.mul(*S(3 * i + k), *d[k],
                                            S_sp(3 * i + k), d_sp[k]))
        th, tl = df.mul(*d[i], zh, zl, d_sp[i])
        dSdh, dSdl = (th, tl) if dSdh is None else df.add(dSdh, dSdl, th, tl)

    c = df.mul(dSdh, dSdl, *r5)
    c_sp = df.split(c[0])
    return [df.mul(*c, *d[k], c_sp, d_sp[k]) for k in range(3)]


def _df_kernel(df, terms, n_pay, trg_ref, src_ref, out_ref, tb_ref, acc_ref,
               sp_ref):
    """One (tile_t, tile_s) block of a DF pair sum, walked in strips.

    ``trg_ref`` [6, tile_t] carries the targets' hi rows then lo rows;
    ``src_ref`` [tile_s / w, 6 + 2 n_pay, w] the sources in chunks of ``w``
    lanes: position hi, lo, then payload hi, lo rows. ``tb_ref`` holds the
    targets broadcast along the lanes and ``acc_ref`` the lane-wise DF
    partial sums, both [6, tile_t, w] and alive across the source axis of
    the grid: the lane reduction runs once a target tile, not once a block.
    ``sp_ref`` [2, tile_s / w, rows, w] is the Dekker split of the source
    block, which depends on the source alone and so is made once a block.
    """
    j = pl.program_id(1)
    tile_t, w = tb_ref.shape[1:]
    n_chunks = src_ref.shape[0]
    # loop bounds as int32 ARRAYS: Python and NumPy ints give the loop an i64
    # counter under x64, which Mosaic refuses
    i32 = jnp.int32

    @pl.when(j == 0)
    def _():
        for k in range(6):
            tb_ref[k] = jnp.broadcast_to(trg_ref[k, :][:, None], (tile_t, w))
        acc_ref[:] = jnp.zeros_like(acc_ref)

    sp_ref[0], sp_ref[1] = df.split(src_ref[:])

    def strip(r, _):
        rows = pl.ds(pl.multiple_of(r * i32(8), 8), 8)
        t = [tb_ref[k, rows, :] for k in range(6)]

        def chunk(c, acc):
            def row(ref, m):
                return jnp.broadcast_to(ref[c, pl.ds(m, 1), :], (8, w))

            d = [df.diff(t[k], t[3 + k], row(src_ref, k), row(src_ref, 3 + k))
                 for k in range(3)]
            d_sp = [df.split(dk[0]) for dk in d]
            u = terms(
                df, d, d_sp,
                lambda m: (row(src_ref, 6 + m), row(src_ref, 6 + n_pay + m)),
                lambda m: (row(sp_ref.at[0], 6 + m),
                           row(sp_ref.at[1], 6 + m)))
            return tuple(df.add(*acc[k], *u[k]) for k in range(3))

        acc = tuple((acc_ref[k, rows, :], acc_ref[3 + k, rows, :])
                    for k in range(3))
        acc = lax.fori_loop(i32(0), i32(n_chunks), chunk, acc)
        for k in range(3):
            acc_ref[k, rows, :], acc_ref[3 + k, rows, :] = acc[k]

    lax.fori_loop(i32(0), i32(tile_t // 8), strip, None)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        for k in range(3):
            out_ref[k, :], out_ref[3 + k, :] = df.reduce_lanes(
                acc_ref[k], acc_ref[3 + k])


def _df_split_T(a):
    """[n, c...] f64/f32 array -> [2c, n] rows (hi, then lo) via the shared
    `df_kernels._df_split` (one split implementation for both DF tiers)."""
    from .df_kernels import _df_split

    return _hl_to_rows(_df_split(a))


def _round_up(n, m):
    return -(-n // m) * m


def _strip_width(n_src, strip_w):
    """Lanes a strip walks at once: ``strip_w``, or the next 128 * 2^k over
    a smaller source set (64 fiber nodes pad to 128, not to a whole strip)."""
    w = 128
    while w < min(strip_w, n_src):
        w *= 2
    return w


#: f32 operations a pair of each strip body (its jaxpr's count, without the
#: selects), for the cost estimate
_FLOPS_PER_PAIR = {_stokeslet_df_terms: 505, _stresslet_df_terms: 670}


def _pallas_df_call(terms, trg_hl, src_hl, payload_hl, n_trg, tile_t, tile_s,
                    strip_w, interpret):
    """Shared pallas_call driver for the DF kernels; returns [n_trg, 3] f64."""
    if strip_w < 128 or strip_w & (strip_w - 1):
        # `_DF.reduce_lanes` is only correct for these widths
        raise ValueError(f"strip_w must be 128 * 2^k, got {strip_w}")
    if tile_t < 8 or tile_t % 8 or tile_s % strip_w:
        raise ValueError(f"tile_t must be a multiple of 8 and tile_s of "
                         f"strip_w={strip_w}, got ({tile_t}, {tile_s})")
    # the tile follows the shapes the call can see: a block no wider than
    # the padded source set, no taller than the padded targets
    n_src = src_hl.shape[1]
    w = _strip_width(n_src, strip_w)
    tile_s = min(tile_s, _round_up(n_src, w))
    tile_t = min(tile_t, _round_up(n_trg, 8))
    nt = _round_up(n_trg, tile_t)
    ns = _round_up(n_src, tile_s)
    # rows a multiple of the 8-sublane tiling (Mosaic refuses a 12-row slice)
    rows = _round_up(6 + payload_hl.shape[0], 8)

    # zero padding everywhere — NOT the exact tiles' 1e18 sentinel: the
    # Dekker split multiplies by 4097, and (sentinel^2)*4097 overflows f32
    # to inf inside `_DF.rsqrt` (NaN via inf - inf). Zero-pad sources are safe
    # here for the same reason as the XLA DF driver: every additive term
    # carries a payload factor (zero-padded), and an exactly-coincident
    # pad/target pair is dropped by the r2 > 0 mask.
    trg_p = _pad_to(trg_hl, nt, axis=1)
    src_p = jnp.concatenate([src_hl, payload_hl], axis=0)
    src_p = _pad_to(_pad_to(src_p, rows, axis=0), ns, axis=1)
    # [rows, ns] -> [ns / w, rows, w]: a strip indexes its chunk of sources
    # on the leading axis
    src_p = src_p.reshape(rows, ns // w, w).transpose(1, 0, 2)

    grid = (nt // tile_t, ns // tile_s)
    z = np.int32(0)  # i64/i32 index-map mix breaks Mosaic (pallas_kernels)
    kernel = partial(_df_kernel, _DF(barrier=interpret), terms,
                     payload_hl.shape[0] // 2)
    out = pl.pallas_call(
        kernel,
        out_shape=_out_struct((6, nt), jnp.float32, trg_p, src_p),
        grid=grid,
        in_specs=[
            pl.BlockSpec((6, tile_t), lambda i, j: (z, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile_s // w, rows, w), lambda i, j: (j, z, z),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((6, tile_t), lambda i, j: (z, i),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((6, tile_t, w), jnp.float32),
            pltpu.VMEM((6, tile_t, w), jnp.float32),
            pltpu.VMEM((2, tile_s // w, rows, w), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=_FLOPS_PER_PAIR[terms] * nt * ns,
            bytes_accessed=4 * (6 * nt + rows * ns + 6 * nt),
            transcendentals=nt * ns),
        interpret=interpret,
    )(trg_p, src_p)

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
    return _pallas_df_call(_stokeslet_df_terms, _hl_to_rows(trg_hl),
                           _hl_to_rows(src_hl), _hl_to_rows(f_hl), n_trg,
                           DF_TILE_T, DF_TILE_S, DF_STRIP_W, interpret)


def stresslet_pallas_df_block(trg_hl, src_hl, s_hl, *, interpret: bool = False):
    """Unscaled DF stresslet partial (includes the kernel's -3, like
    `df_kernels._stresslet_block_df`); ``s_hl`` is the (hi, lo) pair of the
    [n, 3, 3] double-layer source."""
    n_trg = trg_hl[0].shape[0]
    u = _pallas_df_call(_stresslet_df_terms, _hl_to_rows(trg_hl),
                        _hl_to_rows(src_hl), _hl_to_rows(s_hl), n_trg,
                        DF_TILE_T, DF_TILE_S, DF_STRIP_W, interpret)
    return -3.0 * u


@partial(jax.jit,
         static_argnames=("tile_t", "tile_s", "strip_w", "interpret"))
def stokeslet_pallas_df(r_src, r_trg, f_src, eta, *, tile_t: int = DF_TILE_T,
                        tile_s: int = DF_TILE_S, strip_w: int = DF_STRIP_W,
                        interpret: bool = False):
    """Fused double-float Stokeslet sum (same contract as
    `kernels.stokeslet_direct`; f32/f64 inputs, float64 output)."""
    _require_x64("stokeslet_pallas_df")
    n_trg = r_trg.shape[0]
    if n_trg == 0 or r_src.shape[0] == 0:
        return jnp.zeros((n_trg, 3), dtype=jnp.float64)
    u = _pallas_df_call(_stokeslet_df_terms, _df_split_T(r_trg),
                        _df_split_T(r_src), _df_split_T(f_src), n_trg,
                        tile_t, tile_s, strip_w, interpret)
    return u / (8.0 * math.pi) / jnp.asarray(eta, dtype=jnp.float64)


@partial(jax.jit,
         static_argnames=("tile_t", "tile_s", "strip_w", "interpret"))
def stresslet_pallas_df(r_dl, r_trg, f_dl, eta, *, tile_t: int = DF_TILE_T,
                        tile_s: int = DF_TILE_S, strip_w: int = DF_STRIP_W,
                        interpret: bool = False):
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
    u = _pallas_df_call(_stresslet_df_terms, _df_split_T(r_trg),
                        _df_split_T(r_dl), _df_split_T(f_dl), n_trg,
                        tile_t, tile_s, strip_w, interpret)
    return -3.0 * u / (8.0 * math.pi) / jnp.asarray(eta, dtype=jnp.float64)
