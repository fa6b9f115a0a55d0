"""Batched dense block matvec in double-float (compensated f32) words.

``y[b] = M[b] @ x[b]`` for a batch of dense blocks — the fiber-local
products of the coupled operator (`fibers.container.matvec` /
`apply_fiber_force`: each fiber's BC-applied ``A_bc`` [4n, 4n], its force
operator [3n, 4n], and the shared downsampling and differentiation
matrices) — to a float64-grade result from f32 VPU arithmetic, in one fused
Pallas tile. A TPU has no float64 unit: XLA emulates a float64 ``dot`` at
~2.5 G multiply-adds a second (PERF.md section 5), and the rows of ``A_bc``
reach 1e7, so neither the emulated ``dot`` (slow) nor a float32 one (O(1)
absolute noise) serves the Krylov loop of the mixed solver.

Arithmetic (the `ops.pallas_df._DF` helpers, the same error-free
transformations as the double-float pair tiles): every matrix entry is an
unevaluated (hi, lo) f32 pair split ONCE where the block is formed
(`split_words`), the vector is split once an application; each product
``M[r, c] * x[c]`` is Dekker's exact `two_prod` of the hi words plus the
two cross terms, and the sum along ``c`` runs by double-float adds only —
no f32-rounded partial sum anywhere between the products and the single
``hi + lo -> float64`` conversion a result element. Measured against the
float64 product on the fiber cell's own blocks: PERF.md section 6, PR 31.

Layout: the block keeps its natural ``[rows, cols]`` orientation, ``cols``
on the lanes. The kernel walks a block in strips of rows (`_strip_rows`:
32 at 256 columns): the vector's words broadcast along the sublanes, the strip's products fold by
double-float adds to one 128-lane vreg a strip, and the strips' partials
collect in a VMEM scratch ``[rows, 128]``; once a block of 128 rows the
scratch is transposed (XLU) and the 128 partials of each row — now along
the sublanes — reduce by elementwise double-float adds. Rows are padded
to a multiple of 8 and columns to a multiple of 128 where the words are
formed, never per application.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .df_kernels import _df_split
from .pallas_df import _DF, _require_x64, _round_up
from .pallas_kernels import _out_struct

__all__ = ["split_words", "block_matvec_df", "blocks_per_step",
           "fills_a_step", "matvec_df_streamed"]

#: bytes of ONE word of the matrix block a grid step holds in VMEM (two
#: words, double-buffered: four times this, of the 16 MiB a kernel may
#: take). Swept on a v5e at the fiber cell's 256 blocks of 256 x 256
#: (PERF.md section 6, PR 31): 0.5, 1 and 2 MiB read the same.
DF_BLOCK_BYTES = 2 << 20
#: f32 elements of one strip (rows x cols): a value of the strip body is
#: this many / 1,024 vregs, so that the scheduler has independent chains to
#: interleave (the pair tile's strips are 8 x 512). Swept alike: an
#: application of the fiber cell's operators reads 1.010 / 0.929 / 0.885 /
#: 0.896 ms at 2,048 / 4,096 / 8,192 / 16,384.
DF_STRIP_ELEMS = 8192


def split_words(m):
    """Float64 blocks ``[..., rows, cols]`` -> their (hi, lo) float32 words,
    zero-padded to ``rows % 8 == 0`` and ``cols % 128 == 0``: ``hi + lo``
    holds ``m`` to 2^-48. Works on NumPy arrays on the host (the static
    per-resolution matrices: split once a build, at trace time) and on
    traced arrays (the per-step blocks: split once a step, in `prep`)."""
    xp = np if isinstance(m, np.ndarray) else jnp
    rows, cols = m.shape[-2:]
    rows_p, cols_p = padded_block(rows, cols)
    pad = [(0, 0)] * (m.ndim - 2) + [(0, rows_p - rows), (0, cols_p - cols)]
    m = xp.pad(m, pad)
    hi = m.astype(xp.float32)
    lo = (m - hi.astype(xp.float64)).astype(xp.float32)
    return hi, lo


def padded_block(rows: int, cols: int) -> tuple[int, int]:
    """A block's rows x columns as `split_words` pads its words."""
    return _round_up(rows, 8), _round_up(cols, 128)


def blocks_per_step(n_blocks: int, rows: int, cols: int) -> int:
    """Blocks a grid step takes: the largest divisor of ``n_blocks`` whose
    words fit `DF_BLOCK_BYTES` (one block at least)."""
    fit = max(1, DF_BLOCK_BYTES // (4 * rows * cols))
    return max(d for d in range(1, min(fit, n_blocks) + 1)
               if n_blocks % d == 0)


def fills_a_step(n_blocks: int, rows: int, cols: int) -> bool:
    """Whether ``n_blocks`` blocks amount to one grid step of the tile
    (`DF_BLOCK_BYTES` of words: 8 blocks of 256 x 256). A smaller bucket —
    the walkthrough's one fiber — is four launches and their float64 glue
    around microseconds of work, and costs the emulated ``dot`` little; its
    callers keep the ``dot`` (`System._fiber_ops_for`)."""
    return 4 * n_blocks * rows * cols >= DF_BLOCK_BYTES


def _strip_rows(rows: int, cols: int) -> int:
    """Rows a strip takes: `DF_STRIP_ELEMS` worth, halved until they divide
    ``rows`` (8 always does: `split_words`)."""
    h = max(8, DF_STRIP_ELEMS // cols // 8 * 8)
    while rows % h:
        h //= 2
    return h


def _block_matvec_kernel(df, shared, mh_ref, ml_ref, xh_ref, xl_ref, oh_ref,
                         ol_ref, part_ref):
    """``fb`` blocks of one grid step. ``mh_ref`` / ``ml_ref`` [fb, rows,
    cols] (or [rows, cols], one matrix for every block: ``shared``) are the
    matrix words, ``xh_ref`` / ``xl_ref`` [fb, 1, cols] the vector's,
    ``oh_ref`` / ``ol_ref`` [fb, 1, rows_p] the result's; ``part_ref``
    [2, rows_p, 128] the strips' lane-wise partial sums (hi, lo), with
    ``rows_p`` the rows padded to whole blocks of 128. Scratch rows past
    ``rows`` are never written: after the transpose they are result lanes
    past ``rows``, which the caller drops."""
    fb = xh_ref.shape[0]
    rows, cols = mh_ref.shape[-2:]
    hs = _strip_rows(rows, cols)
    # loop bounds as int32 ARRAYS: Python ints give an i64 counter under
    # x64, which Mosaic refuses (`ops.pallas_df`)
    i32 = jnp.int32

    def block(f, _):
        xh, xl = xh_ref[f], xl_ref[f]                       # [1, cols]
        xb = [jnp.broadcast_to(w, (hs, cols))
              for w in (xh, xl, *df.split(xh))]

        def strip(s, _):
            r = pl.ds(pl.multiple_of(s * i32(hs), hs), hs)
            ah = mh_ref[r, :] if shared else mh_ref[f, r, :]
            al = ml_ref[r, :] if shared else ml_ref[f, r, :]
            ph, pl_ = df.mul(ah, al, xb[0], xb[1], y_sp=(xb[2], xb[3]))
            h, l = ph[:, :128], pl_[:, :128]
            for k in range(1, cols // 128):
                lanes = slice(k * 128, (k + 1) * 128)
                h, l = df.add(h, l, ph[:, lanes], pl_[:, lanes])
            part_ref[0, r, :] = h
            part_ref[1, r, :] = l

        lax.fori_loop(i32(0), i32(rows // hs), strip, None)

        def store(rb, h, l):
            oh_ref[f, :, rb] = h
            ol_ref[f, :, rb] = l

        _rows_to_lanes(df, part_ref, store)

    lax.fori_loop(i32(0), i32(fb), block, None)


def _rows_to_lanes(df, part_ref, store):
    """The rows' lane-wise partial sums ``part_ref`` [2, rows_p, 128] (hi,
    lo) -> each row's total, handed to ``store(lanes, hi, lo)`` as [1, 128]
    a block of 128 rows: the block's partials are transposed (XLU) and the
    128 of each row — now along the sublanes — reduce by elementwise
    double-float adds."""
    for b in range(part_ref.shape[1] // 128):
        rb = slice(b * 128, (b + 1) * 128)
        # [128 rows, 128 lanes] -> [128 lanes, 128 rows]: each row's
        # partials now lie along the sublanes
        h, l = part_ref[0, rb, :].T, part_ref[1, rb, :].T
        m = 128
        while m > 8:
            m //= 2
            h, l = df.add(h[:m], l[:m], h[m:], l[m:])
        for w in (4, 2, 1):
            # shift as int32: see `_DF.reduce_lanes`
            h, l = df.add(h, l, pltpu.roll(h, np.int32(w), 0),
                          pltpu.roll(l, np.int32(w), 0))
        store(rb, h[:1], l[:1])


#: f32 operations a matrix entry (the strip body's count without the
#: value-barrier selects: split 4, two_prod 8, cross terms 4, renormalise
#: 3, accumulate 11), for the cost estimate
_FLOPS_PER_ENTRY = 30


@partial(jax.jit, static_argnames=("n_rows", "interpret"))
def block_matvec_df(words, x, *, n_rows: int, interpret: bool = False):
    """``words`` = `split_words` of blocks ``[nb, rows, cols]`` — or of ONE
    matrix ``[rows, cols]`` applied to every vector — and ``x`` [nb, cols']
    (float64, or float32 values: their lo word is zero) -> ``[nb, n_rows]``
    float64. ``cols'`` and ``n_rows`` are the matrix's own sizes before
    `split_words` padded them."""
    _require_x64("block_matvec_df")
    mh, ml = words
    shared = mh.ndim == 2
    rows, cols = mh.shape[-2:]
    nb = x.shape[0]
    if nb == 0:
        return jnp.zeros((0, n_rows), dtype=jnp.float64)
    xh, xl = _df_split(x)
    pad = ((0, 0), (0, cols - x.shape[1]))
    xh = jnp.pad(xh, pad).reshape(nb, 1, cols)
    xl = jnp.pad(xl, pad).reshape(nb, 1, cols)

    fb = blocks_per_step(nb, rows, cols)
    rows_p = _round_up(rows, 128)
    z = np.int32(0)  # i64/i32 index-map mix breaks Mosaic (pallas_kernels)
    if shared:
        m_spec = pl.BlockSpec((rows, cols), lambda i: (z, z),
                              memory_space=pltpu.VMEM)
    else:
        m_spec = pl.BlockSpec((fb, rows, cols), lambda i: (i, z, z),
                              memory_space=pltpu.VMEM)
    x_spec = pl.BlockSpec((fb, 1, cols), lambda i: (i, z, z),
                          memory_space=pltpu.VMEM)
    o_spec = pl.BlockSpec((fb, 1, rows_p), lambda i: (i, z, z),
                          memory_space=pltpu.VMEM)
    out = _out_struct((nb, 1, rows_p), jnp.float32, mh, ml, xh, xl)
    oh, ol = pl.pallas_call(
        partial(_block_matvec_kernel, _DF(barrier=interpret), shared),
        out_shape=(out, out),
        grid=(nb // fb,),
        in_specs=[m_spec, m_spec, x_spec, x_spec],
        out_specs=(o_spec, o_spec),
        scratch_shapes=[pltpu.VMEM((2, rows_p, 128), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=_FLOPS_PER_ENTRY * nb * rows * cols,
            bytes_accessed=4 * (2 * (1 if shared else nb) * rows * cols
                                + 2 * nb * (cols + rows_p)),
            transcendentals=0),
        interpret=interpret,
    )(mh, ml, xh, xl)
    # hi + lo is exactly representable in f64: one conversion an element
    y = oh.astype(jnp.float64) + ol.astype(jnp.float64)
    return y.reshape(nb, rows_p)[:, :n_rows]


# ------------------------------------------- one large matrix, streamed

#: rows x columns of the matrix's words a grid step of the streamed tile
#: holds (`matvec_df_streamed`): two words, double-buffered, are
#: 16 x rows x columns bytes of VMEM. Swept on a v5e at the shell
#: operators of the shell cells (PERF.md section 6, PR 39).
STREAM_ROWS = 512
STREAM_COLS = 1024
#: columns of a strip's chunk: a strip of `_STREAM_STRIP` rows walks a
#: block's columns this many at a time (8 vregs a value, as `DF_STRIP_ELEMS`)
_STREAM_CHUNK = 256
_STREAM_STRIP = 32


def _streamed_kernel(df, n_col_blocks, last_cols, mh_ref, ml_ref, x_ref,
                     oh_ref, ol_ref, acc_ref):
    """Grid step (i, j): row block i of the matrix words ``mh_ref`` /
    ``ml_ref`` [R, C] against column block j of the vector's words
    ``x_ref`` [4, C] (hi, lo, and the Dekker halves of hi). ``acc_ref``
    [2, R, 128] carries each row's lane-wise partial sums (hi, lo) across
    the column blocks by double-float adds; the last column block hands
    them to `_rows_to_lanes`, which writes the row block's totals into
    ``oh_ref`` / ``ol_ref`` [1, R]. The last column block holds
    ``last_cols`` columns of the matrix: the rest of it lies past the
    matrix's edge, whose words are undefined there, and is masked (or,
    chunk by chunk, skipped). Rows past the edge compute garbage into
    lanes the caller drops."""
    j = pl.program_id(1)
    rows, cols = mh_ref.shape
    hs, cw = _STREAM_STRIP, _STREAM_CHUNK
    i32 = jnp.int32

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def accumulate(n_cols):
        def strip(s, _):
            r = pl.ds(pl.multiple_of(s * i32(hs), hs), hs)
            h = l = None
            for k in range(-(-n_cols // cw)):
                c = slice(k * cw, (k + 1) * cw)
                ah, al = mh_ref[r, c], ml_ref[r, c]
                if (k + 1) * cw > n_cols:
                    keep = (lax.broadcasted_iota(i32, (hs, cw), 1)
                            < n_cols - k * cw)
                    ah = jnp.where(keep, ah, 0.0)
                    al = jnp.where(keep, al, 0.0)
                xb = [jnp.broadcast_to(x_ref[w:w + 1, c], (hs, cw))
                      for w in range(4)]
                ph, pl_ = df.mul(ah, al, xb[0], xb[1], y_sp=(xb[2], xb[3]))
                m = cw
                while m > 128:
                    m //= 2
                    ph, pl_ = df.add(ph[:, :m], pl_[:, :m], ph[:, m:],
                                     pl_[:, m:])
                h, l = (ph, pl_) if h is None else df.add(h, l, ph, pl_)
            h, l = df.add(acc_ref[0, r, :], acc_ref[1, r, :], h, l)
            acc_ref[0, r, :] = h
            acc_ref[1, r, :] = l

        lax.fori_loop(i32(0), i32(rows // hs), strip, None)

    def finish():
        accumulate(last_cols)

        def store(rb, h, l):
            oh_ref[:, rb] = h
            ol_ref[:, rb] = l

        _rows_to_lanes(df, acc_ref, store)

    if n_col_blocks == 1:
        finish()
        return
    pl.when(j < n_col_blocks - 1)(lambda: accumulate(cols))
    pl.when(j == n_col_blocks - 1)(finish)


@partial(jax.jit, static_argnames=("interpret",))
def matvec_df_streamed(words, x, *, interpret: bool = False):
    """``words`` = the (hi, lo) float32 words of ONE large matrix [rows,
    cols], unpadded (`periphery.split_operator`), ``x`` [cols] (float64, or
    float32: its lo word is zero) -> ``[rows]`` float64: the product of
    `block_matvec_df`'s arithmetic, for a matrix that no VMEM holds. The
    grid walks the words from HBM in `STREAM_ROWS` x `STREAM_COLS` blocks,
    rows on its first axis, column blocks on its second; the vector is
    split once a call and padded to whole column blocks, the matrix never
    (its edge blocks are masked in the kernel)."""
    _require_x64("matvec_df_streamed")
    mh, ml = words
    rows, cols = mh.shape
    br = min(STREAM_ROWS, _round_up(rows, 128))
    bc = min(STREAM_COLS, _round_up(cols, _STREAM_CHUNK))
    n_i, n_j = -(-rows // br), -(-cols // bc)
    xh, xl = _df_split(x)
    # split by XLA, outside the kernel: the barrier keeps it from folding
    # the Dekker split away (`_DF`)
    xs = jnp.stack([xh, xl, *_DF(barrier=True).split(xh)])
    xs = jnp.pad(xs, ((0, 0), (0, n_j * bc - cols)))
    z = np.int32(0)  # i64/i32 index-map mix breaks Mosaic (pallas_kernels)
    m_spec = pl.BlockSpec((br, bc), lambda i, j: (i, j),
                          memory_space=pltpu.VMEM)
    o_spec = pl.BlockSpec((1, br), lambda i, j: (z, i),
                          memory_space=pltpu.VMEM)
    out = _out_struct((1, n_i * br), jnp.float32, mh, ml, xs)
    oh, ol = pl.pallas_call(
        partial(_streamed_kernel, _DF(barrier=interpret), n_j,
                cols - (n_j - 1) * bc),
        out_shape=(out, out),
        grid=(n_i, n_j),
        in_specs=[m_spec, m_spec,
                  pl.BlockSpec((4, bc), lambda i, j: (z, j),
                               memory_space=pltpu.VMEM)],
        out_specs=(o_spec, o_spec),
        scratch_shapes=[pltpu.VMEM((2, br, 128), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # the words' 16 x R x C double-buffered bytes, and room beside
            vmem_limit_bytes=max(32 << 20, 24 * br * bc)),
        cost_estimate=pl.CostEstimate(
            flops=_FLOPS_PER_ENTRY * rows * cols,
            bytes_accessed=8 * rows * cols + 16 * n_i * n_j * bc
            + 8 * n_i * br,
            transcendentals=0),
        interpret=interpret,
    )(mh, ml, xs)
    # hi + lo is exactly representable in f64: one conversion an element
    y = oh.astype(jnp.float64) + ol.astype(jnp.float64)
    return y[0, :rows]
