"""Pairwise Stokes kernels (Stokeslet / stresslet / rotlet / regularized Oseen).

TPU-native re-implementation of the reference evaluator seam
(`/root/reference/include/kernels.hpp:14-51`, `/root/reference/src/core/kernels.cpp`):
the uniform `Evaluator` signature (r_sl, r_dl, r_trg, f_sl, f_dl, eta) maps here to
plain jit-able functions over `[n, 3]` row-major arrays. All functions are pure,
shape-static, and differentiable; the hot all-pairs sums are evaluated in target
blocks so XLA can tile the distance matmuls onto the MXU without materializing the
full O(N^2) interaction tensor.

Conventions (matched to the reference semantics):

* Stokeslet (Oseen tensor): ``u_i = 1/(8 pi eta) * sum_j [ f_j / r + (d . f_j) d / r^3 ]``
  with ``d = x_trg - x_src`` and the self term (r == 0) dropped
  (`src/core/kernels.cpp:54-67` scale factor 1/(8 pi), divided by eta).
* Stresslet ("stokes_doublevel", 9-component double-layer source):
  ``u = 1/(8 pi eta) * sum_j -3 (d^T S_j d) d / r^5`` (`src/core/kernels.cpp:11-40`).
* Regularized Oseen: for ``r <= epsilon_distance`` replace ``1/r -> 1/sqrt(r^2+reg^2)``
  (`src/core/kernels.cpp:85-195`, defaults reg=5e-3, eps=1e-5 `include/kernels.hpp:35-51`).
* Rotlet: ``u = 1/(8 pi eta) * sum_j (rho_j x d) / r^3`` (`src/core/kernels.cpp:206-242`).
* stresslet_times_normal(_times_density): factor -3/(4 pi), no eta
  (`src/core/kernels.cpp:264-334`); consistent with the stresslet above under the
  double-layer convention ``f_dl = 2 eta n (x) rho``.
"""

from __future__ import annotations

import logging
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

logger = logging.getLogger("skellysim_tpu")

DEFAULT_REG = 5e-3
DEFAULT_EPS = 1e-5

__all__ = [
    "stokeslet_direct",
    "stresslet_direct",
    "oseen_contract",
    "oseen_tensor",
    "rotlet",
    "stresslet_times_normal",
    "stresslet_times_normal_times_density",
]


#: every pair-sum evaluation below is scoped ``pair`` for device-time
#: attribution (obs/profile.py OPERATOR_SCOPES): the tile, whichever it is,
#: and its glue. `jax.named_scope` under the `jax.jit` decorator, so the
#: scope is part of the traced body; metadata only, the program is unchanged


def _block_iter(n: int, block: int) -> int:
    """Number of blocks covering n (n padded up to a multiple of block)."""
    return -(-n // block)


def _blocked_target_sum(kernel_fn, r_trg, block_size):
    """Evaluate ``kernel_fn(trg_block) -> [b, 3]`` over target blocks via lax.map.

    Pads targets to a block multiple so every iteration has a static shape; the
    padding rows compute garbage that is sliced off. This keeps compile time flat
    across target counts within the same padded bucket while bounding peak memory
    at O(block_size * n_src).
    """
    n_trg = r_trg.shape[0]
    if n_trg == 0:
        return jnp.zeros((0, 3), dtype=r_trg.dtype)
    nb = _block_iter(n_trg, block_size)
    pad = nb * block_size - n_trg
    r_pad = jnp.pad(r_trg, ((0, pad), (0, 0)))
    blocks = r_pad.reshape(nb, block_size, 3)
    u = lax.map(kernel_fn, blocks)
    return u.reshape(nb * block_size, 3)[:n_trg]


#: sources beyond this count are chunked (the [t_block, n_src] intermediates
#: would otherwise scale HBM use linearly with n_src — 640k sources against
#: a 4096-target block is a 31 GB displacement tensor)
_SRC_CHUNK_THRESHOLD = 32768
_DEFAULT_SRC_BLOCK = 8192


def _pair_sum(pair_fn, r_trg, src_arrays, block_size, source_block):
    """Target-blocked, source-chunked pairwise sum.

    ``pair_fn(trg_block, *src_chunk_arrays) -> [t, 3]`` must give zero
    contribution for zero-padded sources (every kernel here does: padded
    strengths are zero, and exactly-coincident pairs are masked).
    """
    n_src = src_arrays[0].shape[0]
    if source_block is None:
        source_block = (_DEFAULT_SRC_BLOCK if n_src > _SRC_CHUNK_THRESHOLD
                        else None)
    if source_block is None or n_src <= source_block:
        return _blocked_target_sum(lambda trg: pair_fn(trg, *src_arrays),
                                   r_trg, block_size)
    ns_b = _block_iter(n_src, source_block)
    pad = ns_b * source_block - n_src
    chunks = tuple(
        jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
            (ns_b, source_block) + a.shape[1:])
        for a in src_arrays)

    def kernel(trg):
        def body(acc, chunk):
            return acc + pair_fn(trg, *chunk), None

        acc, _ = lax.scan(body, jnp.zeros((trg.shape[0], 3), dtype=trg.dtype),
                          chunks)
        return acc

    return _blocked_target_sum(kernel, r_trg, block_size)


def stokeslet_block(trg, src, f_src):
    """Unscaled Stokeslet partial sum of one (target-block, source-block) pair.

    Shared by the blocked single-program path and the ring evaluator
    (`parallel/ring.py`) so the masking/regularization semantics cannot
    diverge between backends.
    """
    d = trg[:, None, :] - src[None, :, :]
    r2 = jnp.sum(d * d, axis=-1)
    mask = r2 > 0.0
    rinv = jnp.where(mask, lax.rsqrt(jnp.where(mask, r2, 1.0)), 0.0)
    rinv3 = rinv * rinv * rinv
    df = jnp.einsum("tsk,sk->ts", d, f_src)
    return jnp.einsum("ts,sk->tk", rinv, f_src) + jnp.einsum("ts,tsk->tk", df * rinv3, d)


def stokeslet_block_mxu(trg, src, f_src):
    """`stokeslet_block` restructured so the O(t*s*3) contractions are MXU
    matmuls instead of reductions over a materialized [t, s, 3] displacement
    tensor:

      r2_ts = |t|^2 + |s|^2 - 2 (t @ s^T)               (one [t,3]x[3,s] matmul)
      df_ts = (t @ f^T) - (s . f)_s                      (one matmul)
      u_tk  = rinv @ f + t_k * rowsum(c) - c @ s,  c = df * rinv^3
                                                         (two [t,s]x[s,3] matmuls)

    Only rsqrt + ~6 multiplies per pair stay elementwise on the VPU.

    NUMERICS CAVEAT (why this is opt-in, not the default): the subtraction
    form loses absolute accuracy ~eps * (|t'|^2 + |s'|^2) on r2, so (a) exact
    self-pair detection by r2 == 0 is no longer reliable — pairs are instead
    masked below a relative threshold 16 eps (|t'|^2+|s'|^2), i.e.
    separations under ~4 sqrt(eps) |t'| are treated as coincident — and (b)
    near-field pairs closer than ~sqrt(eps) |t'| carry O(1) relative error.

    Coordinates are recentered on the source block's *first point* (t', s'):
    the dangerous pairs are close ones, and a close target sits near the
    source block, so when source blocks are spatially local (consecutive
    nodes of one fiber; `fibers.container.sort_fibers_morton` for whole
    clouds) |t'| is the block extent and both bounds tighten to harmless.
    Pure far-field blocks have large r2, where the subtraction form is
    accurate anyway. The first point — not the mean — because zero- or
    sentinel-padded tail sources (the ring evaluator pads at 1e7) would
    drag a mean arbitrarily far from the real points.
    """
    center = src[0]
    trg = trg - center
    src = src - center
    eps = jnp.finfo(trg.dtype).eps
    t2 = jnp.sum(trg * trg, axis=1)
    s2 = jnp.sum(src * src, axis=1)
    ts = trg @ src.T
    scale = t2[:, None] + s2[None, :]
    r2 = jnp.maximum(scale - 2.0 * ts, 0.0)
    mask = r2 > 16.0 * eps * scale
    rinv = jnp.where(mask, lax.rsqrt(jnp.where(mask, r2, 1.0)), 0.0)
    rinv3 = rinv * rinv * rinv
    df = trg @ f_src.T - jnp.sum(src * f_src, axis=1)[None, :]
    c = df * rinv3
    return rinv @ f_src + trg * jnp.sum(c, axis=1, keepdims=True) - c @ src


def stresslet_block_mxu(trg, src, S):
    """`stresslet_block` in matmul form (same strategy and numerics caveat as
    `stokeslet_block_mxu`): with d = t - s,

      d.S.d = T9 @ S9^T - t @ (S s + S^T s)^T + (s.S.s)     (matmuls; T9/S9
               are the 9 coordinate products t_i t_j / S_ij per point)
      u_tk  = t_k rowsum(c) - c @ s,   c = -3 (d.S.d) r^-5   (two matmuls)

    leaving rsqrt + ~6 multiplies per pair on the VPU. Like
    `stokeslet_block_mxu`, coordinates recenter on the source block's first
    point.
    """
    center = src[0]
    trg = trg - center
    src = src - center
    eps = jnp.finfo(trg.dtype).eps
    t2 = jnp.sum(trg * trg, axis=1)
    s2 = jnp.sum(src * src, axis=1)
    scale = t2[:, None] + s2[None, :]
    r2 = jnp.maximum(scale - 2.0 * (trg @ src.T), 0.0)
    mask = r2 > 16.0 * eps * scale
    rinv = jnp.where(mask, lax.rsqrt(jnp.where(mask, r2, 1.0)), 0.0)
    rinv5 = (rinv * rinv) ** 2 * rinv

    T9 = (trg[:, :, None] * trg[:, None, :]).reshape(trg.shape[0], 9)
    S9 = S.reshape(S.shape[0], 9)
    Ss = jnp.einsum("sij,sj->si", S, src)
    STs = jnp.einsum("sij,si->sj", S, src)
    sSs = jnp.einsum("si,si->s", src, Ss)
    dSd = T9 @ S9.T - trg @ (Ss + STs).T + sSs[None, :]
    c = -3.0 * dSd * rinv5
    return trg * jnp.sum(c, axis=1, keepdims=True) - c @ src


def stresslet_block(trg, src, S):
    """Unscaled stresslet partial sum of one (target-block, source-block) pair."""
    d = trg[:, None, :] - src[None, :, :]
    r2 = jnp.sum(d * d, axis=-1)
    mask = r2 > 0.0
    rinv = jnp.where(mask, lax.rsqrt(jnp.where(mask, r2, 1.0)), 0.0)
    rinv5 = rinv * rinv * rinv * rinv * rinv
    dSd = jnp.einsum("tsi,sij,tsj->ts", d, S, d)
    return jnp.einsum("ts,tsk->tk", -3.0 * dSd * rinv5, d)


def oseen_block(trg, src, density, eta, reg, epsilon_distance):
    """Regularized-Oseen partial sum (already eta-scaled via fr/gr)."""
    d = trg[:, None, :] - src[None, :, :]
    r2 = jnp.sum(d * d, axis=-1)
    fr, gr = _regularized_frgr(r2, eta, reg, epsilon_distance)
    df = jnp.einsum("tsk,sk->ts", d, density)
    return jnp.einsum("ts,sk->tk", fr, density) + jnp.einsum("ts,tsk->tk", gr * df, d)


def resolve_impl(impl: str, *operands) -> str:
    """The f32 pair tile a seam runs for the name it was handed and the
    operands (arrays or dtypes) it holds — the ONE resolver every seam that
    takes a tile name calls before it compares the name (`stokeslet_direct`
    / `stresslet_direct` here, the ring evaluator in `parallel.ring`), so
    none of them sees ``"auto"`` and the contract cannot drift between them.

    ``"auto"`` (`Params.kernel_impl`'s default) follows what the code can
    observe, as ``solver_precision="auto"`` and ``refine_pair_impl="auto"``
    do: the fused Pallas tile on a TPU when no operand is float64 (the
    mixed tier's f32 interior, an f32 state), the exact XLA tile everywhere
    else (a CPU, another accelerator, the full tier's f64 flows) — silently,
    that is the rule and not a fallback.

    ``"pallas"`` BY NAME is f32-only too: any f64 operand downgrades to the
    exact XLA path, and says so. Every other name passes through untouched.
    """
    if impl not in ("auto", "pallas"):
        return impl
    f64 = any(jnp.result_type(a) == jnp.float64 for a in operands)
    if impl == "auto":
        return ("pallas" if jax.default_backend() == "tpu" and not f64
                else "exact")
    if f64:
        # never silent: this runs at trace time, so it says so once per
        # build — in the log and as a ``fault`` event for `obs summarize`
        # (like `parallel.compat._fused_fallback` does for the ring)
        from ..obs import tracer as obs_tracer

        logger.warning("kernel_impl='pallas' got float64 operands: the "
                       "pallas tile is f32-only, running the 'exact' tile")
        obs_tracer.emit("fault", kind="pallas_tile_fallback",
                        reason="float64-operand")
        return "exact"
    return impl


@partial(jax.jit, static_argnames=("block_size", "source_block", "impl"))
@jax.named_scope("pair")
def stokeslet_direct(r_src, r_trg, f_src, eta, *, block_size: int = 4096,
                     source_block: int | None = None, impl: str = "exact"):
    """Singular Stokeslet sum: [n_src,3] sources, [n_trg,3] targets -> [n_trg,3].

    Self-interactions (exactly coincident points) contribute zero, matching
    `pvfmm::stokes_vel` / `src/core/kernels.cu:17-41`. Sources beyond
    ``_SRC_CHUNK_THRESHOLD`` are scanned in ``source_block`` chunks so peak
    memory stays O(block_size * source_block) at BASELINE scale (640k nodes).

    ``impl`` names the tile (`resolve_impl`: ``"auto"`` is ``"pallas"`` on
    a TPU for operands that are not float64 and ``"exact"`` everywhere else).
    ``impl="mxu"`` selects the matmul-form tile (`stokeslet_block_mxu`) that
    moves the O(N^2 * 3) contractions onto the MXU — see its numerics caveat
    and per-source-block recentering. ``impl="df"`` evaluates in double-float
    f32 arithmetic (`df_kernels.stokeslet_direct_df`, ~1e-14 per-pair
    relative) — the accuracy tier for refinement residuals on hardware whose
    native f64 is emulated. ``impl="pallas_df"`` is the same arithmetic as a
    fused Pallas VMEM tile (`pallas_df.stokeslet_pallas_df`) — Mosaic on
    real TPUs, interpret mode on CPU. The DF tiers return ``r_trg.dtype``
    like every other impl (an f32 solve must not silently promote to f64);
    callers that want the f64-valued result of f32 inputs use the DF
    kernels directly.
    """
    if impl == "pallas_df":
        from .pallas_df import stokeslet_pallas_df

        u = stokeslet_pallas_df(r_src, r_trg, f_src, eta,
                                interpret=jax.default_backend() == "cpu")
        # seam contract: preserve the caller's dtype — the DF tiles return
        # f64 unconditionally, which silently promoted an f32 solve's whole
        # Krylov pipeline to f64 (callers wanting the f64 output call the
        # DF kernels directly)
        return u.astype(r_trg.dtype)
    if impl == "df":
        from .df_kernels import stokeslet_direct_df

        u = stokeslet_direct_df(
            r_src, r_trg, f_src, eta, block_size=min(block_size, 1024),
            source_block=source_block or 4096)
        return u.astype(r_trg.dtype)  # see the pallas_df branch
    impl = resolve_impl(impl, r_trg, r_src, f_src)
    if impl == "pallas":
        # fused VMEM strip tile (`ops.pallas_kernels`); Mosaic lowering on
        # real TPUs (93-129 Gpairs/s standalone on the host clock against
        # 13.7-19.2 for the XLA path on a v5e; PERF.md section 6),
        # interpret mode on CPU (tests / fallback).
        from .pallas_kernels import stokeslet_pallas

        return stokeslet_pallas(r_src, r_trg, f_src, eta,
                                interpret=jax.default_backend() == "cpu")
    factor = 1.0 / (8.0 * math.pi)
    if impl == "mxu":
        u = _pair_sum(stokeslet_block_mxu, r_trg, (r_src, f_src),
                      block_size, source_block)
    else:
        u = _pair_sum(stokeslet_block, r_trg, (r_src, f_src), block_size,
                      source_block)
    return u * (factor / eta)


@partial(jax.jit, static_argnames=("block_size", "source_block", "impl"))
@jax.named_scope("pair")
def stresslet_direct(r_dl, r_trg, f_dl, eta, *, block_size: int = 4096,
                     source_block: int | None = None, impl: str = "exact"):
    """Singular stresslet (double-layer) sum.

    ``f_dl`` is [n_src, 3, 3] (the 9-component source S with rows indexed like the
    reference's sxx..szz, i.e. ``f_dl[s, i, j] = S_ij``); returns [n_trg, 3].
    ``impl="mxu"`` selects the matmul-form tile (`stresslet_block_mxu`,
    recentered per source block on its first point — see
    `stokeslet_block_mxu`'s caveat). ``impl="df"`` evaluates in double-float
    f32 arithmetic (`df_kernels.stresslet_direct_df`); ``impl="pallas_df"``
    is the fused Pallas tile of the same arithmetic. Both return
    ``r_trg.dtype`` (see `stokeslet_direct`).
    """
    if impl == "pallas_df":
        from .pallas_df import stresslet_pallas_df

        u = stresslet_pallas_df(r_dl, r_trg, f_dl, eta,
                                interpret=jax.default_backend() == "cpu")
        return u.astype(r_trg.dtype)  # see stokeslet_direct's pallas_df branch
    if impl == "df":
        from .df_kernels import stresslet_direct_df

        u = stresslet_direct_df(
            r_dl, r_trg, f_dl, eta, block_size=min(block_size, 1024),
            source_block=source_block or 4096)
        return u.astype(r_trg.dtype)  # see stokeslet_direct's pallas_df branch
    impl = resolve_impl(impl, r_trg, r_dl, f_dl)
    if impl == "pallas":
        # see `stokeslet_direct`'s pallas branch
        from .pallas_kernels import stresslet_pallas

        return stresslet_pallas(r_dl, r_trg, f_dl, eta,
                                interpret=jax.default_backend() == "cpu")
    factor = 1.0 / (8.0 * math.pi)
    if impl == "mxu":
        u = _pair_sum(stresslet_block_mxu, r_trg, (r_dl, f_dl),
                      block_size, source_block)
    else:
        u = _pair_sum(stresslet_block, r_trg, (r_dl, f_dl), block_size,
                      source_block)
    return u * (factor / eta)


def _reg_rinv(r2, reg, epsilon_distance, *, inclusive: bool, drop_self: bool):
    """1/r with the reference's near-field regularization, NaN-safe for gradients.

    ``inclusive`` picks the boundary test (`r <= eps` for the Oseen kernels
    `src/core/kernels.cpp:108`, strict `r < eps` for rotlet/stresslet
    `src/core/kernels.cpp:225,278`). ``drop_self`` zeroes exactly-coincident
    pairs (the Oseen/stresslet self-term skip); when False the regularized
    value is kept even at r == 0 (rotlet semantics — its contribution still
    vanishes because the displacement is zero).
    """
    eps2 = epsilon_distance * epsilon_distance
    near = (r2 <= eps2) if inclusive else (r2 < eps2)
    r2_eff = jnp.where(near, r2 + reg * reg, r2)
    if drop_self:
        nonzero = r2 > 0.0
        return jnp.where(nonzero, lax.rsqrt(jnp.where(nonzero, r2_eff, 1.0)), 0.0)
    return lax.rsqrt(jnp.maximum(r2_eff, jnp.finfo(r2.dtype).tiny))


def _regularized_frgr(r2, eta, reg, epsilon_distance):
    """fr = 1/(8 pi eta r), gr = 1/(8 pi eta r^3) with the reference's regularization.

    Exactly coincident points (r == 0) give zero; points closer than
    ``epsilon_distance`` use ``r -> sqrt(r^2 + reg^2)`` (`src/core/kernels.cpp:96-115`).
    """
    factor = 1.0 / (8.0 * math.pi * eta)
    rinv = _reg_rinv(r2, reg, epsilon_distance, inclusive=True, drop_self=True)
    fr = factor * rinv
    gr = factor * rinv * rinv * rinv
    return fr, gr


@partial(jax.jit, static_argnames=("block_size", "source_block"))
@jax.named_scope("pair")
def oseen_contract(r_src, r_trg, density, eta, reg=DEFAULT_REG,
                   epsilon_distance=DEFAULT_EPS, *, block_size: int = 4096,
                   source_block: int | None = None):
    """Regularized Oseen tensor contracted with a density: -> [n_trg, 3].

    Mirror of `kernels::oseen_tensor_contract_direct` (`src/core/kernels.cpp:85-131`).
    """
    return _pair_sum(
        lambda trg, src, dens: oseen_block(trg, src, dens, eta, reg,
                                           epsilon_distance),
        r_trg, (r_src, density), block_size, source_block)


@jax.jit
def oseen_tensor(r_src, r_trg, eta, reg=DEFAULT_REG, epsilon_distance=DEFAULT_EPS):
    """Dense regularized Oseen tensor: -> [n_trg, 3, n_src, 3].

    Mirror of `kernels::oseen_tensor_direct` (`src/core/kernels.cpp:146-195`); reshape
    to ``(3*n_trg, 3*n_src)`` for the reference's interleaved-xyz layout. Used for the
    per-fiber dense self-mobility block, so it is not target-blocked.
    """
    d = r_trg[:, None, :] - r_src[None, :, :]
    r2 = jnp.sum(d * d, axis=-1)
    fr, gr = _regularized_frgr(r2, eta, reg, epsilon_distance)
    eye = jnp.eye(3, dtype=r_src.dtype)
    G = fr[:, :, None, None] * eye[None, None] + gr[:, :, None, None] * d[:, :, :, None] * d[:, :, None, :]
    # [n_trg, n_src, 3, 3] -> [n_trg, 3, n_src, 3]
    return jnp.transpose(G, (0, 2, 1, 3))


@partial(jax.jit, static_argnames=("block_size", "source_block"))
@jax.named_scope("pair")
def rotlet(r_src, r_trg, density, eta, reg=DEFAULT_REG, epsilon_distance=DEFAULT_EPS,
           *, block_size: int = 4096, source_block: int | None = None):
    """Rotlet sum ``u = 1/(8 pi eta) sum_j (rho_j x d)/r^3`` -> [n_trg, 3].

    Mirror of `kernels::rotlet` (`src/core/kernels.cpp:206-242`). Note the reference
    regularizes by the *squared* epsilon test on r^2 and keeps the (zero) self term.
    """
    factor = 1.0 / (8.0 * math.pi * eta)

    def block(trg, src, dens):
        d = trg[:, None, :] - src[None, :, :]
        r2 = jnp.sum(d * d, axis=-1)
        rinv = _reg_rinv(r2, reg, epsilon_distance, inclusive=False, drop_self=False)
        fr = rinv * rinv * rinv
        cross = jnp.cross(dens[None, :, :], d)
        return jnp.einsum("ts,tsk->tk", fr, cross)

    return _pair_sum(block, r_trg, (r_src, density), block_size,
                     source_block) * factor


@jax.jit
def stresslet_times_normal(r, normals, eta, reg=DEFAULT_REG, epsilon_distance=DEFAULT_EPS):
    """Dense stresslet-contracted-with-normal operator -> [n, 3, n, 3].

    ``M[i, :, j, :] = -3/(4 pi) (d . n_j) / r^5 * d d^T`` with ``d = r_i - r_j`` and
    zero diagonal blocks. Mirror of `kernels::stresslet_times_normal`
    (`src/core/kernels.cpp:264-287`; note: no eta dependence). Reshape to
    ``(3n, 3n)`` for the reference layout.
    """
    factor = -3.0 / (4.0 * math.pi)
    n = r.shape[0]
    d = r[:, None, :] - r[None, :, :]
    r2 = jnp.sum(d * d, axis=-1)
    offdiag = ~jnp.eye(n, dtype=bool)
    rinv = _reg_rinv(r2, reg, epsilon_distance, inclusive=False, drop_self=False)
    rinv5 = rinv ** 5
    dn = jnp.einsum("ijk,jk->ij", d, normals)
    coeff = jnp.where(offdiag, factor * dn * rinv5, 0.0)
    M = coeff[:, :, None, None] * d[:, :, :, None] * d[:, :, None, :]
    return jnp.transpose(M, (0, 2, 1, 3))


@partial(jax.jit, static_argnames=("block_size",))
def stresslet_times_normal_blocked(r, normals, eta, reg=DEFAULT_REG,
                                   epsilon_distance=DEFAULT_EPS, *,
                                   block_size: int = 512):
    """Row-blocked `stresslet_times_normal` returning the [3n, 3n] matrix
    directly (interleaved-xyz layout, = the 4D form's `.reshape(3n, 3n)`).

    Two reasons over the dense 4D builder: peak memory is
    O(block_size * n) instead of O(n^2) intermediates, and no [.., n, 3]
    array is ever materialized — XLA's (8, 128) tiled layout pads a
    trailing dim of 3 to 128, a 42x HBM blowup that turns a 6000-node
    shell operator into a 55 GB allocation.
    """
    factor = -3.0 / (4.0 * math.pi)
    n = r.shape[0]
    nb = _block_iter(n, block_size)
    pad = nb * block_size - n
    r_pad = jnp.pad(r, ((0, pad), (0, 0)))
    row_idx = jnp.arange(nb * block_size, dtype=jnp.int32).reshape(nb,
                                                                   block_size)
    col_idx = jnp.arange(n, dtype=jnp.int32)

    def rows(args):
        trg, idx = args
        b = trg.shape[0]
        d = trg[:, None, :] - r[None, :, :]
        r2 = jnp.sum(d * d, axis=-1)
        offdiag = idx[:, None] != col_idx[None, :]
        rinv = _reg_rinv(r2, reg, epsilon_distance, inclusive=False,
                         drop_self=False)
        dn = jnp.einsum("bjk,jk->bj", d, normals)
        coeff = jnp.where(offdiag, factor * dn * rinv**5, 0.0)
        M = coeff[:, :, None, None] * d[:, :, :, None] * d[:, :, None, :]
        # [b, n, 3, 3] -> [b, 3(row), n, 3(col)] -> [3b, 3n]: the transpose
        # fuses into the block's output copy, which is 2-D (no padded-3 dims)
        return jnp.transpose(M, (0, 2, 1, 3)).reshape(3 * b, 3 * n)

    M = lax.map(rows, (r_pad.reshape(nb, block_size, 3), row_idx))
    return M.reshape(3 * nb * block_size, 3 * n)[:3 * n]


def subtract_singularity_columns(M, sing_vecs, weights):
    """Second-kind singularity subtraction on a [3n, 3n] interleaved matrix.

    ``M[3i+a, 3i+k] -= e_k[i, a] / w_i`` for the three singularity vectors
    ``sing_vecs = (ex, ey, ez)`` (each [n, 3]) — the diagonal-block
    correction of `precompute.py:113-130` / `body_spherical.cpp:168-181`,
    scattered in 2-D so no [.., n, 3]-shaped intermediate is materialized
    (XLA tile-pads a trailing dim of 3 to 128: 42x HBM).
    """
    n = weights.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    rows = 3 * idx[:, None] + jnp.arange(3, dtype=jnp.int32)[None, :]  # [n, 3]
    for k, e in enumerate(sing_vecs):
        M = M.at[rows, (3 * idx + k)[:, None]].add(-e / weights[:, None])
    return M


@partial(jax.jit, static_argnames=("block_size",))
def stresslet_times_normal_times_density(r, normals, density, eta, reg=DEFAULT_REG,
                                         epsilon_distance=DEFAULT_EPS, *, block_size: int = 4096):
    """Contracted stresslet ``S_i = -3/(4 pi) sum_{j != i} (d.rho_j)(d.n_j)/r^5 d``.

    Mirror of `kernels::stresslet_times_normal_times_density`
    (`src/core/kernels.cpp:307-334`). Sources and targets are the same point set;
    the diagonal is excluded via the r > 0 mask (the reference skips i == j).
    """
    factor = -3.0 / (4.0 * math.pi)

    def block(trg):
        d = trg[:, None, :] - r[None, :, :]
        r2 = jnp.sum(d * d, axis=-1)
        rinv = _reg_rinv(r2, reg, epsilon_distance, inclusive=False, drop_self=True)
        rinv5 = rinv ** 5
        dn = jnp.einsum("tsk,sk->ts", d, normals)
        dr_ = jnp.einsum("tsk,sk->ts", d, density)
        return jnp.einsum("ts,tsk->tk", dn * dr_ * rinv5, d)

    return _blocked_target_sum(block, r, block_size) * factor
