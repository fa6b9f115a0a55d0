"""Batched fiber state + vmapped operator assembly.

TPU-native replacement for `FiberContainerFiniteDifference`
(`/root/reference/src/core/fiber_container_finite_difference.cpp`): instead of a
`std::list<FiberFiniteDifference>` with per-fiber loops and MPI round-robin
distribution, all fibers of one resolution live in dense batched arrays
([n_fib, n_nodes, ...]) and every per-fiber operation is a `jax.vmap` of the
single-fiber functions in `fd_fiber`. The fiber batch axis is the data-parallel
axis to shard over a device mesh (the analogue of the reference's rank
decomposition, `fiber_container_finite_difference.cpp:98-121`).

An `active` mask supports dynamic instability (nucleation/catastrophe changes
the live fiber count without reshaping the arrays): inactive slots contribute
zero flow/force/error and solve an identity system. How dead slots are
neutralized (select-not-multiply, sentinels, origin-pinned positions) is
docs/audit.md "Masking discipline" — proven per program by the `mask`
audit check, not restated here.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import block_df, block_precond, kernels
from . import fd_fiber
from .fd_fiber import FiberScalars
from .matrices import FibMats, get_mats, padded_rt_mats, typed


class FiberGroup(NamedTuple):
    """State of a batch of same-resolution fibers (a pytree; [nf] leading axis)."""

    x: jnp.ndarray             # [nf, n, 3] node positions
    tension: jnp.ndarray       # [nf, n]
    length: jnp.ndarray        # [nf] target length
    length_prev: jnp.ndarray   # [nf] last accepted length
    bending_rigidity: jnp.ndarray
    radius: jnp.ndarray
    penalty: jnp.ndarray
    beta_tstep: jnp.ndarray
    force_scale: jnp.ndarray
    v_growth: jnp.ndarray
    minus_clamped: jnp.ndarray  # bool [nf]
    plus_pinned: jnp.ndarray    # bool [nf]
    binding_body: jnp.ndarray   # int32 [nf], -1 = unbound
    binding_site: jnp.ndarray   # int32 [nf]
    active: jnp.ndarray         # bool [nf]
    #: int32 [nf] original config-order rank. With multiple resolution
    #: buckets the solver layout is bucket-major; trajectory writers sort
    #: fibers back to this rank so the wire stays reference-ordered
    #: (`trajectory_reader.cpp` reads fibers in config order).
    config_rank: jnp.ndarray = None
    #: runtime node-capacity mats (`matrices.FibMatsRT`) or None. When set,
    #: the trailing node rows beyond the live count are masked inert
    #: capacity (skelly-bucket's node axis): the live resolution's
    #: differentiation matrices ride the pytree as DATA, so scenes with
    #: different live node counts share one compiled program at the same
    #: node capacity. None (the default) keeps the static per-resolution
    #: constants — bit-identical to the pre-bucket programs.
    rt_mats: object = None

    @property
    def n_fibers(self) -> int:
        return self.x.shape[0]

    @property
    def n_nodes(self) -> int:
        return self.x.shape[1]

    @property
    def mats(self):
        if self.rt_mats is not None:
            return typed(self.rt_mats, self.x.dtype)
        # cast to the state dtype so f32 groups never promote to f64 under x64
        return typed(get_mats(self.n_nodes), self.x.dtype)

    def scalars(self) -> FiberScalars:
        return FiberScalars(self.length, self.length_prev, self.bending_rigidity,
                            self.radius, self.penalty, self.beta_tstep, self.v_growth)


class FiberDFWords(NamedTuple):
    """The dense matrices of `matvec` / `apply_fiber_force` as (hi, lo)
    float32 word pairs (`ops.block_df.split_words`), for the double-float
    tile: the per-fiber blocks split once a step where `update_rhs_and_bc`
    forms them, the resolution's shared matrices once a build (static
    constants split on the host at trace time; runtime node-capacity mats
    are data and split with the blocks)."""

    A_bc: tuple       # 2 x [nf, 4n, 4n -> lanes]
    force_op: tuple   # 2 x [nf, 3n, 4n -> lanes]
    P_down: tuple     # 2 x [4n-14 -> 8s, 4n -> lanes]
    D1: tuple         # 2 x [n, n -> lanes]


class FiberCaches(NamedTuple):
    """Per-step derived quantities (`update_cache_variables` + BC application)."""

    xs: jnp.ndarray         # [nf, n, 3]
    xss: jnp.ndarray
    xsss: jnp.ndarray
    xssss: jnp.ndarray
    #: [nf, 3n, 3n] dense self-mobility (interleaved-xyz 2-D layout: a
    #: [.., n, 3]-shaped leaf would be tile-padded 3 -> 128 by XLA, a 42x
    #: HBM blowup at large fiber counts)
    stokeslet: jnp.ndarray
    force_op: jnp.ndarray   # [nf, 3n, 4n]
    A_bc: jnp.ndarray       # [nf, 4n, 4n] (BC-applied)
    RHS: jnp.ndarray        # [nf, 4n] (BC-applied)
    #: the block preconditioner of A_bc, held one of two ways by tier
    #: (`ops.block_precond`): batched LU factors in the state's dtype (full
    #: tier), or the [nf, 4n, 4n] inverse formed from lower-precision factors
    #: once a step, pivots folded in (mixed tier) — never both; all three
    #: are None until `update_rhs_and_bc`
    lu: jnp.ndarray | None
    piv: jnp.ndarray | None
    inv: jnp.ndarray | None
    #: double-float words of the dense matrices, where the lo operator of
    #: the mixed tier multiplies through the tile (`System._fiber_ops_for`);
    #: None wherever the float64 ``dot`` serves
    df: FiberDFWords | None = None


def make_group(x, lengths, bending_rigidity, radius, *, eta=None,
               penalty=fd_fiber.DEFAULT_PENALTY, beta_tstep=fd_fiber.DEFAULT_BETA_TSTEP,
               force_scale=0.0, v_growth=0.0, minus_clamped=False,
               binding_body=None, binding_site=None, config_rank=None,
               dtype=jnp.float64) -> FiberGroup:
    """Build a FiberGroup from [nf, n, 3] positions and broadcastable per-fiber params."""
    x = jnp.asarray(x, dtype=dtype)
    nf, n = x.shape[0], x.shape[1]
    get_mats(n)  # validate resolution

    def vec(v, d=dtype):
        return jnp.broadcast_to(jnp.asarray(v, dtype=d), (nf,))

    return FiberGroup(
        x=x,
        tension=jnp.zeros((nf, n), dtype=dtype),
        length=vec(lengths), length_prev=vec(lengths),
        bending_rigidity=vec(bending_rigidity), radius=vec(radius),
        penalty=vec(penalty), beta_tstep=vec(beta_tstep),
        force_scale=vec(force_scale), v_growth=vec(v_growth),
        minus_clamped=vec(minus_clamped, jnp.bool_),
        plus_pinned=jnp.zeros(nf, dtype=jnp.bool_),
        binding_body=vec(-1 if binding_body is None else binding_body, jnp.int32),
        binding_site=vec(-1 if binding_site is None else binding_site, jnp.int32),
        active=jnp.ones(nf, dtype=jnp.bool_),
        config_rank=(jnp.arange(nf, dtype=jnp.int32) if config_rank is None
                     else jnp.asarray(config_rank, dtype=jnp.int32)),
    )


def as_buckets(fibers) -> tuple:
    """Normalize a fibers field (None | FiberGroup | iterable of groups) to
    a tuple of resolution buckets. `FiberGroup` is itself a NamedTuple, so
    the single-group test must precede any generic tuple handling."""
    if fibers is None:
        return ()
    if isinstance(fibers, FiberGroup):
        return (fibers,)
    return tuple(fibers)


def node_positions(group: FiberGroup) -> jnp.ndarray:
    """[nf * n, 3] flattened node positions (`get_local_node_positions`)."""
    return group.x.reshape(-1, 3)


def live_node_count(group: FiberGroup) -> int:
    """Host-side live node count per fiber (== n_nodes without node padding)."""
    if group.rt_mats is None:
        return group.n_nodes
    return int(np.asarray(group.rt_mats.node_mask).sum())


def node_mask_np(group: FiberGroup) -> np.ndarray:
    """Host-side [n] bool node mask (all-True without node padding)."""
    if group.rt_mats is None:
        return np.ones(group.n_nodes, dtype=bool)
    return np.asarray(group.rt_mats.node_mask)


def strip_node_padding(group: FiberGroup) -> FiberGroup:
    """Group with masked padding node rows removed (live prefix only) and
    runtime mats dropped — the WIRE view: trajectory frames carry live
    nodes only, exactly like they carry active fibers only, so a padded
    run's output is byte-identical to an unpadded run's."""
    if group.rt_mats is None:
        return group
    nl = live_node_count(group)
    return group._replace(x=group.x[:, :nl], tension=group.tension[:, :nl],
                          rt_mats=None)


def node_active_flat(group: FiberGroup) -> jnp.ndarray:
    """Traced [nf * n] bool: node row is live AND its fiber is active —
    the per-node generalization of the `active` mask (masked-node
    discipline; consumed by `_spread_inactive` and the fast planners)."""
    act = jnp.repeat(group.active, group.n_nodes)
    if group.rt_mats is not None:
        act = act & jnp.tile(group.rt_mats.node_mask, group.n_fibers)
    return act


@jax.named_scope("fiber")
def update_cache(group: FiberGroup, dt, eta) -> FiberCaches:
    """Derivatives, self-mobility, pre-BC operator, force operator (vmapped).

    Mirror of `update_cache_variables` (`fiber_container_finite_difference.cpp:147-157`)
    minus the BC/RHS stage, which needs the explicit flow field (see
    `update_rhs_and_bc`).
    """
    mats = group.mats
    sc = group.scalars()

    xs, xss, xsss, xssss = jax.vmap(
        lambda x, lp: fd_fiber.derivatives(x, lp, mats))(group.x, group.length_prev)

    n3 = 3 * group.n_nodes
    stokeslet = jax.vmap(
        lambda x: kernels.oseen_tensor(x, x, eta).reshape(n3, n3))(group.x)
    force_op = jax.vmap(
        lambda a, b, s: fd_fiber.force_operator(a, b, eta, s, mats))(xs, xss, sc)

    zeros44 = jnp.zeros((group.n_fibers, 4 * group.n_nodes, 4 * group.n_nodes), dtype=group.x.dtype)
    zeros4 = jnp.zeros((group.n_fibers, 4 * group.n_nodes), dtype=group.x.dtype)
    return FiberCaches(xs=xs, xss=xss, xsss=xsss, xssss=xssss, stokeslet=stokeslet,
                       force_op=force_op, A_bc=zeros44, RHS=zeros4,
                       lu=None, piv=None, inv=None)


@jax.named_scope("fiber")
def update_rhs_and_bc(group: FiberGroup, caches: FiberCaches, dt, eta,
                      v_on_fibers, f_total, f_ext,
                      precond_dtype=None, df_words: bool = False) -> FiberCaches:
    """Assemble BC-applied A/RHS and the batched block preconditioner.

    Mirrors the prep sequence of `System::prep_state_for_solver`
    (`system.cpp:448-453`): RHS uses the total force (motor + external), the BC
    rows use only the external force. ``precond_dtype`` factors A_bc in a
    lower precision (f32 for TPU, whose LuDecomposition is f32-only) and
    stores each block's inverse, formed here once a step, in the factors'
    place; None keeps the LU factors in the state dtype
    (`ops.block_precond`). A/RHS stay in the state dtype either way.
    ``df_words`` also leaves the dense matrices as double-float words
    (`FiberDFWords`) for callers that pass ``df=True`` to `matvec` /
    `apply_fiber_force`; the float64 blocks stay for everyone else.
    """
    mats = group.mats
    sc = group.scalars()

    def one(x, xs, xss, xsss, s, mc, pp, v, ft, fe):
        A = fd_fiber.build_A(xs, xss, xsss, dt, eta, s, mats)
        RHS = fd_fiber.build_RHS(x, xs, xss, dt, eta, s, mats, flow=v, f_external=ft)
        A_bc, RHS_bc = fd_fiber.apply_bc_rectangular(
            A, RHS, x, xs, xss, dt, eta, s, mats, mc, pp, v_on_fiber=v, f_on_fiber=fe)
        # inactive slots solve the identity so the LU stays well-posed
        # (and, where the inverse is stored, invert to it exactly)
        eye = jnp.eye(A_bc.shape[0], dtype=A_bc.dtype)
        return A_bc, RHS_bc, eye

    A_bc, RHS_bc, eye = jax.vmap(one)(
        group.x, caches.xs, caches.xss, caches.xsss, sc,
        group.minus_clamped, group.plus_pinned, v_on_fibers, f_total, f_ext)
    act = group.active[:, None, None]
    A_bc = jnp.where(act, A_bc, eye)
    RHS_bc = jnp.where(group.active[:, None], RHS_bc, 0.0)

    lu, piv, inv = block_precond.factor(A_bc, precond_dtype)
    df = None
    if df_words:
        split = block_df.split_words
        df = FiberDFWords(A_bc=split(A_bc), force_op=split(caches.force_op),
                          P_down=split(mats.P_down), D1=split(mats.D1))
    return caches._replace(A_bc=A_bc, RHS=RHS_bc, lu=lu, piv=piv, inv=inv,
                           df=df)


def weighted_forces(group: FiberGroup, forces) -> jnp.ndarray:
    """Quadrature-weighted node forces for the all-to-all flow: 0.5 * L * w0 * f.

    (`fiber_container_finite_difference.cpp:185-192`); inactive fibers weigh zero.
    """
    w0 = jnp.asarray(group.mats.weights0, dtype=group.x.dtype)
    w = 0.5 * group.length[:, None] * w0[None, :]
    # select AFTER the product: zeroing only the weight would leave
    # 0 * inf = NaN if an inactive slot's force bits were nonfinite
    # (docs/audit.md "Masking discipline")
    return jnp.where(group.active[:, None, None], w[:, :, None] * forces,
                     0.0)


def flow(group: FiberGroup, caches: FiberCaches, r_trg, forces, eta,
         subtract_self: bool = True, evaluator: str = "direct",
         mesh=None, impl: str = "exact", ewald_plan=None,
         ewald_anchors=None, pair=None, pair_anchors=None) -> jnp.ndarray:
    """Velocity at targets from all fiber nodes (`flow`, `:172-214`).

    ``forces`` is [nf, n, 3]; when ``subtract_self`` the first nf*n targets are
    assumed to be the fiber nodes themselves and each fiber's dense
    self-interaction is subtracted (it is handled by the SBT mobility instead).
    Evaluator selection rides a `ops.evaluator.PairEvaluator` spec
    (``pair`` + traced ``pair_anchors``) — the reference's pair_evaluator
    seam (`fiber_container_base.cpp:20-33`); a spec carrying a
    `ops.treecode.TreePlan` sums through the barycentric treecode. The
    legacy loose kwargs remain for direct callers of the older paths only:
    ``evaluator="ring"`` (with a mesh) rotates source blocks around the ICI
    ring instead of the GSPMD all-gather, ``evaluator="ewald"`` (with an
    `ops.ewald.EwaldPlan`) sums on the spectral grid; the treecode has no
    loose spelling — it is reachable only via the spec.
    """
    return flow_multi((group,), (caches,), r_trg, (forces,), eta,
                      subtract_self=subtract_self, evaluator=evaluator,
                      mesh=mesh, impl=impl, ewald_plan=ewald_plan,
                      ewald_anchors=ewald_anchors, pair=pair,
                      pair_anchors=pair_anchors)


def _spread_inactive(buckets, pos, fills):
    """Replace inactive slots' (replicated) node rows with the planner's
    spread fill sequence: inactive slots replicate slot 0 (`grow_capacity`),
    which would pile their nodes into one cell/leaf and blow up the fast
    plans' static bucket capacity; their weighted forces are zero, so only
    occupancy changes. Indexed by compacted rank among the inactive slots
    so the runtime fill set is exactly the first-n_fill sequence prefix the
    planner counted occupancy for — raw slot indices would select an
    arbitrary subsequence whose phases can locally align and overflow the
    planned capacity (silent point eviction). Padded node rows of ACTIVE
    fibers (skelly-bucket's node axis) are fill slots too — same zero
    weighted force, same occupancy-only role."""
    act = jnp.concatenate([node_active_flat(g) for g in buckets])
    rank = jnp.clip(jnp.cumsum(~act) - 1, 0, None)
    return jnp.where(act[:, None], pos, fills[rank])


def flow_multi(buckets, caches_list, r_trg, forces_list, eta,
               subtract_self: bool = True, evaluator: str = "direct",
               mesh=None, impl: str = "exact", ewald_plan=None,
               ewald_anchors=None, pair=None,
               pair_anchors=None) -> jnp.ndarray:
    """`flow` over a tuple of resolution buckets in ONE evaluator pass.

    The TPU answer to the reference's mixed-resolution `std::list` container
    (`fiber_container_finite_difference.cpp:519-562`): each resolution is a
    dense vmapped bucket, and the all-to-all flow concatenates every
    bucket's sources so the pair evaluator (dense tile, ICI ring, Ewald
    grid, or treecode) runs once over the union instead of once per
    bucket. When ``subtract_self`` the leading targets must be the
    concatenated fiber nodes in bucket order; each bucket's dense
    self-interaction is subtracted at its own slice.

    ``pair`` (a `ops.evaluator.PairEvaluator`) supersedes the loose
    ``evaluator``/``impl``/``ewald_plan`` kwargs, which remain for direct
    callers; when ``pair_anchors`` is None the plan's own stored anchors
    are materialized (so pass anchors explicitly for stripped plans).
    """
    from ..ops.evaluator import resolve

    evaluator, impl, ewald_plan, ewald_anchors, pair_anchors = resolve(
        pair, pair_anchors, r_trg.dtype, evaluator, impl, ewald_plan,
        ewald_anchors)
    tree_plan = pair.plan if (pair is not None
                              and pair.evaluator == "tree") else None
    spectral_plan = pair.plan if (pair is not None
                                  and pair.evaluator == "spectral") else None
    pos = jnp.concatenate([node_positions(g) for g in buckets], axis=0)
    wf = jnp.concatenate([weighted_forces(g, f).reshape(-1, 3)
                          for g, f in zip(buckets, forces_list)], axis=0)
    # dead slots' weighted forces are exact zeros, so their positions are
    # occupancy-only: pin them to the origin so no garbage coordinate ever
    # enters a pair kernel (a nonfinite stale position would turn the
    # zero-force product into NaN — docs/audit.md "Masking discipline").
    # The fast planners re-fill them with spread anchors (`_spread_inactive`)
    act = jnp.concatenate([node_active_flat(g) for g in buckets])
    pos = jnp.where(act[:, None], pos, 0.0)
    n_fib_nodes = pos.shape[0]
    if subtract_self:
        # keep the leading self targets consistent with the pinned sources
        r_trg = jnp.concatenate([pos, r_trg[n_fib_nodes:]], axis=0)
    if evaluator == "ring" and mesh is not None:
        if impl in ("df", "pallas_df"):
            # the DF ring entry point serves both spellings: "df" runs the
            # XLA blocks, "pallas_df" the fused Pallas DF tile per chip.
            # Cast back to the target dtype like the direct seam — the f64
            # ring output would otherwise promote an f32 solve's pipeline
            from ..parallel.ring import ring_stokeslet_df

            vel = ring_stokeslet_df(pos, r_trg, wf, eta, mesh=mesh,
                                    impl=impl).astype(r_trg.dtype)
        else:
            from ..parallel.ring import ring_stokeslet

            vel = ring_stokeslet(pos, r_trg, wf, eta, mesh=mesh, impl=impl)
    elif evaluator == "ewald" and ewald_plan is not None:
        from ..ops import ewald as ew

        if ewald_anchors is None:
            ewald_anchors = ew.plan_anchors(ewald_plan, r_trg.dtype)
            ewald_plan = ew.strip_anchors(ewald_plan)
        # the plan reserved fill room for inactive slots
        # (`plan_ewald(n_fill=...)`; see `_spread_inactive`)
        fills = ew.fill_positions(ewald_plan, ewald_anchors[1],
                                  n_fib_nodes, pos.dtype)
        pos = _spread_inactive(buckets, pos, fills)
        n_self = n_fib_nodes if subtract_self else 0
        if n_self:
            # the leading targets are the fiber nodes: keep them consistent
            # with the (spread) source positions so self pairs stay exact
            r_trg = jnp.concatenate([pos, r_trg[n_self:]], axis=0)
        vel = ew._stokeslet_ewald_impl(ewald_plan, ewald_anchors, pos, r_trg,
                                       wf, n_self)
        # the kernel scales as 1/eta and the plan baked plan.eta in; honor
        # this call's eta like the direct/ring branches do
        vel = vel * (ewald_plan.eta / eta)
    elif evaluator == "spectral" and spectral_plan is not None:
        from ..ops import spectral as spec

        # same fill discipline as the ewald branch: the plan reserved
        # occupancy room for inactive slots (`plan_spectral(n_fill=...)`)
        fills = spec.fill_positions(spectral_plan, pair_anchors[1],
                                    n_fib_nodes, pos.dtype)
        pos = _spread_inactive(buckets, pos, fills)
        n_self = n_fib_nodes if subtract_self else 0
        if n_self:
            r_trg = jnp.concatenate([pos, r_trg[n_self:]], axis=0)
        vel = spec._stokeslet_spectral_impl(spectral_plan, pair_anchors, pos,
                                            r_trg, wf, n_self)
        # the kernel scales as 1/eta and the plan baked plan.eta in
        vel = vel * (spectral_plan.eta / eta)
    elif evaluator == "tree" and tree_plan is not None:
        from ..ops import treecode as tcode

        fills = tcode.fill_positions(tree_plan, pair_anchors[0],
                                     n_fib_nodes, pos.dtype)
        pos = _spread_inactive(buckets, pos, fills)
        if subtract_self:
            # keep the leading (fiber-node) targets consistent with the
            # spread source positions so self pairs stay exactly coincident
            # (the treecode's near tile drops them like the dense kernel)
            r_trg = jnp.concatenate([pos, r_trg[n_fib_nodes:]], axis=0)
        if tree_plan.depth == 0:
            vel = kernels.stokeslet_direct(pos, r_trg, wf, eta, impl=impl)
        else:
            vel = tcode._stokeslet_tree_impl(tree_plan, pair_anchors, pos,
                                             r_trg, wf, eta)
    else:
        vel = kernels.stokeslet_direct(pos, r_trg, wf, eta, impl=impl)
    if subtract_self:
        off = 0
        for g, caches in zip(buckets, caches_list):
            nfn = g.n_fibers * g.n_nodes
            self_vel = jnp.einsum("fij,fj->fi", caches.stokeslet,
                                  wf[off:off + nfn].reshape(g.n_fibers, -1))
            vel = vel.at[off:off + nfn].add(-self_vel.reshape(-1, 3))
            off += nfn
    return vel


def flow_multi_local(buckets, caches_list, forces_list, r_loc, r_rep, eta, *,
                     axis_name, n_dev: int, subtract_self: bool = True,
                     impl: str = "exact", pair=None, pair_anchors=None):
    """`flow_multi` for callers ALREADY INSIDE a `shard_map` over the fiber
    axis (the SPMD implicit step, `parallel.spmd`).

    ``buckets``/``caches_list``/``forces_list`` are this shard's resident
    fiber blocks. Two target classes with different evaluation strategies:

    * ``r_loc`` — targets resident on this shard (its own fiber nodes, its
      shell row block). Source blocks rotate the ring (`lax.ppermute`), so
      every shard's resident targets see all sources: n_dev-1 nearest-
      neighbor hops, O(N/D) peak memory, identical to `parallel.ring`.
    * ``r_rep`` — targets REPLICATED across shards (body nodes, a
      replicated shell). Evaluated as one local source block partial whose
      `psum` is the caller's job — the replication discipline
      (docs/parallel.md "Replication discipline", statically enforced by
      the `replication` audit check): a ring accumulation onto replicated
      rows is the deadlock anti-pattern the analyzer flags as
      ring-order-accumulation.

    Returns ``(v_loc, v_rep_partial)`` (``None`` for an absent class); when
    ``subtract_self`` the leading rows of ``r_loc`` must be this shard's
    concatenated fiber nodes in bucket order. DF impls ("df"/"pallas_df")
    accumulate in float64 and cast back to the target dtype at the seam,
    like `flow_multi`'s ring branch.

    A ``pair`` spec with ``evaluator="tree"`` composes the treecode with
    the SPMD decomposition: every shard buckets the all-gathered source
    set into the SHARED global `TreePlan` (the plan covers the whole
    cloud, a subset just lowers occupancy) and evaluates its own resident
    targets — one all-gather of [N, 3] sources replaces the n_dev-1 ring
    hops of the same total bytes, and per-shard compute drops from
    O(N^2/D) dense tiles to the treecode's near+cluster work. Replicated
    targets keep the partial-sum contract (each shard sums its LOCAL
    sources through the tree; the caller's psum keeps replicated rows
    bitwise identical across shards, same as the ring path).
    """
    from ..parallel.ring import ring_flow_local

    pos = jnp.concatenate([node_positions(g) for g in buckets], axis=0)
    wf = jnp.concatenate([weighted_forces(g, f).reshape(-1, 3)
                          for g, f in zip(buckets, forces_list)], axis=0)

    if (pair is not None and pair.evaluator == "tree"
            and pair.plan is not None and pair.plan.depth > 0):
        from jax import lax

        from ..ops import treecode as tcode

        pos_all = lax.all_gather(pos, axis_name, axis=0, tiled=True)
        wf_all = lax.all_gather(wf, axis_name, axis=0, tiled=True)
        v_loc = tcode._stokeslet_tree_impl(pair.plan, pair_anchors, pos_all,
                                           r_loc, wf_all, eta)
        v_rep = (tcode._stokeslet_tree_impl(pair.plan, pair_anchors, pos,
                                            r_rep, wf, eta)
                 if r_rep is not None else None)
    else:
        v_loc = ring_flow_local("stokeslet", impl, r_loc, pos, wf, eta,
                                axis_name=axis_name, n_dev=n_dev, ring=True)
        v_rep = (ring_flow_local("stokeslet", impl, r_rep, pos, wf, eta,
                                 axis_name=axis_name, n_dev=n_dev,
                                 ring=False)
                 if r_rep is not None else None)

    if subtract_self:
        off = 0
        for g, caches in zip(buckets, caches_list):
            nfn = g.n_fibers * g.n_nodes
            self_vel = jnp.einsum("fij,fj->fi", caches.stokeslet,
                                  wf[off:off + nfn].reshape(g.n_fibers, -1))
            v_loc = v_loc.at[off:off + nfn].add(
                -self_vel.reshape(-1, 3).astype(v_loc.dtype))
            off += nfn
    return v_loc, v_rep


def _df_product(words, x, n_rows):
    """``words`` (a `FiberDFWords` field) times the rows of ``x`` through
    the double-float tile: compiled for a TPU, interpreted on a CPU (the
    tests' oracle runs), as every Pallas tile of the package."""
    return block_df.block_matvec_df(
        words, x, n_rows=n_rows, interpret=jax.default_backend() == "cpu")


def apply_fiber_force(group: FiberGroup, caches: FiberCaches, x_all,
                      df: bool = False) -> jnp.ndarray:
    """Solution -> force density on nodes, [nf, n, 3] (`apply_fiber_force`, `:272-287`).

    ``df=True`` (the lo operator of the mixed tier) multiplies through the
    double-float words where ``caches`` holds them; the result is float64
    either way."""
    n = group.n_nodes
    with jax.named_scope("fiber"), jax.named_scope("force"):
        if df and caches.df is not None:
            f = _df_product(caches.df.force_op, x_all, 3 * n)
        else:
            f = jnp.einsum("fij,fj->fi", caches.force_op, x_all)  # [nf, 3n]
        return jnp.stack([f[:, :n], f[:, n:2 * n], f[:, 2 * n:]], axis=-1)


def matvec(group: FiberGroup, caches: FiberCaches, x_all, v_fib, v_boundary,
           df: bool = False) -> jnp.ndarray:
    """Block-diagonal fiber matvec [nf, 4n] (`matvec`, `:216-234`).

    ``df=True`` (the lo operator of the mixed tier) runs the three dense
    products of `fd_fiber.matvec` through the double-float words where
    ``caches`` holds them (`_matvec_df`); without them, and for every other
    caller, the float64 ``dot``."""
    with jax.named_scope("fiber"), jax.named_scope("matvec"):
        if df and caches.df is not None:
            res = _matvec_df(group, caches, x_all, v_fib, v_boundary)
        else:
            mats = group.mats
            sc = group.scalars()
            res = jax.vmap(
                lambda A, xv, v, vb, xs, s, pp: fd_fiber.matvec(A, xv, v, vb, xs, s, mats, pp)
            )(caches.A_bc, x_all, v_fib, v_boundary, caches.xs, sc, group.plus_pinned)
        return jnp.where(group.active[:, None], res, x_all)


def _matvec_df(group: FiberGroup, caches: FiberCaches, x_all, v, v_boundary):
    """`fd_fiber.matvec` over the whole batch with its dense products
    (``D1 @ sum(xs * v)``, ``P_down @ vT``, ``A_bc @ x``) in double-float
    words: the same terms in the same order, everything between the
    products in the vectors' float64. ``D1`` is applied unscaled and the
    per-fiber ``2 / length_prev`` after it."""
    words = caches.df
    mats = group.mats
    n = group.n_nodes
    bc_start = 4 * n - 14
    xs = caches.xs
    nm = getattr(mats, "node_mask", None)
    if nm is not None:
        v = jnp.where(nm[None, :, None], v, 0.0)
    e_last = getattr(mats, "e_last", None)
    if e_last is None:
        def last(a):
            return a[:, -1]
    else:
        def last(a):
            # the last LIVE node, as a masked sum: no float64 ``dot``
            return jnp.sum(a * e_last.astype(a.dtype)[None, :, None], axis=1)

    vT_tension = (2.0 / group.length_prev)[:, None] * _df_product(
        words.D1, jnp.sum(xs * v, axis=2), n)
    vT = jnp.concatenate([v[..., 0], v[..., 1], v[..., 2], vT_tension],
                         axis=1)
    vT_in = jnp.pad(_df_product(words.P_down, vT, bc_start),
                    ((0, 0), (0, 14)))

    res = _df_product(words.A_bc, x_all, 4 * n) - vT_in
    res = res.at[:, bc_start + 3].add(jnp.sum(v[:, 0] * xs[:, 0], axis=-1))
    res = res.at[:, bc_start + 10].add(
        jnp.where(group.plus_pinned,
                  jnp.sum(last(v) * last(xs), axis=-1), 0.0))
    if v_boundary is not None:
        res = res.at[:, bc_start:bc_start + 7].add(v_boundary)
    return res


@jax.named_scope("fiber")
def apply_preconditioner(group: FiberGroup, caches: FiberCaches, x_all) -> jnp.ndarray:
    """Every fiber's block A_bc^-1 applied, [nf, 4n] (`apply_preconditioner`,
    `:331-339`): one batched matmul with the stored inverse in the mixed
    tier, batched LU solves in the full tier (`ops.block_precond`).

    Works in the stored block's (possibly lower) precision and casts back —
    a preconditioner only needs to approximate A^-1.
    """
    return block_precond.solve(caches, x_all)


def step(group: FiberGroup, fiber_sol) -> FiberGroup:
    """Advance positions/tension from the solution [nf, 4n] (`step`, `:292-302`)."""
    n = group.n_nodes
    x_new = jnp.stack([fiber_sol[:, :n], fiber_sol[:, n:2 * n], fiber_sol[:, 2 * n:3 * n]], axis=-1)
    t_new = fiber_sol[:, 3 * n:]
    x_new = jnp.where(group.active[:, None, None], x_new, group.x)
    t_new = jnp.where(group.active[:, None], t_new, group.tension)
    if group.rt_mats is not None:
        # padded node entries solve the identity to exact zero; keep their
        # far-point placeholder positions instead (distinct coordinates are
        # what keeps the dense kernels and self-mobility finite)
        nm = group.rt_mats.node_mask
        x_new = jnp.where(nm[None, :, None], x_new, group.x)
        t_new = jnp.where(nm[None, :], t_new, group.tension)
    return group._replace(x=x_new, tension=t_new, length_prev=group.length)


def generate_constant_force(group: FiberGroup, caches: FiberCaches) -> jnp.ndarray:
    """Implicit motor force f = force_scale * xs [nf, n, 3] (`generate_constant_force`)."""
    return group.force_scale[:, None, None] * caches.xs


def fiber_errors(group: FiberGroup) -> jnp.ndarray:
    """[nf] per-fiber inextensibility violation, inactive slots masked to 0
    — the flight recorder's per-fiber strain diagnostic (obs.flight);
    `fiber_error` is its max."""
    mats = group.mats
    errs = jax.vmap(lambda x, L: fd_fiber.fiber_error(x, L, mats))(group.x, group.length)
    return jnp.where(group.active, errs, 0.0)


def fiber_error(group: FiberGroup) -> jnp.ndarray:
    """Max inextensibility violation over active fibers (`fiber_error_local`)."""
    # -inf sentinel so inactive slots can never win the max; the outer
    # maximum(0, ·) keeps the all-inactive value finite and is otherwise
    # a no-op (errors are nonnegative) — docs/audit.md "Masking discipline"
    errs = jax.vmap(lambda x, L: fd_fiber.fiber_error(x, L, group.mats))(
        group.x, group.length)
    return jnp.maximum(0.0, jnp.max(jnp.where(group.active, errs, -jnp.inf)))


def solution_size(group: FiberGroup) -> int:
    return group.n_fibers * 4 * group.n_nodes


def sort_fibers_morton(group: FiberGroup) -> FiberGroup:
    """Reorder fibers by the Morton (Z-order) code of their centroids.

    Makes consecutive fibers spatially local, so the source *chunks* of the
    chunked pairwise kernels (`ops.kernels._pair_sum`) and the rotating ring
    blocks are compact in space — which is what keeps the MXU matmul-form
    tiles accurate in f32 (their per-block recentering bound scales with the
    block's spatial extent; see `stokeslet_block_mxu`). Safe to apply at any
    time: all per-fiber state rides along, and nothing indexes fibers by
    position (body bindings point at bodies, not fibers). Host-side; call at
    setup or after nucleation bursts, not per step.
    """
    nf = group.n_fibers
    if nf <= 1:
        return group
    # f64 centroids regardless of group dtype: a float32 span floored with a
    # denormal underflows to 0 and NaN-poisons the Morton codes; node-padded
    # groups centroid over LIVE nodes only (far-point pad rows would snap
    # every centroid to one octant)
    nm = node_mask_np(group)
    cent = np.asarray(
        jnp.mean(group.x[:, nm, :], axis=1), dtype=np.float64)  # [nf, 3]
    lo = cent.min(axis=0)
    span = np.maximum(cent.max(axis=0) - lo, np.finfo(np.float64).tiny)
    q = np.clip((cent - lo) / span * 1023.0, 0, 1023).astype(np.uint64)

    def spread(v):
        # interleave 10 bits with two zero bits (standard Morton dilation)
        v = (v | (v << 16)) & np.uint64(0x030000FF)
        v = (v | (v << 8)) & np.uint64(0x0300F00F)
        v = (v | (v << 4)) & np.uint64(0x030C30C3)
        v = (v | (v << 2)) & np.uint64(0x09249249)
        return v

    code = spread(q[:, 0]) | (spread(q[:, 1]) << np.uint64(1)) \
        | (spread(q[:, 2]) << np.uint64(2))
    order = np.argsort(code, kind="stable")

    def permute(name, leaf):
        if name == "rt_mats" or leaf is None:
            return leaf  # group-level runtime mats carry no fiber axis
        leaf = np.asarray(leaf)
        if leaf.ndim >= 1 and leaf.shape[0] == nf:
            return leaf[order]
        return leaf

    return type(group)(*[permute(n, l)
                         for n, l in zip(group._fields, group)])


def grow_capacity(group: FiberGroup, new_cap: int,
                  node_multiple: int = 1) -> FiberGroup:
    """Pad every [nf]-leading leaf to ``new_cap`` slots (padding inactive).

    Used by dynamic instability (geometric capacity growth) and by the
    builder to round the fiber batch up to a mesh-divisible count for the
    ring evaluator. ``node_multiple`` (the mesh size) rounds ``new_cap``
    further up until the total node count divides it — every grower must
    preserve the ring divisibility invariant or a long run dies mid-flight
    in `System._fiber_flow`. Padded slots replicate slot 0 instead of
    zero-filling: a zero-length/zero-x fiber makes the cache derivatives
    inf/NaN, and 0-weight * NaN leaks NaN through the stokeslet sum even for
    inactive slots. Padded slots are inert: inactive and unbound.
    """
    if node_multiple > 1:
        while (new_cap * group.n_nodes) % node_multiple != 0:
            new_cap += 1
    nf = group.n_fibers
    pad = new_cap - nf
    if pad <= 0:
        return group

    def pad_leaf(name, leaf):
        if name == "rt_mats" or leaf is None:
            return leaf  # group-level runtime mats carry no fiber axis
        leaf = np.asarray(leaf)
        if leaf.ndim >= 1 and leaf.shape[0] == nf:
            if nf == 0:
                fill = np.zeros((pad,) + leaf.shape[1:], dtype=leaf.dtype)
            else:
                fill = np.repeat(leaf[:1], pad, axis=0)
            return np.concatenate([leaf, fill], axis=0)
        return leaf

    padded = type(group)(*[pad_leaf(n, l)
                           for n, l in zip(group._fields, group)])
    active = np.asarray(padded.active)
    active[nf:] = False
    binding_body = np.asarray(padded.binding_body)
    binding_body[nf:] = -1
    return padded._replace(active=active, binding_body=binding_body)


def grow_node_capacity(group: FiberGroup, new_n: int) -> FiberGroup:
    """Pad the NODE axis to ``new_n`` rows per fiber (padding masked inert).

    `grow_capacity` extended to the second shape axis (skelly-bucket): the
    live resolution's matrices become runtime data (`matrices.FibMatsRT`)
    riding the group, padded node rows replicate the fiber's FIRST node
    (the same placeholder discipline as `grow_capacity`'s replicated slot
    0: zero quadrature weight makes them silent sources, exact-coincidence
    pairs are dropped by every kernel impl, and staying inside the live
    geometry keeps the f32 MXU tiles' recentering extent honest), and
    every operator reduces to the live fiber's math on the live block.
    ``new_n == n_nodes`` still ATTACHES runtime mats — an exact-fit scene
    must share its bucket's pytree structure, or it would compile its own
    program and defeat the bucket.
    """
    n = group.n_nodes
    n_live = live_node_count(group)
    if new_n < n:
        raise ValueError(
            f"grow_node_capacity: new_n {new_n} below current node capacity "
            f"{n} (node capacity never shrinks)")
    dtype = group.x.dtype
    rt = padded_rt_mats(n_live, new_n, dtype)
    pad = new_n - n
    if pad == 0:
        return group._replace(rt_mats=rt)
    nf = group.n_fibers

    x_np = np.asarray(group.x)
    fill = np.repeat(x_np[:, :1, :], pad, axis=1)      # replicate node 0
    x = np.concatenate([x_np, fill], axis=1)
    tension = np.concatenate(
        [np.asarray(group.tension),
         np.zeros((nf, pad), dtype=np.asarray(group.tension).dtype)], axis=1)
    return group._replace(x=jnp.asarray(x, dtype=dtype),
                          tension=jnp.asarray(tension, dtype=dtype),
                          rt_mats=rt)
