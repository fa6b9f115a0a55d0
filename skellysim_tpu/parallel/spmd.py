"""Explicitly-sharded end-to-end implicit timestep: one `shard_map` program.

The GSPMD path (`shard_state` + jit) leaves the collectives of the coupled
solve to the compiler; this module is the reference's actual distributed
design (SURVEY §2, §5.8: Scatterv'd shell rows, per-rank fiber blocks,
all-reduced dot products) written out as ONE `shard_map` program over the
fiber axis that runs the entire implicit step — prep, GMRES, preconditioner
applications, mixed-precision refinement sweeps, and the state advance —
without leaving the mesh program.

Decomposition (everything per shard, mesh size D):

* fiber buckets shard along the batch axis (nf/D whole fibers per shard):
  caches, the block preconditioner's factors or inverses
  (`ops.block_precond`), and their application never leave the owning shard
  — the preconditioner-locality analogue of the reference's round-robin
  fiber distribution;
* the shell row-shards node-aligned (N/D nodes per shard): the dense
  operators [3N/D, 3N], the density rows, and the RHS rows live distributed;
  applying the dense operator / its inverse is all-gather(density) + local
  row-block GEMV — exactly the reference's `periphery.cpp:21-47` matvec. A
  shell whose N the mesh does not divide is padded to N' = ceil(N/D) D
  nodes (`mesh.padded_nodes`): its operators come in padded [3N'/D, 3N']
  (the padded rows the identity, the padded columns zero), its O(N)
  vectors replicated at N, and the step pads them to this shard's N'/D
  nodes on entry and gathers the new density back to 3N (scope
  ``shell_pad``). A padded node is inert: masked (`node_mask`), zero
  normal, weight and density, at node 0's position (never a zero-filled
  point), so its density solves to exactly zero and no flow leaves it;
* bodies and scalars replicate (the reference's rank-0 body ownership).

Collectives are explicit and bounded (docs/parallel.md documents the full
inventory; tests/test_spmd.py pins it against the lowered HLO):

* `psum` for the GMRES reductions (injected into `solver.gmres` through
  its ``rdot`` seam — with ``Params.gmres_block_s > 1`` the s-step cycle
  batches them into two [(m+1)+s, s] Gram rounds per s iterations instead
  of 3 per iteration; docs/parallel.md) and for the partial sums onto
  REPLICATED rows (body-node velocities, link forces/torques, bundled
  into ONE tuple-psum per matvec);
* `ppermute` ring rotation of fiber/shell source blocks for all pairwise
  flows at shard-resident targets (`fibers.container.flow_multi_local`,
  `periphery.flow_local`) — including the double-float refinement tiles, so
  mixed-precision sweeps stay inside the mesh program;
* one density-sized (3N) `all_gather` per shell operator/preconditioner
  application — the Scatterv analogue, never an operand of fiber-cache size.

Replicated values are kept BITWISE identical across shards by the
replication discipline (docs/parallel.md "Replication discipline"):
replicated-inputs-only computation or psum-of-partials, never a ring
accumulation. This is no longer a prose convention — the `replication`
audit check (`audit.repflow`) statically verifies it on every registered
step_spmd program, with the replicated-output surface pinned in
`audit/contracts/step_spmd_d*.toml`.

The spectral-Ewald evaluator is not served here (its plan is built
host-side per step and is a different scaling regime); `pair_evaluator`
is ignored — the SPMD program always rings over its mesh.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..bodies import bodies as bd
from ..fibers import container as fc
from ..ops import kernels
from ..periphery import periphery as peri
from ..solver import gmres, gmres_ir
from ..system.system import (SimState, StepInfo, _cast_floats, _rewrap_bodies,
                             _rewrap_fibers, body_buckets, fiber_buckets)
from .compat import pmax
from .mesh import FIBER_AXIS, padded_nodes, shell_divides, shell_specs


class SpmdSolution(NamedTuple):
    """Structured (still-sharded) solution: per-bucket fiber blocks [nf, 4n],
    the shell density [3N], the body solution — what
    ``build_spmd_step(flat_solution=False)`` returns instead of gathering
    the flat reference-layout vector."""

    fibers: tuple
    shell: jnp.ndarray | None
    bodies: jnp.ndarray | None


def spmd_shell_mode(state: SimState, mesh: Mesh, *,
                    allow_replicated_shell: bool = False) -> str:
    """Validate a state for the SPMD step; returns the shell placement mode
    ("sharded" | "padded" | "replicated" | "none").

    The shell splits NODE-aligned so a node's three density components
    never straddle shards: "sharded" where D divides n_nodes, else
    "padded" (its operators at `padded_nodes` rows, as
    `shard_state(step="spmd")` places them), or the whole shell
    "replicated" where the caller opts in. Every fiber bucket must divide
    the mesh (`fibers.container.grow_capacity` pads a batch up).
    """
    buckets = fiber_buckets(state.fibers)
    if not buckets:
        raise ValueError(
            "the SPMD step shards the fiber batch axis; a fiberless state "
            "has nothing to distribute (use the plain solve)")
    for g in buckets:
        if g.n_fibers % mesh.size != 0:
            raise ValueError(
                f"fiber bucket of {g.n_fibers} fibers does not divide the "
                f"mesh size ({mesh.size}); round the batch up with "
                "fibers.container.grow_capacity (inactive padding fibers "
                "are free)")
    if state.shell is None:
        return "none"
    n = state.shell.n_nodes
    if shell_divides(n, mesh.size, "spmd"):
        return "sharded"
    if allow_replicated_shell:
        return "replicated"
    rows = 3 * padded_nodes(n, mesh.size)
    if state.shell.M_inv.shape[0] != rows:
        raise ValueError(
            f"shell n_nodes ({n}) is not divisible by the mesh size "
            f"({mesh.size}): the mesh step takes its operators padded to "
            f"{rows} rows, and these have {state.shell.M_inv.shape[0]}. "
            "Place the state with parallel.shard_state(state, mesh, "
            "step='spmd'), which pads them, or pass "
            "allow_replicated_shell=True to replicate the whole shell.")
    return "padded"


def _state_specs(state: SimState, shell_mode: str) -> SimState:
    """PartitionSpec pytree for a SimState under the SPMD decomposition."""
    def rep(sub):
        return (None if sub is None
                else jax.tree_util.tree_map(lambda _: P(), sub))

    buckets = fiber_buckets(state.fibers)
    placed = tuple(jax.tree_util.tree_map(lambda _: P(FIBER_AXIS), g)
                   for g in buckets)
    fib_spec = (placed[0] if isinstance(state.fibers, fc.FiberGroup)
                else placed)
    # the shell's leaves by `mesh.shell_specs`, the table `shard_state` and
    # the builder's loader place them by: every leaf leading-axis sharded
    # (nodes/normals [N, 3], weights [N], density [3N], the dense
    # operators' ROWS), or the whole shell replicated
    shell_spec = (None if state.shell is None else
                  shell_specs(state.shell, "spmd",
                              sharded=shell_mode in ("sharded", "padded"),
                              padded=shell_mode == "padded"))
    return SimState(time=P(), dt=P(), fibers=fib_spec,
                    points=rep(state.points), background=rep(state.background),
                    shell=shell_spec, bodies=rep(state.bodies),
                    # the flight-recorder ring replicates: every shard
                    # writes the bitwise-identical row (psum'd/pmax'd
                    # reductions — obs.flight; repflow-verified)
                    flight=rep(state.flight))


def _pad_shell_rows(shell, axis: str, n_dev: int):
    """Inside the mesh step: this shard's block of a shell whose nodes the
    mesh does not divide, its N replicated nodes padded to `padded_nodes`
    and cut to N'/D. A padded node sits at node 0's position (a finite
    target; never a zero-filled point) with a zero normal, weight and
    density and ``node_mask`` False: it solves the identity against a zero
    right-hand side and its double layer is zero."""
    n = shell.n_nodes
    per = padded_nodes(n, n_dev) // n_dev
    pad = per * n_dev - n
    live = (jnp.ones(n, dtype=bool) if shell.node_mask is None
            else shell.node_mask)

    def grown(a, fill):
        return jnp.concatenate(
            [a, jnp.broadcast_to(fill, (pad * a.shape[0] // n,)
                                 + a.shape[1:]).astype(a.dtype)])

    start = lax.axis_index(axis) * per

    def take(a, k=1):
        return lax.dynamic_slice_in_dim(a, start * k, per * k)

    return shell._replace(
        nodes=take(grown(shell.nodes, shell.nodes[:1])),
        normals=take(grown(shell.normals, 0.0)),
        weights=take(grown(shell.weights, 0.0)),
        density=take(grown(shell.density, 0.0), 3),
        node_mask=take(grown(live, False)))


def _make_rdot(axis: str, nonrep_end: int) -> Callable:
    """Reduction over the SPMD vector layout [sharded rows | replicated rows]:
    `psum` the sharded partial, add the replicated tail exactly once (it is
    bitwise identical on every shard, so no collective is needed for it)."""
    def rdot(A, w):
        # "psum-dots" device-time scope (obs/profile.py): THE solver
        # collective the s-step ladder exists to batch — metadata only
        with jax.named_scope("psum-dots"):
            part = lax.psum(A[..., :nonrep_end] @ w[:nonrep_end], axis)
            return part + A[..., nonrep_end:] @ w[nonrep_end:]
    return rdot


def build_spmd_step(system, mesh: Mesh, state: SimState, *,
                    allow_replicated_shell: bool = False,
                    flat_solution: bool = True, donate: str | bool = "auto",
                    pair=None, jit_wrapper=None):
    """Build the jitted explicitly-sharded full step for states shaped like
    ``state``. Returns ``step(state) -> (new_state, solution, info)`` with
    ``new_state`` still sharded on ``mesh``.

    ``pair`` (an anchor-stripped `ops.evaluator.PairEvaluator` carrying a
    `TreePlan`) routes the Krylov-interior fiber Stokeslet flows through
    the treecode instead of the ring (`fibers.container.flow_multi_local`'s
    tree branch: one tiled source all-gather + per-shard tree evaluation at
    resident targets). The built ``step`` then takes the plan's traced
    anchors as a second argument — `System.step_spmd` supplies both. The
    f64 refinement-residual matvec and prep flows keep the same role gating
    as the single-chip solve (dense — tree_tol must not cap the refined
    residual in mixed mode); the Gauss-Seidel shell correction stays on the
    ring path (the shell double layer is not the O(N^2) wall this evaluator
    exists to break).

    ``flat_solution=True`` assembles the reference-layout flat solution
    vector outside the mesh program (one explicit gather — skip it at scale
    with ``False``, which returns an `SpmdSolution` of sharded parts).
    ``donate="auto"`` donates the input state's buffers into the step on
    accelerator backends (XLA aliases the pass-through leaves — the dense
    shell operators above all — instead of double-buffering them); rejected
    adaptive steps must not reuse a donated input, so callers that roll
    back pass ``donate=False``.

    ``jit_wrapper`` replaces the final `jax.jit` (same kwargs) — the
    audit layer's retrace-probe seam (`testing.trace_counting_jit`).
    """
    p = system.params
    if (p.guard_dt_halvings or p.guard_block_fallback
            or p.guard_f64_fallback):
        # once per BUILD (System.step_spmd caches the program): the mesh
        # program threads the HEALTH WORD but not the escalation ladder —
        # silent inertness would surprise a user who armed guard_*
        # expecting device-side retries. The replication analyzer
        # (audit.repflow) proves the guard-armed build AND the ladder's
        # retry pattern replication-safe (tests/test_guard.py), so what
        # remains for in-mesh escalation is wiring and compile cost, not a
        # correctness unknown — docs/robustness.md "In-mesh escalation".
        import warnings

        warnings.warn("Params.guard_* escalation is not applied on the "
                      "step_spmd path: the mesh program reports health "
                      "verdicts but does not retry; escalation runs on "
                      "the single-chip and ensemble paths only")
    axis = FIBER_AXIS
    n_dev = mesh.size
    shell_mode = spmd_shell_mode(
        state, mesh, allow_replicated_shell=allow_replicated_shell)
    # a padded shell runs the sharded shell's program on its padded rows:
    # `local_step` pads it on entry and gathers the density back
    padded_shell = shell_mode == "padded"
    sharded_shell = shell_mode in ("sharded", "padded")
    has_shell = shell_mode != "none"
    has_bodies = state.bodies is not None

    precision = system._precision_for(state)
    is_f64 = state.time.dtype == jnp.float64
    # mixed f64: prep flows AND the refinement-residual matvec both run
    # through the refinement tile (System._prep / _solve_impl semantics)
    refine = precision == "mixed" and is_f64
    prep_impl = hi_impl = (
        system._refine_impl if refine
        else kernels.resolve_impl(p.kernel_impl, state.time.dtype))
    if precision == "mixed":
        system._announce_refine_tile(hi_impl)
    precond_dtype = jnp.float32 if precision == "mixed" else None
    has_pair = pair is not None and getattr(pair, "is_fast", False)
    if has_pair and pair.evaluator != "tree":
        # flow_multi_local's fast branch serves ONLY the tree: an ewald
        # spec would pass validation, thread a dead anchors operand, and
        # silently run the O(N^2/D) ring flows the caller thinks it
        # replaced (the FFT-grid evaluator has no per-shard decomposition
        # here — docs/parallel.md)
        raise ValueError(
            f"build_spmd_step(pair=...) composes only the 'tree' "
            f"evaluator with the SPMD step, got {pair.evaluator!r}; "
            "pass pair=None for the ring flows")
    if has_pair:
        # the SPMD layout has no global inactive-slot spread (flow_multi's
        # _spread_inactive needs the full concatenated active mask, which
        # no single shard holds): padding nodes replicating slot 0 would
        # pile into one leaf and overflow the plan's static bucket
        # capacity, silently evicting real sources (_bucket's rank clamp).
        # System.step_spmd falls back to the ring flows for such states;
        # direct callers of this seam get a build-time error, not wrong
        # physics.
        import numpy as np
        if not all(bool(np.all(np.asarray(g.active)))
                   for g in fiber_buckets(state.fibers)):
            raise ValueError(
                "build_spmd_step(pair=...) requires every fiber slot "
                "active: the SPMD layout cannot spread inactive padding "
                "nodes, which would overflow the fast plan's static leaf "
                "buckets; pass pair=None (ring flows) for states with "
                "inactive capacity")
    # mixed-mode prep flows stay dense through the refinement tile — the
    # same role gating as System._prep (tree_tol must not cap RHS accuracy)
    prep_pair = None if (refine or not has_pair) else pair

    def node_targets(st, body_caches):
        """(r_loc, r_rep, nf_nodes_local): shard-resident target rows
        (this shard's fiber nodes [+ shell row block]) and replicated
        target rows ([replicated shell nodes +] body nodes)."""
        parts_loc = [fc.node_positions(g) for g in fiber_buckets(st.fibers)]
        nf_l = sum(g.n_fibers * g.n_nodes for g in fiber_buckets(st.fibers))
        if sharded_shell:
            parts_loc.append(st.shell.nodes)
        parts_rep = []
        if shell_mode == "replicated":
            parts_rep.append(st.shell.nodes)
        b_list = body_buckets(st.bodies)
        for i, g in enumerate(b_list):
            nodes = (body_caches[i].nodes if body_caches is not None
                     else bd.place(g)[0])
            parts_rep.append(nodes.reshape(-1, 3))
        r_loc = jnp.concatenate(parts_loc, axis=0)
        r_rep = jnp.concatenate(parts_rep, axis=0) if parts_rep else None
        return r_loc, r_rep, nf_l

    def rep_splits(st):
        """(shell rows, body rows) node counts inside the r_rep block."""
        ns_rep = st.shell.n_nodes if shell_mode == "replicated" else 0
        nb = sum(g.n_bodies * g.n_nodes for g in body_buckets(st.bodies))
        return ns_rep, nb

    # ----------------------------------------------------------------- prep

    def prep(st, anchors=None):
        """Port of `System._prep` to the SPMD layout: all per-fiber work
        (caches, BC/RHS assembly, the blocks' factorization and, in the mixed
        tier, inversion) on the owning shard;
        explicit flows ring at resident rows, psum onto replicated rows."""
        st = system._update_plus_pinning(st)
        buckets = fiber_buckets(st.fibers)
        b_list = body_buckets(st.bodies)
        caches = None
        body_caches = None
        shell_rhs = None
        body_rhs = None

        if b_list:
            body_caches = [bd.update_cache(g, p.eta,
                                           precond_dtype=precond_dtype)
                           for g in b_list]
        r_loc, r_rep, nf_l = node_targets(st, body_caches)
        v_loc = jnp.zeros_like(r_loc)
        v_rep_dense = jnp.zeros_like(r_rep) if r_rep is not None else None
        v_rep_part = None

        caches = [fc.update_cache(g, st.dt, p.eta) for g in buckets]
        external = system._periphery_force_fibers(st)
        motor = [jnp.where(st.time >= p.implicit_motor_activation_delay,
                           fc.generate_constant_force(g, c),
                           jnp.zeros_like(g.x))
                 for g, c in zip(buckets, caches)]
        # a zero wall force (no shell, no force law): no ring, no psum
        if system._announce_prep_flow(
                st, "ring" if prep_pair is None else prep_pair.evaluator,
                prep_impl):
            fl, fp = fc.flow_multi_local(buckets, caches, external, r_loc,
                                         r_rep, p.eta, axis_name=axis,
                                         n_dev=n_dev, subtract_self=True,
                                         impl=prep_impl, pair=prep_pair,
                                         pair_anchors=anchors)
            v_loc = v_loc + fl
            v_rep_part = fp

        if b_list:
            for g, bc in zip(b_list, body_caches):
                ext_ft = bd.external_forces_torques(g, st.time)
                v_loc = v_loc + bd.flow(g, bc, r_loc, None, ext_ft, p.eta,
                                        impl=prep_impl)
                v_rep_dense = v_rep_dense + bd.flow(g, bc, r_rep, None,
                                                    ext_ft, p.eta,
                                                    impl=prep_impl)

        v_loc = v_loc + system._external_flows(st, r_loc)
        if r_rep is not None:
            v_rep_dense = v_rep_dense + system._external_flows(st, r_rep)
            v_rep = v_rep_dense
            if v_rep_part is not None:
                v_rep = v_rep + lax.psum(v_rep_part, axis)
        else:
            v_rep = None

        ns_rep, _ = rep_splits(st)
        if b_list:
            body_rhs = []
            off = ns_rep
            for g in b_list:
                nbn = g.n_bodies * g.n_nodes
                v_bodies = v_rep[off:off + nbn].reshape(
                    g.n_bodies, g.n_nodes, 3)
                body_rhs.append(bd.update_RHS(g, v_bodies))
                off += nbn

        off = 0
        new_caches = []
        for g, c, mo, ex in zip(buckets, caches, motor, external):
            nfn = g.n_fibers * g.n_nodes
            v_fib = v_loc[off:off + nfn].reshape(g.n_fibers, g.n_nodes, 3)
            new_caches.append(fc.update_rhs_and_bc(
                g, c, st.dt, p.eta, v_fib, mo + ex, ex,
                precond_dtype=precond_dtype,
                df_words=system._fiber_ops_for(
                    st, precision, g)[0] == "df_tile"))
            off += nfn
        caches = new_caches

        if has_shell:
            if sharded_shell:
                v_shell = v_loc[nf_l:]
            else:
                v_shell = v_rep[:ns_rep]
            shell_rhs = peri.update_RHS(v_shell, st.shell.node_mask)

        return st, caches, body_caches, shell_rhs, body_rhs

    # --------------------------------------------------------- the operator

    def make_matvec(st, caches, body_caches, lo=None, flow_impl=None,
                    pair_spec=None, pair_anchors=None):
        """Port of `System._apply_matvec` to the SPMD layout (same lo-seam
        semantics: all flows/dense ops through the f32 copies, stiff
        fiber-local rows in the solve dtype). ``pair_spec`` routes the
        fiber Stokeslet flow through `flow_multi_local`'s tree branch."""
        impl = p.kernel_impl if flow_impl is None else flow_impl
        buckets = fiber_buckets(st.fibers)
        b_list = body_buckets(st.bodies)
        fib_size, shell_size, _ = system._sizes(st)
        f_state, f_caches, f_bcaches = ((st, caches, body_caches)
                                        if lo is None else lo)
        f_buckets = fiber_buckets(f_state.fibers)
        f_b_list = body_buckets(f_state.bodies)

        def matvec(x):
            hi = x.dtype
            lo_dtype = hi if lo is None else f_state.time.dtype
            r_loc, r_rep, nf_l = node_targets(f_state, f_bcaches)
            ns_rep, _ = rep_splits(f_state)
            v_loc = jnp.zeros_like(r_loc)
            # replicated-row velocities split by evaluation strategy:
            # per-shard PARTIALS that one psum will sum, vs dense values
            # every shard computes identically from replicated inputs
            v_rep_part = (jnp.zeros_like(r_rep) if r_rep is not None
                          else None)
            v_rep_dense = (jnp.zeros_like(r_rep) if r_rep is not None
                           else None)

            x_fibs = []
            off = 0
            for g in buckets:
                size = fc.solution_size(g)
                x_fibs.append(x[off:off + size].reshape(g.n_fibers,
                                                        4 * g.n_nodes))
                off += size
            fws = [fc.apply_fiber_force(g, c, xf, df=lo is not None)
                   for g, c, xf in zip(buckets, caches, x_fibs)]
            fl, fp = fc.flow_multi_local(
                f_buckets, f_caches, [fw.astype(lo_dtype) for fw in fws],
                r_loc, r_rep, p.eta, axis_name=axis, n_dev=n_dev,
                subtract_self=True, impl=impl, pair=pair_spec,
                pair_anchors=pair_anchors)
            v_loc = v_loc + fl
            if fp is not None:
                v_rep_part = v_rep_part + fp

            x_shell = x[fib_size:fib_size + shell_size]
            if has_shell and (buckets or b_list):
                # shell flow at fiber and body rows only; the shell
                # self-interaction lives in the dense operator
                rho = x_shell.astype(lo_dtype)
                if sharded_shell:
                    sl, sp = peri.flow_local(
                        f_state.shell, r_loc[:nf_l], r_rep, rho, p.eta,
                        axis_name=axis, n_dev=n_dev, impl=impl)
                    v_loc = v_loc.at[:nf_l].add(sl)
                    if sp is not None:
                        v_rep_part = v_rep_part + sp
                else:
                    # replicated shell: dense double layer from the full
                    # node set, deterministic on every shard — added OUTSIDE
                    # the psum of partials
                    r_fb = (jnp.concatenate([r_loc[:nf_l], r_rep[ns_rep:]],
                                            axis=0)
                            if r_rep is not None and r_rep.shape[0] > ns_rep
                            else r_loc[:nf_l])
                    vfb = peri.flow(f_state.shell, r_fb, rho, p.eta,
                                    impl=impl)
                    v_loc = v_loc.at[:nf_l].add(vfb[:nf_l])
                    if vfb.shape[0] > nf_l:
                        v_rep_dense = v_rep_dense.at[ns_rep:].add(
                            vfb[nf_l:])

            # body link conditions: per-shard fiber partials -> one psum
            x_bods = []
            v_boundaries = None
            body_fts = None
            if b_list:
                nbt = bd.n_total(b_list)
                off_b = fib_size + shell_size
                for g in b_list:
                    size = g.solution_size
                    x_bods.append(x[off_b:off_b + size].reshape(
                        g.n_bodies, 3 * g.n_nodes + 6))
                    off_b += size
                body_fts = [jnp.zeros((g.n_bodies, 6), dtype=hi)
                            for g in b_list]
                if buckets:
                    v_boundaries = [jnp.zeros((g.n_fibers, 7), dtype=hi)
                                    for g in buckets]
                    for j, (gb, bc, xb) in enumerate(
                            zip(b_list, body_caches, x_bods)):
                        for i, (gf, c, xf) in enumerate(
                                zip(buckets, caches, x_fibs)):
                            gf_loc = bd.local_binding(gf, gb, nbt)
                            vb, ft = bd.link_conditions(gb, bc, gf_loc, c,
                                                        xf, xb)
                            v_boundaries[i] = v_boundaries[i] + vb
                            body_fts[j] = body_fts[j] + ft

            # ONE psum per matvec: replicated-row partial velocities + the
            # link forces/torques together (bodies imply r_rep is present)
            v_rep = None
            if body_fts is not None:
                v_rep_part, body_fts = lax.psum((v_rep_part, body_fts), axis)
            elif r_rep is not None:
                v_rep_part = lax.psum(v_rep_part, axis)
            if r_rep is not None:
                v_rep = v_rep_part + v_rep_dense

            if b_list:
                r_all = (jnp.concatenate([r_loc, r_rep], axis=0)
                         if r_rep is not None else r_loc)
                for gb, f_gb, f_bc, xb, ft in zip(
                        b_list, f_b_list,
                        f_bcaches or [None] * len(b_list), x_bods, body_fts):
                    vflow = bd.flow(f_gb, f_bc, r_all,
                                    xb.astype(lo_dtype),
                                    ft.astype(lo_dtype), p.eta, impl=impl)
                    v_loc = v_loc + vflow[:r_loc.shape[0]]
                    v_rep = v_rep + vflow[r_loc.shape[0]:]

            res = []
            off = 0
            for i, (g, c, xf) in enumerate(zip(buckets, caches, x_fibs)):
                nfn = g.n_fibers * g.n_nodes
                v_fib = v_loc[off:off + nfn].reshape(
                    g.n_fibers, g.n_nodes, 3).astype(hi)
                vb = (v_boundaries[i] if v_boundaries is not None
                      else jnp.zeros((g.n_fibers, 7), dtype=hi))
                res.append(fc.matvec(g, c, xf, v_fib, vb,
                                     df=lo is not None).reshape(-1))
                off += nfn
            if has_shell:
                if sharded_shell:
                    v_shell = v_loc[nf_l:]
                    with jax.named_scope("allgather-density"):
                        x_full = lax.all_gather(x_shell, axis, tiled=True)
                    res.append(peri.matvec(f_state.shell,
                                           x_full.astype(lo_dtype),
                                           v_shell).astype(hi))
                else:
                    v_shell = v_rep[:ns_rep]
                    res.append(peri.matvec(f_state.shell,
                                           x_shell.astype(lo_dtype),
                                           v_shell).astype(hi))
            off = ns_rep
            for g, f_gb, f_bc, xb in zip(b_list, f_b_list,
                                         f_bcaches or [None] * len(b_list),
                                         x_bods):
                nbn = g.n_bodies * g.n_nodes
                v_bodies = v_rep[off:off + nbn].reshape(
                    g.n_bodies, g.n_nodes, 3)
                res.append(bd.matvec(f_gb, f_bc, xb.astype(lo_dtype),
                                     v_bodies).astype(hi).reshape(-1))
                off += nbn
            return jnp.concatenate(res)

        return matvec

    # ----------------------------------------------------- the preconditioner

    def make_precond(st, caches, body_caches):
        """Port of `System._apply_precond`: per-fiber block solves
        (`fc.apply_preconditioner`) on the owning shard; shell solve =
        all-gather(density) + local M_inv row block; the shell-first
        Gauss-Seidel correction rings the local shell blocks at fiber rows
        and psums the body-row partial."""
        buckets = fiber_buckets(st.fibers)
        b_list = body_buckets(st.bodies)
        fib_size, shell_size, _ = system._sizes(st)
        nf_l = sum(g.n_fibers * g.n_nodes for g in buckets)

        def precond(x):
            # scoped like System._apply_precond: device time lands under
            # gmres/arnoldi/precond in the obs profile table
            with jax.named_scope("precond"):
                return precond_impl(x)

        def precond_impl(x):
            y_shell = None
            if has_shell:
                x_shell = x[fib_size:fib_size + shell_size]
                if sharded_shell:
                    with jax.named_scope("allgather-density"):
                        x_full = lax.all_gather(x_shell, axis, tiled=True)
                    shell = st.shell
                    # `peri.apply_preconditioner` on the gathered density:
                    # the one shell product it does not reach, scoped alike
                    with jax.named_scope("shell"):
                        y_shell = (shell.M_inv
                                   @ x_full.astype(shell.M_inv.dtype)
                                   ).astype(x.dtype)
                else:
                    y_shell = peri.apply_preconditioner(st.shell, x_shell)

            v_corr_loc = None
            v_corr_rep = None
            if p.precond == "gs" and y_shell is not None:
                r_loc, r_rep, _ = node_targets(st, body_caches)
                rho = y_shell.astype(st.shell.nodes.dtype)
                ns_rep, nb_nodes = rep_splits(st)
                r_body = (r_rep[ns_rep:] if (r_rep is not None and nb_nodes)
                          else None)
                if sharded_shell:
                    vl, vp = peri.flow_local(st.shell, r_loc[:nf_l], r_body,
                                             rho, p.eta, axis_name=axis,
                                             n_dev=n_dev, impl=p.kernel_impl)
                    v_corr_loc = vl.astype(x.dtype)
                    if vp is not None:
                        v_corr_rep = lax.psum(vp, axis).astype(x.dtype)
                else:
                    r_fb = (jnp.concatenate([r_loc[:nf_l], r_body], axis=0)
                            if r_body is not None else r_loc[:nf_l])
                    v = peri.flow(st.shell, r_fb, rho, p.eta,
                                  impl=p.kernel_impl).astype(x.dtype)
                    v_corr_loc = v[:nf_l]
                    if r_body is not None:
                        v_corr_rep = v[nf_l:]

            res = []
            off = 0
            off_v = 0
            for g, c in zip(buckets, caches):
                size = fc.solution_size(g)
                x_fib = x[off:off + size].reshape(g.n_fibers, 4 * g.n_nodes)
                if v_corr_loc is not None:
                    nfn = g.n_fibers * g.n_nodes
                    v_fib = v_corr_loc[off_v:off_v + nfn].reshape(
                        g.n_fibers, g.n_nodes, 3)
                    # fiber rows of A at (0, y_shell, 0): pure coupling term
                    x_fib = x_fib - fc.matvec(
                        g, c, jnp.zeros_like(x_fib), v_fib,
                        jnp.zeros((g.n_fibers, 7), dtype=x.dtype), df=True)
                    off_v += nfn
                res.append(fc.apply_preconditioner(g, c, x_fib).reshape(-1))
                off += size
            if y_shell is not None:
                res.append(y_shell)
            off_b = fib_size + shell_size
            off_v = 0
            for j, g in enumerate(b_list):
                size = g.solution_size
                x_bod = x[off_b:off_b + size].reshape(g.n_bodies, -1)
                if v_corr_rep is not None:
                    nbn = g.n_bodies * g.n_nodes
                    v_bod = v_corr_rep[off_v:off_v + nbn].reshape(
                        g.n_bodies, g.n_nodes, 3)
                    # body rows of A at (0, y_shell, 0) = [v_nodes, 0]
                    x_bod = x_bod - bd.matvec(
                        g, body_caches[j], jnp.zeros_like(x_bod), v_bod)
                    off_v += nbn
                res.append(bd.apply_preconditioner(
                    g, body_caches[j], x_bod).reshape(-1))
                off_b += size
            return jnp.concatenate(res)

        return precond

    # ------------------------------------------------------------ local step

    def local_step(st, anchors=None):
        whole = st.shell
        if padded_shell:
            with jax.named_scope("shell_pad"):
                st = st._replace(shell=_pad_shell_rows(whole, axis, n_dev))
        # skelly-pulse phase scopes (obs/profile.py): metadata-only — the
        # audited mesh programs (collective inventory, replication
        # analysis, cost baselines) are byte-identical
        with jax.named_scope("prep"):
            st, caches, body_caches, shell_rhs, body_rhs = prep(st, anchors)
            buckets = fiber_buckets(st.fibers)
            b_list = body_buckets(st.bodies)
            fib_size, shell_size, _ = system._sizes(st)

            rhs_parts = [c.RHS.reshape(-1) for c in caches]
            if shell_rhs is not None:
                rhs_parts.append(shell_rhs)
            for br in (body_rhs or []):
                rhs_parts.append(br.reshape(-1))
            rhs = jnp.concatenate(rhs_parts)
        # a shard's own blocks: the shapes are one device's
        system._announce_block_precond(caches, body_caches)
        system._announce_periphery(
            st, chips=n_dev if sharded_shell else 1,
            live_nodes=whole.n_nodes if padded_shell else None)
        system._announce_fiber_ops(st, precision)
        system._announce_pair_tile(st, precision)

        nonrep_end = fib_size + (shell_size if sharded_shell else 0)
        rdot = _make_rdot(axis, nonrep_end)

        krylov_pair = pair if has_pair else None
        if precision == "mixed":
            lo = _cast_floats((st, caches, body_caches), jnp.float32)
            with jax.named_scope("gmres"):
                result = gmres_ir(
                    # hi residual matvec: dense regardless of the spec —
                    # the fast evaluator's tol must not cap residual_true
                    make_matvec(st, caches, body_caches,
                                flow_impl=hi_impl),
                    make_matvec(st, caches, body_caches, lo=lo,
                                pair_spec=krylov_pair,
                                pair_anchors=anchors),
                    rhs,
                    precond_lo=make_precond(lo[0], lo[1], lo[2]),
                    tol=p.gmres_tol, inner_tol=p.inner_tol,
                    restart=p.gmres_restart, maxiter=p.gmres_maxiter,
                    max_refine=p.max_refine, rdot=rdot,
                    block_s=p.gmres_block_s)
        else:
            with jax.named_scope("gmres"):
                result = gmres(
                    make_matvec(st, caches, body_caches,
                                pair_spec=krylov_pair,
                                pair_anchors=anchors), rhs,
                    precond=make_precond(st, caches, body_caches),
                    tol=p.gmres_tol, restart=p.gmres_restart,
                    maxiter=p.gmres_maxiter, rdot=rdot,
                    block_s=p.gmres_block_s)

        # ------------------------------------------------ advance components
        with jax.named_scope("advance"):
            new_state = st
            off = 0
            stepped = []
            sol_fibs = []
            for g in buckets:
                size = fc.solution_size(g)
                sol_fib = result.x[off:off + size].reshape(g.n_fibers, -1)
                sol_fibs.append(sol_fib)
                stepped.append(fc.step(g, sol_fib))
                off += size
            new_state = new_state._replace(
                fibers=_rewrap_fibers(st.fibers, stepped))
            sol_shell = None
            if has_shell:
                sol_shell = result.x[fib_size:fib_size + shell_size]
                if padded_shell:
                    # the live density, replicated, as the state holds it
                    with jax.named_scope("shell_pad"):
                        sol_shell = lax.all_gather(
                            sol_shell, axis,
                            tiled=True)[:whole.solution_size]
                    new_state = new_state._replace(shell=whole._replace(
                        density=sol_shell))
                else:
                    new_state = new_state._replace(shell=st.shell._replace(
                        density=sol_shell))
            sol_body = None
            if b_list:
                off_b = fib_size + shell_size
                sol_body = result.x[off_b:]
                new_b = []
                for g in b_list:
                    size = g.solution_size
                    sol_bod = result.x[off_b:off_b + size].reshape(
                        g.n_bodies, -1)
                    new_b.append(bd.step(g, sol_bod, st.dt))
                    off_b += size
                new_state = new_state._replace(
                    bodies=_rewrap_bodies(st.bodies, new_b))
                # fibers re-pin to their (moved) nucleation sites —
                # per-shard local fibers against the replicated moved bodies
                nbt = bd.n_total(new_b)
                repinned = list(fiber_buckets(new_state.fibers))
                for gb in new_b:
                    _, _, new_sites = bd.place(gb)
                    repinned = [
                        g._replace(x=bd.repin_to_bodies(
                            bd.local_binding(g, gb, nbt), new_sites, gb).x)
                        for g in repinned]
                new_state = new_state._replace(
                    fibers=_rewrap_fibers(new_state.fibers, repinned))
            err_local = jnp.max(jnp.stack(
                [fc.fiber_error(g) for g in fiber_buckets(new_state.fibers)]))
            fiber_error = pmax(err_local, axis)

        # the guard health word rides the mesh program too: the solver's
        # bits are replicated (psum'd reductions), the fiber-error check is
        # on the pmax'd global error — every shard computes the identical
        # word, keeping replicated outputs bitwise in lockstep
        from ..guard.verdict import nonfinite_word

        health = (jnp.asarray(result.health, dtype=jnp.int32)
                  | nonfinite_word(fiber_error))
        if st.flight is not None:
            # skelly-flight on the mesh program: the SAME diagnostics row,
            # with every reduction an explicit collective (pmax/pmin via
            # record_step's axis_name spelling, the solution norm through
            # the replication-restoring rdot seam) so all shards write the
            # bitwise-identical replicated ring — `audit.repflow` analyzes
            # the armed build clean (tests/test_flight.py)
            from ..obs import flight as flight_mod

            new_state = new_state._replace(flight=flight_mod.record_step(
                st._replace(shell=whole) if padded_shell else st,
                new_state, result.x,
                residual_true=result.residual_true, health=health,
                dt_used=st.dt, shell_shape=system.shell_shape,
                solution_norm=jnp.sqrt(rdot(result.x, result.x)),
                axis_name=axis, axis_size=n_dev,
                sol_scan_rows=nonrep_end,
                shell_sharded=sharded_shell and not padded_shell))
        info = StepInfo(
            converged=result.converged, iters=result.iters,
            residual=result.residual, fiber_error=fiber_error,
            residual_true=result.residual_true,
            loss_of_accuracy=(result.converged
                              & (result.residual_true > 10.0 * p.gmres_tol)),
            refines=jnp.asarray(result.refines, dtype=jnp.int32),
            # skelly-scope gmres_cycles ride along; the convergence ring
            # buffer stays None in the mesh program (a replicated [N,3]
            # carry per shard buys nothing over the single-chip history)
            cycles=jnp.asarray(result.cycles, dtype=jnp.int32),
            health=health, dt_used=st.dt, guard_retries=jnp.int32(0),
            gram_rows=jnp.asarray(result.gram_rows, dtype=jnp.int32))
        return new_state, (tuple(sol_fibs), sol_shell, sol_body), info

    # -------------------------------------------------------------- assembly

    state_specs = _state_specs(state, shell_mode)
    sol_specs = (
        tuple(P(FIBER_AXIS) for _ in fiber_buckets(state.fibers)),
        (P(FIBER_AXIS) if sharded_shell and not padded_shell else P())
        if has_shell else None,
        P() if has_bodies else None,
    )
    info_specs = jax.tree_util.tree_map(
        lambda _: P(), StepInfo(converged=0, iters=0, residual=0.0,
                                fiber_error=0.0, residual_true=0.0,
                                loss_of_accuracy=False, refines=0,
                                cycles=0, history=None, health=0,
                                dt_used=0.0, guard_retries=0, gram_rows=0))
    # check_vma off on this one program: with it on, the GMRES while_loop's
    # carries (H, cs, sn — initialised unvarying, fed by psum'd dots the
    # checker sees as varying over `fib`) fail to trace until each initial
    # carry gets a `lax.pcast` (tried in PR 22). Replicated-output
    # correctness is guaranteed by construction here (psum-or-replicated
    # inputs only — see the module docstring), audited by `audit.repflow`
    # and pinned by the parity tests
    if has_pair:
        # the plan's traced anchors enter as one replicated operand so a
        # quantized anchor hop under drift reuses the compiled program
        sharded = jax.shard_map(
            local_step, mesh=mesh, in_specs=(state_specs, P()),
            out_specs=(state_specs, sol_specs, info_specs), check_vma=False)
    else:
        sharded = jax.shard_map(
            lambda st: local_step(st), mesh=mesh, in_specs=(state_specs,),
            out_specs=(state_specs, sol_specs, info_specs), check_vma=False)

    def step(st, pair_anchors=None):
        if has_pair:
            new_state, (sol_fibs, sol_shell, sol_body), info = sharded(
                st, pair_anchors)
        else:
            new_state, (sol_fibs, sol_shell, sol_body), info = sharded(st)
        if flat_solution:
            with jax.named_scope("advance"):
                parts = [s.reshape(-1) for s in sol_fibs]
                if sol_shell is not None:
                    parts.append(sol_shell)
                if sol_body is not None:
                    parts.append(sol_body)
                solution = jnp.concatenate(parts)
        else:
            solution = SpmdSolution(fibers=tuple(sol_fibs), shell=sol_shell,
                                    bodies=sol_body)
        return new_state, solution, info

    if donate == "auto":
        # CPU XLA has no buffer donation — jit would warn on every call
        donate = jax.default_backend() != "cpu"
    wrap = jax.jit if jit_wrapper is None else jit_wrapper
    return wrap(step, donate_argnums=(0,) if donate else ())


def spmd_step(system, state: SimState, mesh: Mesh, *,
              allow_replicated_shell: bool = False,
              flat_solution: bool = True):
    """One explicitly-sharded implicit step (build + run, uncached).

    `System.step_spmd` caches the built program per (mesh, state structure)
    — prefer it for anything iterative.
    """
    fn = build_spmd_step(system, mesh, state,
                         allow_replicated_shell=allow_replicated_shell,
                         flat_solution=flat_solution, donate=False)
    return fn(state)


# ---------------------------------------------------------------- skelly-audit

def auditable_programs():
    """The SPMD scaling ladder's audit entries: the coupled explicitly-
    sharded step lowered on 2/4/8-device CPU meshes. The contracts pin the
    collective inventory of docs/parallel.md's table per mesh size —
    including the bound that no all-gather ever exceeds the shell density
    (the GSPMD silent-replication failure mode). The d2 program also runs
    the retrace probe (d4/d8 would re-pay the same compile for no new
    signal)."""
    from ..audit import fixtures
    from ..audit.registry import AuditProgram, built_from
    from . import shard_state
    from .mesh import make_mesh

    def build(n_dev, shell_nodes=fixtures.SHELL_NODES):
        def _build():
            mesh = make_mesh(n_dev)
            # gmres_block_s=4: the audited ladder configuration IS the
            # communication-avoiding solver (ISSUE 8) — the contracts pin
            # the BATCHED Gram rounds (2 all-reduces per 4 Krylov
            # iterations in the solver loop body, vs the sequential
            # cycle's 3 per iteration), so a regression back to
            # per-iteration psums fails the collective inventory
            system = fixtures.make_system(shell=True, gmres_block_s=4)
            state = fixtures.coupled_state(system, shell_nodes=shell_nodes)
            # the dividing ladder is lowered from the placement it was
            # pinned on; a padded shell only from the mesh step's own
            state = shard_state(state, mesh, step="spmd" if shell_nodes
                                != fixtures.SHELL_NODES else "gspmd")
            fn = build_spmd_step(system, mesh, state, flat_solution=False,
                                 donate=True)
            return built_from(fn, state)
        return _build

    def retrace_probe():
        from ..testing import trace_counting_jit

        mesh = make_mesh(2)
        system = fixtures.make_system(gmres_block_s=4)
        state = shard_state(fixtures.free_state(system), mesh)
        fn = build_spmd_step(system, mesh, state, donate=False,
                             jit_wrapper=trace_counting_jit)
        new_state, _, _ = fn(state)
        fn(new_state)  # same structure, new values: must not retrace
        return fn.trace_count

    progs = []
    for n_dev in (2, 4, 8):
        progs.append(AuditProgram(
            name=f"step_spmd_d{n_dev}", layer="parallel",
            summary=f"explicitly-sharded coupled step on the {n_dev}-device "
                    "mesh (row-sharded shell, donated state)",
            build=build(n_dev),
            retrace_probe=retrace_probe if n_dev == 2 else None))
    progs.append(AuditProgram(
        name="step_spmd_padded_d4", layer="parallel",
        summary="explicitly-sharded coupled step on the 4-device mesh, its "
                f"{fixtures.PADDED_SHELL_NODES}-node shell padded to whole "
                "nodes a device (donated state)",
        build=build(4, fixtures.PADDED_SHELL_NODES)))
    return progs
