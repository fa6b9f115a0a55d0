"""Rigid bodies (MTOCs/centrosomes) as first/second-kind boundary integrals.

TPU-native replacement for `SphericalBody`/`EllipsoidalBody`/`BodyContainer`
(`/root/reference/src/core/body_spherical.cpp`, `body_ellipsoidal.cpp`,
`body_container.cpp`): bodies of one surface resolution live in batched arrays
[nb, n, ...] and all per-body dense operators are vmapped; the reference's
rank-0 body ownership + MPI broadcast disappears (body state is replicated in
the jit program). Spherical and ellipsoidal bodies share one formulation (the
reference's two classes are near-duplicates); the `kind` only matters for
collision geometry.

Solution layout per body (matching `body_spherical.hpp:61`):
[3n node densities (node-major xyz) | 6 rigid velocities (U, omega)].

External forces support the reference's Linear and Oscillatory schedules
(`body_container.cpp:413-447`).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import block_precond, kernels
from ..utils import quaternion as quat

EXTFORCE_LINEAR = 0
EXTFORCE_OSCILLATORY = 1


class BodyGroup(NamedTuple):
    """Batched same-resolution rigid bodies (a pytree; [nb] leading axis)."""

    nodes_ref: jnp.ndarray        # [nb, n, 3]
    normals_ref: jnp.ndarray      # [nb, n, 3]
    weights: jnp.ndarray          # [nb, n]
    nucleation_sites_ref: jnp.ndarray  # [nb, ns, 3]
    position: jnp.ndarray         # [nb, 3]
    orientation: jnp.ndarray      # [nb, 4] quaternion (w, x, y, z)
    solution: jnp.ndarray         # [nb, 3n+6]
    velocity: jnp.ndarray         # [nb, 3]
    angular_velocity: jnp.ndarray  # [nb, 3]
    external_force: jnp.ndarray   # [nb, 3]
    external_torque: jnp.ndarray  # [nb, 3]
    ext_force_type: jnp.ndarray   # [nb] int32 (Linear/Oscillatory)
    osc_amplitude: jnp.ndarray    # [nb]
    osc_omega: jnp.ndarray        # [nb]
    osc_phase: jnp.ndarray        # [nb]
    radius: jnp.ndarray           # [nb] attachment radius (spheres; 0 otherwise)
    kind_sphere: jnp.ndarray      # [nb] bool: sphere (True) / ellipsoid (False)
    #: [nb, 3] ellipsoid semiaxes in the body frame (zeros for spheres /
    #: generic bodies) — drives the rigid-motion containment override in
    #: velocity fields (`system.cpp:371-380` handles ellipsoids too)
    semiaxes: jnp.ndarray = None
    #: int32 [nb] original config-order index. With multiple shape/resolution
    #: buckets the solver layout is bucket-major; `config_rank` is the GLOBAL
    #: body id fibers' `binding_body` refers to, and trajectory writers sort
    #: bodies back to it so the wire stays reference- (config-) ordered.
    config_rank: jnp.ndarray = None

    @property
    def n_bodies(self) -> int:
        return self.nodes_ref.shape[0]

    @property
    def n_nodes(self) -> int:
        return self.nodes_ref.shape[1]

    @property
    def solution_size(self) -> int:
        return self.n_bodies * (3 * self.n_nodes + 6)


class BodyCaches(NamedTuple):
    nodes: jnp.ndarray       # [nb, n, 3] lab frame
    normals: jnp.ndarray     # [nb, n, 3] lab frame
    nucleation_sites: jnp.ndarray  # [nb, ns, 3] lab frame
    K: jnp.ndarray           # [nb, 3n, 6]
    ex: jnp.ndarray          # [nb, n, 3] singularity-subtraction vectors
    ey: jnp.ndarray
    ez: jnp.ndarray
    #: the dense body operator's block preconditioner, held one of two
    #: ways by tier (`ops.block_precond`): batched LU factors (full tier) or
    #: the [nb, 3n+6, 3n+6] inverse formed once a step (mixed tier)
    lu: jnp.ndarray | None
    piv: jnp.ndarray | None
    inv: jnp.ndarray | None


def make_group(nodes_ref, normals_ref, weights, *, position=None, orientation=None,
               nucleation_sites_ref=None, external_force=0.0, external_torque=0.0,
               ext_force_type=EXTFORCE_LINEAR, osc_amplitude=0.0, osc_omega=0.0,
               osc_phase=0.0, radius=0.0, kind="sphere", semiaxes=0.0,
               config_rank=None, dtype=jnp.float64) -> BodyGroup:
    nodes_ref = jnp.asarray(nodes_ref, dtype=dtype)
    if nodes_ref.ndim == 2:
        nodes_ref = nodes_ref[None]
    nb, n = nodes_ref.shape[0], nodes_ref.shape[1]

    def mat(v, shape):
        return jnp.broadcast_to(jnp.asarray(v, dtype=dtype), shape)

    if nucleation_sites_ref is None:
        nucleation_sites_ref = jnp.zeros((nb, 0, 3), dtype=dtype)
    else:
        nucleation_sites_ref = jnp.asarray(nucleation_sites_ref, dtype=dtype)
        if nucleation_sites_ref.ndim == 2:
            nucleation_sites_ref = jnp.broadcast_to(
                nucleation_sites_ref[None], (nb,) + nucleation_sites_ref.shape)

    return BodyGroup(
        nodes_ref=nodes_ref,
        normals_ref=mat(normals_ref, (nb, n, 3)),
        weights=mat(weights, (nb, n)),
        nucleation_sites_ref=nucleation_sites_ref,
        position=mat(0.0 if position is None else position, (nb, 3)),
        orientation=(jnp.broadcast_to(jnp.asarray(quat.IDENTITY, dtype=dtype), (nb, 4))
                     if orientation is None else mat(orientation, (nb, 4))),
        solution=jnp.zeros((nb, 3 * n + 6), dtype=dtype),
        velocity=jnp.zeros((nb, 3), dtype=dtype),
        angular_velocity=jnp.zeros((nb, 3), dtype=dtype),
        external_force=mat(external_force, (nb, 3)),
        external_torque=mat(external_torque, (nb, 3)),
        ext_force_type=jnp.broadcast_to(jnp.asarray(ext_force_type, jnp.int32), (nb,)),
        osc_amplitude=mat(osc_amplitude, (nb,)),
        osc_omega=mat(osc_omega, (nb,)),
        osc_phase=mat(osc_phase, (nb,)),
        radius=mat(radius, (nb,)),
        kind_sphere=jnp.broadcast_to(jnp.asarray(kind == "sphere"), (nb,)),
        semiaxes=mat(semiaxes, (nb, 3)),
        config_rank=(jnp.arange(nb, dtype=jnp.int32) if config_rank is None
                     else jnp.asarray(config_rank, dtype=jnp.int32)),
    )


def as_buckets(bodies) -> tuple:
    """Normalize a bodies field (None | BodyGroup | iterable of buckets) to
    a tuple. `BodyGroup` is itself a NamedTuple, so the single-group test
    must precede generic tuple handling."""
    if bodies is None:
        return ()
    if isinstance(bodies, BodyGroup):
        return (bodies,)
    return tuple(bodies)


def n_total(bodies) -> int:
    """Total body count across buckets (the global `binding_body` id space)."""
    return sum(g.n_bodies for g in as_buckets(bodies))


def local_binding(fibers, group: BodyGroup, n_bodies_total: int):
    """Remap fibers' GLOBAL `binding_body` ids into ``group``-local slots.

    Returns a fibers view whose `binding_body` is the local slot for fibers
    bound to a body in this bucket and -1 otherwise — what the per-bucket
    `link_conditions` / `repin_to_bodies` expect. The lookup table is built
    from `config_rank` (the global id of each slot), host-independent and
    jit-safe (static shapes).
    """
    ranks = (group.config_rank if group.config_rank is not None
             else jnp.arange(group.n_bodies, dtype=jnp.int32))
    lookup = jnp.full((max(n_bodies_total, 1),), -1, dtype=jnp.int32)
    lookup = lookup.at[ranks].set(jnp.arange(group.n_bodies, dtype=jnp.int32))
    bb = fibers.binding_body
    local = jnp.where(bb >= 0, lookup[jnp.clip(bb, 0, n_bodies_total - 1)], -1)
    return fibers._replace(binding_body=local)


# ----------------------------------------------------------------- kinematics

def place(group: BodyGroup):
    """Lab-frame nodes/normals/nucleation sites (`SphericalBody::place`,
    `body_spherical.cpp:146-159`)."""
    rot = quat.rotation_matrix(group.orientation)          # [nb, 3, 3]
    nodes = group.position[:, None, :] + jnp.einsum("bij,bnj->bni", rot, group.nodes_ref)
    normals = jnp.einsum("bij,bnj->bni", rot, group.normals_ref)
    sites = group.position[:, None, :] + jnp.einsum("bij,bsj->bsi", rot,
                                                    group.nucleation_sites_ref)
    return nodes, normals, sites


@jax.named_scope("body")
def update_cache(group: BodyGroup, eta, precond_dtype=None) -> BodyCaches:
    """Lab placement + singularity subtraction + K matrix + the dense
    operator's block preconditioner (`update_cache_variables`,
    `body_spherical.cpp:94-127`).

    ``precond_dtype`` factors the operator in a lower precision (f32 for
    TPU, whose LuDecomposition is f32-only) and stores its inverse, formed
    here once a step, in the factors' place; None keeps the LU factors in
    the state dtype (`ops.block_precond`)."""
    nodes, normals, sites = place(group)
    nb, n = group.n_bodies, group.n_nodes

    def sing(nodes_b, normals_b, w_b, k):
        e = jnp.zeros((n, 3), dtype=nodes_b.dtype).at[:, k].set(w_b)
        return kernels.stresslet_times_normal_times_density(nodes_b, normals_b, e, eta)

    ex = jax.vmap(lambda a, b, w: sing(a, b, w, 0))(nodes, normals, group.weights)
    ey = jax.vmap(lambda a, b, w: sing(a, b, w, 1))(nodes, normals, group.weights)
    ez = jax.vmap(lambda a, b, w: sing(a, b, w, 2))(nodes, normals, group.weights)

    # K: node-major 3-row blocks [I | cross(r)] (`update_K_matrix`, `:74-86`)
    vec = nodes - group.position[:, None, :]               # [nb, n, 3]
    eye3 = jnp.eye(3, dtype=nodes.dtype)

    def k_node(v):
        rotpart = jnp.array([[0.0, v[2], -v[1]],
                             [-v[2], 0.0, v[0]],
                             [v[1], -v[0], 0.0]], dtype=v.dtype)
        return jnp.concatenate([eye3, rotpart], axis=1)    # [3, 6]

    K = jax.vmap(jax.vmap(k_node))(vec).reshape(nb, 3 * n, 6)

    # dense operator A (`update_preconditioner`, `:104-127`); assembled in
    # the 2-D [3n, 3n] layout throughout — a [.., n, 3]-shaped intermediate
    # would be tile-padded 3 -> 128 by XLA (42x HBM)
    def build_A(nodes_b, normals_b, w_b, ex_b, ey_b, ez_b, K_b):
        M = kernels.stresslet_times_normal_blocked(
            nodes_b, normals_b, eta, block_size=min(512, -(-n // 8) * 8))
        M = kernels.subtract_singularity_columns(M, (ex_b, ey_b, ez_b), w_b)
        top = jnp.concatenate([M, -K_b], axis=1)
        bottom = jnp.concatenate([-K_b.T, jnp.eye(6, dtype=M.dtype)], axis=1)
        return jnp.concatenate([top, bottom], axis=0)

    A = jax.vmap(build_A)(nodes, normals, group.weights, ex, ey, ez, K)
    lu, piv, inv = block_precond.factor(A, precond_dtype)

    return BodyCaches(nodes=nodes, normals=normals, nucleation_sites=sites,
                      K=K, ex=ex, ey=ey, ez=ez, lu=lu, piv=piv, inv=inv)


# ------------------------------------------------------------------ operators

@jax.named_scope("body")
def matvec(group: BodyGroup, caches: BodyCaches, x_bodies, v_bodies):
    """A_body x per body (`SphericalBody::matvec`, `body_spherical.cpp:39-63`).

    ``x_bodies`` [nb, 3n+6]; ``v_bodies`` [nb, n, 3] velocities at body nodes.
    """
    nb, n = group.n_bodies, group.n_nodes
    d = x_bodies[:, :3 * n].reshape(nb, n, 3)
    U = x_bodies[:, 3 * n:]

    c = (d[:, :, 0:1] / group.weights[..., None] * caches.ex
         + d[:, :, 1:2] / group.weights[..., None] * caches.ey
         + d[:, :, 2:3] / group.weights[..., None] * caches.ez)   # [nb, n, 3]

    KU = jnp.einsum("bik,bk->bi", caches.K, U)                    # [nb, 3n]
    KTl = jnp.einsum("bik,bi->bk", caches.K, d.reshape(nb, 3 * n))

    res_nodes = -c.reshape(nb, 3 * n) - KU + v_bodies.reshape(nb, 3 * n)
    res_com = -KTl + U
    return jnp.concatenate([res_nodes, res_com], axis=1)


@jax.named_scope("body")
def apply_preconditioner(group: BodyGroup, caches: BodyCaches, x_bodies):
    """Every body's dense operator inverted on ``x_bodies``
    (`apply_preconditioner`, `body_spherical.cpp:37`): one matmul with the
    stored inverse in the mixed tier, LU solves in the full tier
    (`ops.block_precond`), in the stored block's (possibly lower) precision,
    cast back."""
    return block_precond.solve(caches, x_bodies)


def update_RHS(group: BodyGroup, v_on_bodies):
    """RHS = [-v_nodes, 0(6)] per body (`update_RHS`, `body_spherical.cpp:134-138`)."""
    nb, n = group.n_bodies, group.n_nodes
    return jnp.concatenate([-v_on_bodies.reshape(nb, 3 * n),
                            jnp.zeros((nb, 6), dtype=v_on_bodies.dtype)], axis=1)


@jax.named_scope("body")
def flow(group: BodyGroup, caches: BodyCaches, r_trg, x_bodies, forces_torques,
         eta, impl: str = "exact", ewald_plan=None, ewald_anchors=None,
         pair=None, pair_anchors=None):
    """Body -> target velocities (`flow_spherical`, `body_container.cpp:269-339`):
    double-layer stresslet from node densities + Stokeslet from COM forces +
    rotlet from COM torques. ``forces_torques`` is [nb, 6]. Pass
    ``x_bodies=None`` to skip the stresslet term (e.g. the explicit RHS flow,
    which only carries COM forces/torques). The COM Stokeslet/rotlet stay on
    the exact tile regardless of ``impl`` — nb sources are negligible.

    With an ``ewald_plan`` (covering body nodes + targets) the node-density
    double layer sums through the spectral-Ewald stresslet — the
    one-evaluator-serves-all seam (`body_container.cpp:552-573` routes body
    flows through the FMM); a ``pair`` spec (`ops.evaluator.PairEvaluator`)
    carrying a `TreePlan` routes it through the barycentric-treecode
    stresslet instead. Coincident body-node targets drop in every mode
    (no stresslet self term)."""
    from ..ops.evaluator import resolve

    nb, n = group.n_bodies, group.n_nodes
    _, impl, ewald_plan, ewald_anchors, pair_anchors = resolve(
        pair, pair_anchors, r_trg.dtype, impl=impl, ewald_plan=ewald_plan,
        ewald_anchors=ewald_anchors)
    if x_bodies is None:
        v = jnp.zeros_like(r_trg)
    else:
        densities = x_bodies[:, :3 * n].reshape(nb * n, 3)
        normals = caches.normals.reshape(nb * n, 3)
        f_dl = 2.0 * eta * normals[:, :, None] * densities[:, None, :]
        if (pair is not None and pair.evaluator == "tree"
                and pair.plan is not None and pair.plan.depth > 0):
            from ..ops import treecode as tcode

            v = tcode._stresslet_tree_impl(
                pair.plan, pair_anchors, caches.nodes.reshape(nb * n, 3),
                r_trg, f_dl, eta)
        elif (pair is not None and pair.evaluator == "spectral"
                and pair.plan is not None):
            from ..ops import spectral as spec

            v = spec._stresslet_spectral_impl(
                pair.plan, pair_anchors, caches.nodes.reshape(nb * n, 3),
                r_trg, f_dl) * (pair.plan.eta / eta)
        elif ewald_plan is not None:
            from ..ops import ewald as ew

            if ewald_anchors is None:
                ewald_anchors = ew.plan_anchors(ewald_plan, r_trg.dtype)
                ewald_plan = ew.strip_anchors(ewald_plan)
            v = ew._stresslet_ewald_impl(
                ewald_plan, ewald_anchors, caches.nodes.reshape(nb * n, 3),
                r_trg, f_dl) * (ewald_plan.eta / eta)
        else:
            v = kernels.stresslet_direct(caches.nodes.reshape(nb * n, 3),
                                         r_trg, f_dl, eta, impl=impl)
    v = v + kernels.stokeslet_direct(group.position, r_trg, forces_torques[:, :3], eta)
    v = v + kernels.rotlet(group.position, r_trg, forces_torques[:, 3:], eta)
    return v


def external_forces_torques(group: BodyGroup, time):
    """Linear / oscillatory force schedule [nb, 6]
    (`calculate_external_forces_torques`, `body_container.cpp:413-447`)."""
    osc = group.osc_amplitude * jnp.sin(group.osc_omega * time - group.osc_phase)
    scale = jnp.where(group.ext_force_type == EXTFORCE_OSCILLATORY, osc, 1.0)
    force = scale[:, None] * group.external_force
    return jnp.concatenate([force, group.external_torque], axis=1)


def step(group: BodyGroup, body_sol, dt) -> BodyGroup:
    """Integrate rigid motion (`SphericalBody::step`, `body_spherical.cpp:13-35`)."""
    nb, n = group.n_bodies, group.n_nodes
    U = body_sol[:, 3 * n:3 * n + 3]
    omega = body_sol[:, 3 * n + 3:]
    new_pos = group.position + U * dt
    dq = quat.from_rotation_vector(omega * dt)
    new_q = quat.normalize(quat.multiply(dq, group.orientation))
    return group._replace(position=new_pos, orientation=new_q, solution=body_sol,
                          velocity=U, angular_velocity=omega)


# ------------------------------------------------------------- link conditions

def link_conditions(group: BodyGroup, caches: BodyCaches, fibers, fiber_caches,
                    fiber_sol, x_bodies):
    """Fiber <-> body attachment coupling (`calculate_link_conditions`,
    `body_container.cpp:170-267`).

    Returns (velocities_on_fiber [nf, 7], body_forces_torques [nb, 6]).
    ``fiber_sol`` is [nf, 4n_f] in [x|y|z|T] block layout.
    """
    nf, n_f = fibers.n_fibers, fibers.n_nodes
    nb, n = group.n_bodies, group.n_nodes
    dtype = fiber_sol.dtype
    mats = fibers.mats

    attached = fibers.binding_body >= 0
    body_idx = jnp.clip(fibers.binding_body, 0, nb - 1)
    site_idx = jnp.clip(fibers.binding_site, 0,
                        max(group.nucleation_sites_ref.shape[1] - 1, 0))

    body_vel = x_bodies[:, 3 * n:3 * n + 3]
    body_omega = x_bodies[:, 3 * n + 3:]

    if group.nucleation_sites_ref.shape[1] == 0:
        return (jnp.zeros((nf, 7), dtype=dtype), jnp.zeros((nb, 6), dtype=dtype))

    sites = caches.nucleation_sites[body_idx, site_idx]          # [nf, 3]
    site_pos = sites - group.position[body_idx]                  # body-frame offset

    x_new = jnp.stack([fiber_sol[:, :n_f], fiber_sol[:, n_f:2 * n_f],
                       fiber_sol[:, 2 * n_f:3 * n_f]], axis=-1)  # [nf, n_f, 3]
    T0 = fiber_sol[:, 3 * n_f]
    xs0 = fiber_caches.xs[:, 0]                                  # [nf, 3] old tangent

    s = 2.0 / fibers.length
    D2, D3 = jnp.asarray(mats.D2, dtype=dtype), jnp.asarray(mats.D3, dtype=dtype)
    xss0 = (s[:, None] ** 2) * jnp.einsum("j,fjk->fk", D2[0], x_new)
    xsss0 = (s[:, None] ** 3) * jnp.einsum("j,fjk->fk", D3[0], x_new)

    E = fibers.bending_rigidity[:, None]
    F_body = -E * xsss0 + xs0 * T0[:, None]
    L_body = (-E * jnp.cross(site_pos, xsss0)
              + jnp.cross(site_pos, xs0) * T0[:, None]
              + E * jnp.cross(xs0, xss0))

    ft = jnp.where(attached[:, None], jnp.concatenate([F_body, L_body], axis=1), 0.0)
    body_ft = jax.ops.segment_sum(ft, body_idx, num_segments=nb)

    vb = body_vel[body_idx]
    wb = body_omega[body_idx]
    v_fiber = -vb - jnp.cross(wb, site_pos)
    tension_cond = -jnp.einsum("fk,fk->f", xs0, vb) \
        + jnp.einsum("fk,fk->f", jnp.cross(xs0, site_pos), wb)
    site_hat = site_pos / jnp.linalg.norm(site_pos, axis=1, keepdims=True)
    w_fiber = jnp.cross(site_hat, wb)

    v7 = jnp.concatenate([v_fiber, tension_cond[:, None], w_fiber], axis=1)
    v7 = jnp.where(attached[:, None], v7, 0.0)
    return v7, body_ft


def repin_to_bodies(fibers, nucleation_sites, group: BodyGroup):
    """Move attached fiber minus ends back onto their nucleation sites
    (`repin_to_bodies`, `fiber_container_finite_difference.cpp:308-316`).
    ``nucleation_sites`` is the lab-frame [nb, ns, 3] array from `place`."""
    if group.nucleation_sites_ref.shape[1] == 0:
        return fibers
    attached = fibers.binding_body >= 0
    body_idx = jnp.clip(fibers.binding_body, 0, group.n_bodies - 1)
    site_idx = jnp.clip(fibers.binding_site, 0, group.nucleation_sites_ref.shape[1] - 1)
    sites = nucleation_sites[body_idx, site_idx]
    delta = jnp.where(attached[:, None], sites - fibers.x[:, 0], 0.0)
    return fibers._replace(x=fibers.x + delta[:, None, :])


# ------------------------------------------------------------------ collisions

def check_collision_shell(group: BodyGroup, shell_radius, threshold):
    """Spherical body vs spherical periphery (`periphery.cpp:94-97`);
    non-sphere pairs never collide (reference stub parity)."""
    dist = jnp.linalg.norm(group.position, axis=1) + group.radius
    hit = (dist > (shell_radius - threshold)) & group.kind_sphere
    return jnp.any(hit)


def check_collision_pairwise(group: BodyGroup, threshold):
    """Sphere-sphere body collisions (`body_spherical.cpp:304-307`)."""
    nb = group.n_bodies
    d2 = jnp.sum((group.position[:, None, :] - group.position[None, :, :]) ** 2, axis=-1)
    rsum = group.radius[:, None] + group.radius[None, :] + threshold
    both_spheres = group.kind_sphere[:, None] & group.kind_sphere[None, :]
    offdiag = ~jnp.eye(nb, dtype=bool)
    return jnp.any((d2 < rsum**2) & both_spheres & offdiag)


def check_collision_pairwise_multi(buckets, threshold):
    """Sphere-sphere collisions across ALL buckets (collision only needs the
    per-body position/radius/kind columns, which concatenate trivially)."""
    buckets = as_buckets(buckets)
    if not buckets:
        return jnp.asarray(False)
    flat = BodyGroup(
        nodes_ref=jnp.zeros((n_total(buckets), 0, 3),
                            dtype=buckets[0].position.dtype),
        normals_ref=None, weights=None, nucleation_sites_ref=None,
        position=jnp.concatenate([g.position for g in buckets]),
        orientation=None, solution=None, velocity=None, angular_velocity=None,
        external_force=None, external_torque=None, ext_force_type=None,
        osc_amplitude=None, osc_omega=None, osc_phase=None,
        radius=jnp.concatenate([g.radius for g in buckets]),
        kind_sphere=jnp.concatenate([g.kind_sphere for g in buckets]))
    return check_collision_pairwise(flat, threshold)


def check_collision_shell_multi(buckets, shell_radius, threshold):
    buckets = as_buckets(buckets)
    hit = jnp.asarray(False)
    for g in buckets:
        hit = hit | check_collision_shell(g, shell_radius, threshold)
    return hit
