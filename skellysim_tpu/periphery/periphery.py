"""Confining periphery (cell cortex) as a second-kind boundary integral.

TPU-native replacement for `Periphery` (`/root/reference/src/core/periphery.cpp`,
`include/periphery.hpp`): the dense precomputed operator and its inverse live as
device arrays; matvec/preconditioner are single dense matmuls (MXU-native)
instead of MPI row-scatter + Allgatherv + local GEMV. Row-sharding over a mesh
replaces the reference's `MPI_Scatterv` distribution.

Operator assembly (matching `src/skelly_sim/precompute.py:104-140`):
  M = stresslet_times_normal(nodes, normals; eta=1)
      - blockdiag([ex_i | ey_i | ez_i] / w_i)          (singularity subtraction)
      - diag(1/w_i per component)                       (second-kind identity)
      + n n^T                                           (null-space completion)
  M_inv = inverse(M)   (the preconditioner; exact inverse of the self-operator)

Shape-specific collision / fiber steric forces mirror
`SphericalPeriphery`/`EllipsoidalPeriphery`/`GenericPeriphery`
(`src/core/periphery.cpp:94-335`).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..ops import kernels


class PeripheryState(NamedTuple):
    """Device-resident shell state (a pytree)."""

    nodes: jnp.ndarray        # [N, 3]
    normals: jnp.ndarray      # [N, 3] (inward, as stored by precompute)
    weights: jnp.ndarray      # [N]
    M_inv: jnp.ndarray        # [3N, 3N] preconditioner
    stresslet_plus_complementary: jnp.ndarray  # [3N, 3N] operator
    density: jnp.ndarray      # [3N] current solution slice
    #: [N] bool quadrature-row mask, or None (all rows live — the default).
    #: Padded rows (skelly-bucket's shell axis, `grow_capacity`) carry zero
    #: normals/weights and solve the identity: scenes with different shell
    #: quadrature sizes share one compiled program at a capacity rung.
    node_mask: jnp.ndarray = None

    @property
    def n_nodes(self) -> int:
        return self.nodes.shape[0]

    @property
    def solution_size(self) -> int:
        return 3 * self.n_nodes


@dataclass(frozen=True)
class PeripheryShape:
    """Static collision geometry. kind: 'sphere' | 'ellipsoid' | 'generic'."""

    kind: str = "generic"
    radius: float = 0.0
    abc: tuple = (0.0, 0.0, 0.0)


def build_shell_operator(nodes, normals, weights, eta: float = 1.0):
    """Dense second-kind operator + inverse (host-side, float64).

    Faithful to `precompute.py:113-140`; uses the tested JAX kernels for the
    stresslet blocks and NumPy/LAPACK for the O(N^3) inversion.
    """
    nodes = np.asarray(nodes, dtype=np.float64)
    normals = np.asarray(normals, dtype=np.float64)
    weights = np.asarray(weights, dtype=np.float64)
    N = len(nodes)

    # row-blocked 2-D assembly: the dense 4-D builder materializes a
    # [N, 3, N, 3] device array whose trailing dim of 3 XLA tile-pads to 128
    # (55 GB at N = 6000 — an OOM on any real accelerator backend)
    M = np.array(kernels.stresslet_times_normal_blocked(nodes, normals, eta))

    # singularity subtraction vectors e_k integrated with quadrature weights
    def sing_vec(k):
        e = np.zeros((N, 3))
        e[:, k] = weights
        return np.asarray(
            kernels.stresslet_times_normal_times_density(nodes, normals, e, eta))

    ex, ey, ez = sing_vec(0), sing_vec(1), sing_vec(2)
    for i in range(N):
        M[3 * i:3 * i + 3, 3 * i + 0] -= ex[i] / weights[i]
        M[3 * i:3 * i + 3, 3 * i + 1] -= ey[i] / weights[i]
        M[3 * i:3 * i + 3, 3 * i + 2] -= ez[i] / weights[i]

    M -= np.diag(np.repeat(1.0 / weights, 3))
    M += np.outer(normals.reshape(-1), normals.reshape(-1))

    import scipy.linalg as scla

    M_inv = scla.inv(M)
    return M, M_inv


def block_inv(M, max_direct: int = 12000):
    """Dense inverse via recursive 2x2 Schur-complement blocking (on device).

    TPU LuDecomposition keeps an [n, 128] panel in scoped VMEM; at n = 18000
    (a 6000-node shell) that panel is 17.7 MB against a 16 MB limit and the
    compile fails. Halving until blocks fit turns the inverse into two
    smaller LUs plus MXU matmuls. Accuracy is preconditioner-grade, which is
    all its callers need: M_inv only ever feeds `apply_preconditioner`; the
    solve's convergence tolerance is enforced by GMRES against the
    *operator*, not the inverse.
    """
    n = M.shape[0]
    if n <= max_direct:
        return jnp.linalg.inv(M)
    h = n // 2
    A, B = M[:h, :h], M[:h, h:]
    C, D = M[h:, :h], M[h:, h:]
    Ai = block_inv(A, max_direct)
    AiB = Ai @ B
    Si = block_inv(D - C @ AiB, max_direct)
    CAi = C @ Ai
    top = jnp.concatenate([Ai + AiB @ (Si @ CAi), -AiB @ Si], axis=1)
    bot = jnp.concatenate([-Si @ CAi, Si], axis=1)
    return jnp.concatenate([top, bot], axis=0)


def build_shell_operator_device(nodes, normals, weights, eta: float = 1.0, *,
                                op_dtype=jnp.float64,
                                inv_dtype=jnp.float32):
    """Dense second-kind operator + inverse, assembled and inverted on device.

    Same math as `build_shell_operator` (the host/scipy path, mirroring the
    reference's `precompute.py:113-140`), with the O(N^2) assembly row-blocked
    on the accelerator and the O(N^3) inverse done by `block_inv` instead of
    host LAPACK — at 6000 nodes the scipy inverse is ~5 minutes on one host
    core vs seconds on a TPU chip. ``op_dtype`` should stay float64 (the
    operator's accuracy caps the mixed solver's achievable residual);
    ``inv_dtype`` defaults to float32 because the inverse is only ever a
    preconditioner AND TPU LuDecomposition is f32-only. Returns DEVICE
    arrays (callers that persist to npz convert; callers that keep solving
    skip a pointless device->host->device round trip).
    """
    import jax

    if jnp.dtype(op_dtype) == jnp.float64 and not jax.config.jax_enable_x64:
        # without x64 the float64 request silently canonicalizes to f32 and
        # the stored operator caps the mixed solver's achievable residual
        raise RuntimeError(
            "build_shell_operator_device(op_dtype=float64) needs "
            "jax_enable_x64 (the operator's accuracy bounds the solve)")
    N = len(nodes)
    nodes_d = jnp.asarray(nodes, dtype=op_dtype)
    normals_d = jnp.asarray(normals, dtype=op_dtype)
    w_d = jnp.asarray(weights, dtype=op_dtype)

    M = kernels.stresslet_times_normal_blocked(nodes_d, normals_d, eta)

    def sv(k):
        e = jnp.zeros((N, 3), dtype=op_dtype).at[:, k].set(w_d)
        return kernels.stresslet_times_normal_times_density(
            nodes_d, normals_d, e, eta)

    M = kernels.subtract_singularity_columns(M, (sv(0), sv(1), sv(2)), w_d)
    d = jnp.arange(3 * N, dtype=jnp.int32)
    M = M.at[d, d].add(-jnp.repeat(1.0 / w_d, 3))
    M = M + jnp.outer(normals_d.reshape(-1), normals_d.reshape(-1))
    M_inv = block_inv(M.astype(inv_dtype))
    return M, M_inv


def make_state(nodes, normals, weights, operator, M_inv, dtype=jnp.float64,
               precond_dtype=None, put=None) -> PeripheryState:
    """``precond_dtype`` stores M_inv (the preconditioner — accuracy does not
    matter) in a lower precision, halving its HBM footprint in mixed mode.
    ``put(host_array, dtype)`` hands each leaf to the device(s) (default:
    the whole of it to the default device; a mesh run's builder divides
    every leaf by rows, `parallel.mesh.rows_to_shards`)."""
    if put is None:
        def put(a, dt):
            return jnp.asarray(a, dtype=dt)
    N = len(nodes)
    return PeripheryState(
        nodes=put(nodes, dtype),
        normals=put(normals, dtype),
        weights=put(weights, dtype),
        M_inv=put(M_inv, precond_dtype or dtype),
        stresslet_plus_complementary=put(operator, dtype),
        density=put(np.zeros(3 * N), dtype),
    )


def grow_capacity(shell: PeripheryState, new_n: int) -> PeripheryState:
    """Shell state padded to ``new_n`` quadrature rows (masked inert).

    The shell leg of skelly-bucket's capacity discipline: padded rows
    replicate node 0's position (silent sources — their normals are zero,
    so the double-layer density f_dl vanishes there; exact-coincidence
    pairs are dropped by the kernels anyway), weigh zero, and both dense
    operators grow block-diagonally with the identity — so the padded
    system's inverse IS the padded inverse and padded density entries
    solve to exact zero. ``new_n == n_nodes`` still attaches the mask so
    an exact-fit scene shares its bucket's pytree structure.
    """
    n = shell.n_nodes
    if new_n < n:
        raise ValueError(
            f"periphery.grow_capacity: new_n {new_n} below current shell "
            f"size {n} (capacity never shrinks)")
    mask = np.zeros(new_n, dtype=bool)
    live = (np.asarray(shell.node_mask) if shell.node_mask is not None
            else np.ones(n, dtype=bool))
    mask[:n] = live
    pad = new_n - n
    if pad == 0:
        return shell._replace(node_mask=jnp.asarray(mask))

    def pad_rows(a):
        a = np.asarray(a)
        fill = np.repeat(a[:1], pad, axis=0)
        return np.concatenate([a, fill], axis=0)

    def pad_op(m):
        m = np.asarray(m)
        out = np.eye(3 * new_n, dtype=m.dtype)
        out[:3 * n, :3 * n] = m
        return out

    dtype = shell.nodes.dtype
    normals = np.concatenate(
        [np.asarray(shell.normals), np.zeros((pad, 3))], axis=0)
    return PeripheryState(
        nodes=jnp.asarray(pad_rows(shell.nodes), dtype=dtype),
        normals=jnp.asarray(normals, dtype=dtype),
        weights=jnp.asarray(np.concatenate(
            [np.asarray(shell.weights), np.zeros(pad)]), dtype=dtype),
        M_inv=jnp.asarray(pad_op(shell.M_inv), dtype=shell.M_inv.dtype),
        stresslet_plus_complementary=jnp.asarray(
            pad_op(shell.stresslet_plus_complementary),
            dtype=shell.stresslet_plus_complementary.dtype),
        density=jnp.asarray(np.concatenate(
            [np.asarray(shell.density), np.zeros(3 * pad)]), dtype=dtype),
        node_mask=jnp.asarray(mask))


# ------------------------------------------------------------------ operators

@jax.named_scope("shell")
def matvec(shell: PeripheryState, x, v_on_shell):
    """A_shell x = (S + N) x + v (`periphery.cpp:38-47`); v is [N, 3].

    Padded quadrature rows (``node_mask``) drop their v contribution so
    they stay on the identity — the flow evaluators produce garbage values
    at the padded placeholder targets."""
    if shell.node_mask is not None:
        v_on_shell = jnp.where(shell.node_mask[:, None],
                               v_on_shell.reshape(-1, 3), 0.0)
    return (_apply_operator(shell.stresslet_plus_complementary, x)
            + v_on_shell.reshape(-1))


#: row block of a large float64 operator application (`_apply_operator`)
_F64_ROW_BLOCK = 2048


def _row_blocked(op) -> bool:
    return op.dtype == jnp.float64 and op.shape[0] > 2 * _F64_ROW_BLOCK


def describe(shell: PeripheryState, chips: int = 1) -> dict:
    """What a run holds of a shell and how `_apply_operator` multiplies its
    float64 operator, for `System._announce_periphery` (shapes and dtypes
    only: a traced state serves). ``shell`` is what ONE chip holds: the
    whole, or inside the mesh step its share of a shell divided by rows over
    ``chips`` devices — the shell's own numbers are then the share's times
    ``chips``, and the product's policy is the share's (it is the share's
    rows that `_apply_operator` sees there)."""
    out = {"nodes": shell.n_nodes * chips}
    for name, m in (("operator", shell.stresslet_plus_complementary),
                    ("m_inv", shell.M_inv)):
        dtype = jnp.dtype(m.dtype)
        out.update({name: f"{m.shape[0] * chips}x{m.shape[1]}",
                    name + "_dtype": dtype.name,
                    name + "_bytes": m.size * dtype.itemsize * chips})
    blocked = _row_blocked(shell.stresslet_plus_complementary)
    return dict(out, f64_product="row_blocks" if blocked else "whole",
                row_block=_F64_ROW_BLOCK if blocked else 0, chips=chips,
                rows_per_chip=shell.stresslet_plus_complementary.shape[0])


@jax.named_scope("shell")
def _apply_operator(op, x):
    """``op @ x``; a large float64 operator goes in row blocks.

    A TPU emulates an f64 dot through an ``[8, rows, cols]`` f32 expansion
    of the matrix: 9.7 GB at 6,000 shell nodes, which with the operator and
    its inverse is more than a 16 GB chip holds (the walkthrough's step did
    not compile there, PR 22). Blocking bounds that temporary at
    ``[8, _F64_ROW_BLOCK, cols]``; each row's dot product is unchanged. The
    last block starts early enough to stay in range, so trailing rows may
    be computed twice — to the same values."""
    rows = op.shape[0]
    if not _row_blocked(op):
        return op @ x
    block = _F64_ROW_BLOCK

    def body(i, y):
        start = jnp.minimum(i * block, rows - block)
        rows_i = jax.lax.dynamic_slice_in_dim(op, start, block, axis=0)
        return jax.lax.dynamic_update_slice_in_dim(y, rows_i @ x, start,
                                                   axis=0)

    y0 = jnp.zeros((rows,) + x.shape[1:], dtype=jnp.result_type(op, x))
    return jax.lax.fori_loop(0, -(-rows // block), body, y0)


@jax.named_scope("shell")
def apply_preconditioner(shell: PeripheryState, x):
    """P^-1 x = M_inv x (`periphery.cpp:21-29`); applied in M_inv's (possibly
    lower) precision and cast back."""
    return (shell.M_inv @ x.astype(shell.M_inv.dtype)).astype(x.dtype)


def update_RHS(v_on_shell, node_mask=None):
    """RHS = -v_on_shell (`periphery.cpp:86`); padded quadrature rows
    (``node_mask``) get exact-zero RHS so their density solves to zero."""
    if node_mask is not None:
        v_on_shell = jnp.where(node_mask[:, None],
                               v_on_shell.reshape(-1, 3), 0.0)
    return -v_on_shell.reshape(-1)


@jax.named_scope("shell")
def flow(shell: PeripheryState, r_trg, density, eta, *, evaluator: str = "direct",
         mesh=None, impl: str = "exact", ewald_plan=None, ewald_anchors=None,
         pair=None, pair_anchors=None):
    """Shell -> target velocities via the double-layer stresslet
    (`periphery.cpp:55-79`): f_dl = 2 eta n (x) rho.

    Evaluator selection rides a `ops.evaluator.PairEvaluator` spec
    (``pair`` + traced ``pair_anchors``) or the legacy loose kwargs.
    ``evaluator="ring"`` (with a mesh) rotates shell-node source blocks around
    the ICI ring — the same pair-evaluator seam as `fibers.container.flow`
    (reference: one evaluator serves all components, `kernels.hpp:78-122`).
    Zero-strength far-point pads make the node count mesh-divisible; callers
    pad the *target* rows (see `System._ring_pad_targets`).

    ``evaluator="ewald"`` (with a plan covering shell nodes + targets) sums
    the double layer in O(N log N) via the free-space Ewald stresslet,
    ``evaluator="tree"`` via the barycentric-treecode stresslet, and
    ``evaluator="spectral"`` via the periodic particle-mesh stresslet
    (`ops.spectral.stresslet_spectral`) — the
    reference's one-evaluator-serves-all design (`periphery.cpp:337-352`
    routes the shell's stresslet through the FMM). The shell's
    SELF-interaction is not computed here in any mode: `System._apply_matvec`
    evaluates this flow at fiber/body rows only, the self block living in
    the dense stored operator.
    """
    from ..ops.evaluator import resolve

    evaluator, impl, ewald_plan, ewald_anchors, pair_anchors = resolve(
        pair, pair_anchors, r_trg.dtype, evaluator, impl, ewald_plan,
        ewald_anchors)
    rho = density.reshape(-1, 3)
    f_dl = 2.0 * eta * shell.normals[:, :, None] * rho[:, None, :]
    if (pair is not None and evaluator == "tree" and pair.plan is not None):
        from ..ops import treecode as tcode

        if pair.plan.depth == 0:
            return kernels.stresslet_direct(shell.nodes, r_trg, f_dl, eta,
                                            impl=impl)
        return tcode._stresslet_tree_impl(pair.plan, pair_anchors,
                                          shell.nodes, r_trg, f_dl, eta)
    if evaluator == "ewald" and ewald_plan is not None:
        from ..ops import ewald as ew

        if ewald_anchors is None:
            ewald_anchors = ew.plan_anchors(ewald_plan, r_trg.dtype)
            ewald_plan = ew.strip_anchors(ewald_plan)
        vel = ew._stresslet_ewald_impl(ewald_plan, ewald_anchors,
                                       shell.nodes, r_trg, f_dl)
        # the screened kernels scale as 1/eta and the plan baked plan.eta in
        return vel * (ewald_plan.eta / eta)
    if (pair is not None and evaluator == "spectral"
            and pair.plan is not None):
        from ..ops import spectral as spec

        vel = spec._stresslet_spectral_impl(pair.plan, pair_anchors,
                                            shell.nodes, r_trg, f_dl)
        return vel * (pair.plan.eta / eta)
    if evaluator == "ring" and mesh is not None:
        src = shell.nodes
        pad = (-src.shape[0]) % mesh.size
        if pad:
            src = jnp.concatenate(
                [src, jnp.full((pad, 3), 1e7, dtype=src.dtype)], axis=0)
            f_dl = jnp.concatenate(
                [f_dl, jnp.zeros((pad, 3, 3), dtype=f_dl.dtype)], axis=0)
        if impl in ("df", "pallas_df"):
            # see fibers.container.flow_multi: "df" = XLA blocks,
            # "pallas_df" = fused Pallas DF tile per chip; cast back to the
            # target dtype like the direct seam
            from ..parallel.ring import ring_stresslet_df

            return ring_stresslet_df(src, r_trg, f_dl, eta, mesh=mesh,
                                     impl=impl).astype(r_trg.dtype)
        from ..parallel.ring import ring_stresslet

        return ring_stresslet(src, r_trg, f_dl, eta, mesh=mesh, impl=impl)
    return kernels.stresslet_direct(shell.nodes, r_trg, f_dl, eta, impl=impl)


@jax.named_scope("shell")
def flow_local(shell: PeripheryState, r_loc, r_rep, density, eta, *,
               axis_name, n_dev: int, impl: str = "exact"):
    """`flow` for callers ALREADY INSIDE a `shard_map` over the fiber axis
    (`parallel.spmd`): ``shell`` is this shard's row block (nodes/normals
    node-aligned with ``density``'s [3*N/D] rows).

    Like `fibers.container.flow_multi_local`, two target classes:
    ``r_loc`` (shard-resident rows — fiber nodes) accumulates over the
    rotating shell source blocks with `lax.ppermute`; ``r_rep``
    (replicated rows — body nodes) is one local source-block partial for
    the caller to `psum` — the replication discipline (docs/parallel.md,
    enforced by the `replication` audit check: ringing replicated rows is
    the ring-order-accumulation finding). Returns ``(v_loc,
    v_rep_partial)``. The shell
    SELF-interaction is not computed in any mode — it lives in the dense
    stored operator (`System._apply_matvec`)."""
    from ..parallel.ring import ring_flow_local

    rho = density.reshape(-1, 3)
    f_dl = 2.0 * eta * shell.normals[:, :, None] * rho[:, None, :]
    src = shell.nodes

    v_loc = ring_flow_local("stresslet", impl, r_loc, src, f_dl, eta,
                            axis_name=axis_name, n_dev=n_dev, ring=True)
    v_rep = (ring_flow_local("stresslet", impl, r_rep, src, f_dl, eta,
                             axis_name=axis_name, n_dev=n_dev, ring=False)
             if r_rep is not None else None)
    return v_loc, v_rep


# ------------------------------------------------- shape-specific interactions

def signed_clearance(shape: PeripheryShape, points):
    """[n] signed node-periphery clearance: positive inside (clear of the
    wall), NEGATIVE once a point crosses it — so penetration is visible
    as a magnitude, unlike `check_collision`'s bool (the flight
    recorder's ``min_clearance`` diagnostic, obs.flight).

    sphere: ``radius - |p|``; ellipsoid: the radial distance to the
    cortex point of `check_collision`'s comparison, ``|r_cortex| - |p|``
    (exact on the axes, a radial-ray approximation elsewhere — a
    diagnostic, not a force); generic: +inf (no wall physics, stub
    parity with the zero steric force)."""
    if shape.kind == "sphere":
        return shape.radius - jnp.linalg.norm(points, axis=-1)
    if shape.kind == "ellipsoid":
        a, b, c = shape.abc
        abc = jnp.asarray(shape.abc, dtype=points.dtype)
        r_scaled = points / abc
        r_scaled_mag = jnp.linalg.norm(r_scaled, axis=-1)
        phi = jnp.arctan2(r_scaled[:, 1], r_scaled[:, 0] + 1e-12)
        theta = jnp.arccos(jnp.clip(r_scaled[:, 2] / (1e-12 + r_scaled_mag),
                                    -1, 1))
        sin_t = jnp.sin(theta)
        r_cortex = jnp.stack([a * sin_t * jnp.cos(phi),
                              b * sin_t * jnp.sin(phi),
                              c * jnp.cos(theta)], axis=-1)
        return (jnp.linalg.norm(r_cortex, axis=-1)
                - jnp.linalg.norm(points, axis=-1))
    return jnp.full(points.shape[:-1], jnp.inf, dtype=points.dtype)


def check_collision(shape: PeripheryShape, points, threshold):
    """True if any point crosses the shell (vectorized over [n, 3] points).

    sphere: any |p| >= radius - threshold (`periphery.cpp:126-133`)
    ellipsoid: radial comparison against the threshold-shrunk cortex point
    (`periphery.cpp:204-224`); generic: never collides (stub parity,
    `periphery.cpp:312-319`).
    """
    if shape.kind == "sphere":
        r2 = jnp.sum(points**2, axis=-1)
        return jnp.any(r2 >= (shape.radius - threshold) ** 2)
    if shape.kind == "ellipsoid":
        a, b, c = shape.abc
        abc = jnp.asarray(shape.abc, dtype=points.dtype)
        r_scaled = points / abc
        r_scaled_mag = jnp.linalg.norm(r_scaled, axis=-1)
        phi = jnp.arctan2(r_scaled[:, 1], r_scaled[:, 0] + 1e-12)
        theta = jnp.arccos(jnp.clip(r_scaled[:, 2] / (1e-12 + r_scaled_mag), -1, 1))
        sin_t = jnp.sin(theta)
        r_cortex = jnp.stack([(a - threshold) * sin_t * jnp.cos(phi),
                              (b - threshold) * sin_t * jnp.sin(phi),
                              (c - threshold) * jnp.cos(theta)], axis=-1)
        return jnp.any(jnp.sum(points**2, axis=-1) >= jnp.sum(r_cortex**2, axis=-1))
    return jnp.asarray(False)


def fiber_steric_force(shape: PeripheryShape, points, f_0, l_0, skip_first):
    """Exponential repulsion wall force on fiber nodes [n, 3] -> [n, 3].

    sphere: f = f_0 * dr/|dr| * exp(-(R - r)/l_0) for r < R
    (`periphery.cpp:140-162`); ellipsoid analogue (`periphery.cpp:232-263`);
    generic: zero (stub parity). ``skip_first`` masks the clamped minus-end node.
    """
    n = points.shape[0]
    mask = jnp.arange(n, dtype=jnp.int32) >= jnp.where(skip_first, 1, 0)
    if shape.kind == "sphere":
        r_mag = jnp.linalg.norm(points, axis=-1)
        safe_r = jnp.where(r_mag > 0, r_mag, 1.0)
        u_hat = points / safe_r[:, None]
        dr = points - u_hat * shape.radius
        d = jnp.linalg.norm(dr, axis=-1)
        safe_d = jnp.where(d > 0, d, 1.0)
        f = f_0 * dr / safe_d[:, None] * jnp.exp(-(shape.radius - r_mag) / l_0)[:, None]
        inside = (r_mag < shape.radius) & mask
        return jnp.where(inside[:, None], f, 0.0)
    if shape.kind == "ellipsoid":
        a, b, c = shape.abc
        abc = jnp.asarray(shape.abc, dtype=points.dtype)
        r_scaled = points / abc
        r_scaled_mag = jnp.linalg.norm(r_scaled, axis=-1)
        r_mag = jnp.linalg.norm(points, axis=-1)
        phi = jnp.arctan2(r_scaled[:, 1], r_scaled[:, 0] + 1e-12)
        theta = jnp.arccos(jnp.clip(r_scaled[:, 2] / (1e-12 + r_scaled_mag), -1, 1))
        sin_t = jnp.sin(theta)
        r_cortex = jnp.stack([a * sin_t * jnp.cos(phi),
                              b * sin_t * jnp.sin(phi),
                              c * jnp.cos(theta)], axis=-1)
        r_cortex_mag = jnp.linalg.norm(r_cortex, axis=-1)
        dr = points - r_cortex
        d = jnp.linalg.norm(dr, axis=-1)
        safe_d = jnp.where(d > 0, d, 1.0)
        f = f_0 * dr / safe_d[:, None] * jnp.exp(-(r_cortex_mag - r_mag) / l_0)[:, None]
        inside = (r_mag < r_cortex_mag) & mask
        return jnp.where(inside[:, None], f, 0.0)
    return jnp.zeros_like(points)
