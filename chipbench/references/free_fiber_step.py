"""The plain reference: one implicit step of the slender-body fiber system,
written straight from the published equations, in float64, matrix-free.

Nothing here imports the program or takes anything it made. Given a state
BEFORE a step (node positions) and the answer the program produced for that
step (new positions and tensions, as its trajectory frame holds them), it
assembles the right-hand side b and applies the coupled operator A to the
answer on its own, and returns ||b - A x|| / ||b|| — the explicit residual
the configuration's ``gmres_tol`` bounds. A solve that dropped pairs, used
another operator, skipped its float64 refinement, or did not move the state
leaves a residual far above that tolerance HERE, whatever its own residual
says.

Equations (SkellySim, `fiber_finite_difference.cpp`; Nazockdast et al.,
J. Comput. Phys. 329 (2017)): each fiber has unknowns X [n, 3] and tension
T [n] at the new time; xs, xss, xsss are arclength derivatives of the OLD
positions. With c0 = -log(e eps^2) / (8 pi eta), c1 = 2 / (8 pi eta),
eps = radius / length, E the bending rigidity:

  position rows   beta/dt X + E (c0+c1) X'''' + E (c0-c1) xs (xs . X'''')
                  - 2 c0 xs T' - (c0+c1) xss T  - v            = rhs_x
  tension row     sum_k [-(c1+7c0) E xss_k X_k'''' - 6 c0 E xsss_k X_k'''
                  - penalty xs_k X_k'] - 2 c0 T'' + (c0+c1)|xss|^2 T
                  - (xs . v)'                                   = rhs_T

downsampled to n-4 / n-2 interior points by barycentric interpolation, plus
14 boundary rows (free ends: force and torque balance). v is the flow at
the fiber's nodes from the forces of all OTHER fibers
(f = -E X'''' + (T xs)', trapezoid weights), summed with the Oseen tensor
over every pair of nodes.

Derivative matrices: finite differences on n equispaced points with
5/6/7/8-point stencils for D1..D4 (the stencil windows are upstream's,
`utils.cpp:54-68`), weights solved exactly in rational arithmetic.
"""

from __future__ import annotations

from fractions import Fraction
from functools import lru_cache
from math import factorial

import numpy as np

PENALTY = 500.0      # tension penalty (fiber_finite_difference.hpp:31)
BETA_TSTEP = 1.0     # fiber_finite_difference.hpp:36


# -------------------------------------------------------- derivative matrices

def _fd_weights_exact(offsets: list[int], order: int) -> list[Fraction]:
    """Weights w with sum_j w_j f(offset_j) = f^(order)(0) exactly for
    polynomials up to degree len(offsets)-1, unit spacing: solve the
    Vandermonde system in rationals by Gauss-Jordan elimination."""
    m = len(offsets)
    rows = [[Fraction(o) ** k for o in offsets]
            + [Fraction(factorial(order) if k == order else 0)]
            for k in range(m)]
    for col in range(m):
        piv = next(r for r in range(col, m) if rows[r][col] != 0)
        rows[col], rows[piv] = rows[piv], rows[col]
        rows[col] = [v / rows[col][col] for v in rows[col]]
        for r in range(m):
            if r != col and rows[r][col] != 0:
                fac = rows[r][col]
                rows[r] = [a - fac * b for a, b in zip(rows[r], rows[col])]
    return [rows[r][m] for r in range(m)]


def fd_matrix(n: int, order: int, n_stencil: int) -> np.ndarray:
    """d^order/d alpha^order on alpha = linspace(-1, 1, n)."""
    h = 2.0 / (n - 1)
    half = (n_stencil - 1) // 2
    D = np.zeros((n, n))
    for i in range(n):
        if i < half:
            lo = 0
        elif i > n - half - 2:
            lo = n - n_stencil
        else:
            lo = i - half
        w = _fd_weights_exact([j - i for j in range(lo, lo + n_stencil)],
                              order)
        D[i, lo:lo + n_stencil] = [float(v) for v in w]
    return D / h ** order


def barycentric(x: np.ndarray, y: np.ndarray) -> np.ndarray:
    """Interpolation matrix from equispaced x to y with the trapezoid
    barycentric weights (+-1, halved at the ends)."""
    n = x.size
    w = (-1.0) ** np.arange(n)
    w[0] *= 0.5
    w[-1] *= 0.5
    P = np.zeros((y.size, n))
    for j, yj in enumerate(y):
        diff = yj - x
        hit = np.abs(diff) <= np.finfo(float).eps
        if hit.any():
            P[j, np.argmax(hit)] = 1.0
        else:
            t = w / diff
            P[j] = t / t.sum()
    return P


@lru_cache(maxsize=None)
def fiber_matrices(n: int) -> dict:
    alpha = np.linspace(-1.0, 1.0, n)
    roots = 2 * (0.5 + np.arange(n - 4)) / (n - 4) - 1
    tens = 2 * (0.5 + np.arange(n - 2)) / (n - 2) - 1
    w0 = np.full(n, 2.0)
    w0[[0, -1]] = 1.0
    return {"D1": fd_matrix(n, 1, 5), "D2": fd_matrix(n, 2, 6),
            "D3": fd_matrix(n, 3, 7), "D4": fd_matrix(n, 4, 8),
            "P_X": barycentric(alpha, roots), "P_T": barycentric(alpha, tens),
            "w0": w0 / (n - 1)}


# ------------------------------------------------------------- pairwise flows

def oseen_flow_other_fibers(r, wf, fiber_id, eta, block=512, xp=None):
    """u_i = 1/(8 pi eta) sum_j [ wf_j / |d| + d (d . wf_j) / |d|^3 ] over
    every node j of ANOTHER fiber (d = r_i - r_j), float64, in blocks of
    target rows. ``xp`` is numpy, or jax.numpy where float64 is enabled —
    the same plain formula either way."""
    if xp is None:
        xp = np
    r = xp.asarray(r, dtype=xp.float64)
    wf = xp.asarray(wf, dtype=xp.float64)
    fid = xp.asarray(fiber_id)
    out = []
    sx, sy, sz = r[:, 0], r[:, 1], r[:, 2]
    fx, fy, fz = wf[:, 0], wf[:, 1], wf[:, 2]
    for lo in range(0, r.shape[0], block):
        hi = min(lo + block, r.shape[0])
        dx = sx[lo:hi, None] - sx[None, :]
        dy = sy[lo:hi, None] - sy[None, :]
        dz = sz[lo:hi, None] - sz[None, :]
        other = fid[lo:hi, None] != fid[None, :]
        r2 = dx * dx + dy * dy + dz * dz
        rinv = xp.where(other, 1.0 / xp.sqrt(xp.where(other, r2, 1.0)), 0.0)
        df = (dx * fx[None, :] + dy * fy[None, :] + dz * fz[None, :]) \
            * rinv ** 3
        out.append(xp.stack([
            (rinv * fx[None, :] + df * dx).sum(axis=1),
            (rinv * fy[None, :] + df * dy).sum(axis=1),
            (rinv * fz[None, :] + df * dz).sum(axis=1)], axis=1))
    return xp.concatenate(out, axis=0) / (8.0 * np.pi * eta)


# ----------------------------------------------------------- the fiber system

class FiberStep:
    """The linear system of one step for ``F`` free fibers of ``n`` nodes.

    ``x_old`` [F, n, 3]; per-fiber ``length``, ``bending``, ``radius``,
    ``force_scale`` [F]. ``flow`` is the pairwise evaluator
    (`oseen_flow_other_fibers` bound to numpy or jax.numpy)."""

    def __init__(self, x_old, length, bending, radius, force_scale, *, dt,
                 eta, flow, explicit_flow=None):
        self.x = np.asarray(x_old, dtype=np.float64)
        F, n, _ = self.x.shape
        self.F, self.n = F, n
        self.L = np.broadcast_to(np.asarray(length, float), (F,))
        self.E = np.broadcast_to(np.asarray(bending, float), (F,))
        rad = np.broadcast_to(np.asarray(radius, float), (F,))
        self.fs = np.broadcast_to(np.asarray(force_scale, float), (F,))
        self.dt, self.eta, self.flow = float(dt), float(eta), flow
        eps = rad / self.L
        self.c0 = -np.log(np.e * eps ** 2) / (8 * np.pi * eta)
        self.c1 = np.full(F, 2.0 / (8 * np.pi * eta))
        self.m = fiber_matrices(n)
        s = 2.0 / self.L
        self.xs = self._d("D1", self.x, s)
        self.xss = self._d("D2", self.x, s ** 2)
        self.xsss = self._d("D3", self.x, s ** 3)
        self.v_explicit = (np.zeros_like(self.x) if explicit_flow is None
                           else np.asarray(explicit_flow, float))
        self.fiber_id = np.repeat(np.arange(F), n)

    def _d(self, name, a, scale):
        """Apply a derivative matrix along the node axis, per-fiber scale."""
        out = np.einsum("ij,fj...->fi...", self.m[name], a)
        return out * scale.reshape((-1,) + (1,) * (out.ndim - 1))

    def _down(self, xyz, t):
        """[F, n, 3], [F, n] -> [F, 4n - 14] interior rows."""
        px = np.einsum("ij,fjk->fki", self.m["P_X"], xyz)      # [F, 3, n-4]
        pt = np.einsum("ij,fj->fi", self.m["P_T"], t)          # [F, n-2]
        return np.concatenate([px.reshape(self.F, -1), pt], axis=1)

    # the right-hand side ---------------------------------------------------
    def rhs(self):
        s = 2.0 / self.L
        c0, c1 = self.c0[:, None], self.c1[:, None]
        xs, xss, v = self.xs, self.xss, self.v_explicit
        f = self.fs[:, None, None] * xs            # motor force density
        xsf = np.sum(xs * f, axis=2, keepdims=True)
        rx = (self.x / self.dt + v
              + c0[..., None] * (f + xs * xsf) + c1[..., None] * (f - xs * xsf))
        rt = (-PENALTY + np.sum(xs * self._d("D1", v, s), axis=2)
              + 2.0 * c0 * np.sum(xs * self._d("D1", f, s), axis=2)
              + (c0 - c1) * np.sum(xss * f, axis=2))
        # free ends carry no external force: the 14 boundary rows are zero
        return np.concatenate([self._down(rx, rt),
                               np.zeros((self.F, 14))], axis=1)

    # the operator applied to an answer --------------------------------------
    def weighted_force(self, X, T):
        """Quadrature-weighted force of an answer on the nodes [F, n, 3]:
        f = -E X'''' + xss T + xs T', trapezoid weights L/2 w0."""
        X = np.asarray(X, float)
        T = np.asarray(T, float)
        s = 2.0 / self.L
        f = (-self.E[:, None, None] * self._d("D4", X, s ** 4)
             + self.xss * T[..., None]
             + self.xs * self._d("D1", T, s)[..., None])
        return (0.5 * self.L[:, None] * self.m["w0"][None, :])[..., None] * f

    def apply(self, X, T, v_other=None):
        """A [X, T] with the implicit flow of the other fibers, plus
        ``v_other`` [F, n, 3]: the implicit flow of whatever else the
        scene holds (a shell, bodies)."""
        X = np.asarray(X, float)
        T = np.asarray(T, float)
        F, n = self.F, self.n
        s = 2.0 / self.L
        E = self.E[:, None]
        c0, c1 = self.c0[:, None], self.c1[:, None]
        xs, xss, xsss = self.xs, self.xss, self.xsss
        X1, X2 = self._d("D1", X, s), self._d("D2", X, s ** 2)
        X3, X4 = self._d("D3", X, s ** 3), self._d("D4", X, s ** 4)
        T1, T2 = self._d("D1", T, s), self._d("D2", T, s ** 2)

        # the flow the answer's forces drive on the other fibers
        wf = self.weighted_force(X, T)
        v = np.asarray(self.flow(self.x.reshape(-1, 3), wf.reshape(-1, 3),
                                 self.fiber_id, self.eta)).reshape(F, n, 3)
        if v_other is not None:
            v = v + np.asarray(v_other, float)

        xsX4 = np.sum(xs * X4, axis=2, keepdims=True)
        ax = (BETA_TSTEP / self.dt * X
              + (E * (c0 + c1))[..., None] * X4
              + (E * (c0 - c1))[..., None] * xs * xsX4
              - 2.0 * c0[..., None] * xs * T1[..., None]
              - (c0 + c1)[..., None] * xss * T[..., None]
              - v)
        at = (np.sum(-((c1 + 7.0 * c0) * E)[..., None] * xss * X4
                     - (6.0 * c0 * E)[..., None] * xsss * X3
                     - PENALTY * xs * X1, axis=2)
              - 2.0 * c0 * T2 + (c0 + c1) * np.sum(xss ** 2, axis=2) * T
              - self._d("D1", np.sum(xs * v, axis=2), s))
        interior = self._down(ax, at)

        # 14 boundary rows, both ends free (force and torque balance)
        bc = np.zeros((F, 14))
        bc[:, 0:3] = E * X3[:, 0] - xs[:, 0] * T[:, 0:1]
        bc[:, 3] = (-self.E * np.sum(xss[:, 0] * X2[:, 0], axis=1) - T[:, 0]
                    + np.sum(v[:, 0] * xs[:, 0], axis=1))
        bc[:, 4:7] = X2[:, 0]
        bc[:, 7:10] = -E * X3[:, -1] + xs[:, -1] * T[:, -1:]
        bc[:, 10] = self.E * np.sum(xss[:, -1] * X2[:, -1], axis=1) + T[:, -1]
        bc[:, 11:14] = X2[:, -1]
        return np.concatenate([interior, bc], axis=1)

    def residual(self, X, T) -> float:
        b = self.rhs()
        return float(np.linalg.norm(b - self.apply(X, T))
                     / np.linalg.norm(b))


# ------------------------------------------------- what `check.py` calls

def jax_flow(block: int = 512):
    """`oseen_flow_other_fibers` through jax.numpy in float64 (x64 has to be
    on), one jitted program over blocks of target rows: the same formula on
    whatever device JAX has — on the chip a 16,384-node sum takes a second
    where numpy takes most of a minute."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(r, wf, fid, eta):
        n = r.shape[0]
        nb = -(-n // block)
        pad = nb * block - n
        # padded target rows belong to no fiber; their flows are cut off
        rt = jnp.concatenate([r, jnp.zeros((pad, 3), r.dtype)])
        ft = jnp.concatenate([fid, jnp.full((pad,), -1, fid.dtype)])
        sx, sy, sz = r[:, 0], r[:, 1], r[:, 2]
        fx, fy, fz = wf[:, 0], wf[:, 1], wf[:, 2]

        def one(args):
            tb, tid = args
            dx = tb[:, 0:1] - sx[None, :]
            dy = tb[:, 1:2] - sy[None, :]
            dz = tb[:, 2:3] - sz[None, :]
            other = tid[:, None] != fid[None, :]
            r2 = dx * dx + dy * dy + dz * dz
            rinv = jnp.where(other, 1.0 / jnp.sqrt(jnp.where(other, r2, 1.0)),
                             0.0)
            df = (dx * fx[None, :] + dy * fy[None, :] + dz * fz[None, :]) \
                * rinv ** 3
            return jnp.stack([(rinv * fx[None, :] + df * dx).sum(axis=1),
                              (rinv * fy[None, :] + df * dy).sum(axis=1),
                              (rinv * fz[None, :] + df * dz).sum(axis=1)],
                             axis=1)

        u = jax.lax.map(one, (rt.reshape(nb, block, 3),
                              ft.reshape(nb, block)))
        return u.reshape(nb * block, 3)[:n] / (8.0 * jnp.pi * eta)

    def flow(r, wf, fiber_id, eta):
        if not jax.config.jax_enable_x64:
            raise RuntimeError("the reference needs jax_enable_x64")
        out = run(jnp.asarray(r, jnp.float64), jnp.asarray(wf, jnp.float64),
                  jnp.asarray(fiber_id, jnp.int32), jnp.float64(eta))
        return np.asarray(out, dtype=np.float64)

    return flow


def step_residual(cfg, pre, post, *, dt, eta, flow=None) -> float:
    """||b - A x|| / ||b|| of the answer ``post`` for the step that began
    at ``pre`` (`run.snapshot` dicts: fibers only in this reference)."""
    for key in ("bodies", "shell_density"):
        if key in pre:
            raise ValueError(f"this reference has no {key}: the "
                             "configuration needs another")
    groups = pre["fibers"]
    cat = lambda k, gs: np.concatenate([g[k] for g in gs])  # noqa: E731
    step = FiberStep(cat("x", groups), cat("length", groups),
                     cat("bending_rigidity", groups), cat("radius", groups),
                     cat("force_scale", groups), dt=dt, eta=eta,
                     flow=flow or jax_flow())
    return step.residual(cat("x", post["fibers"]),
                         cat("tension", post["fibers"]))
