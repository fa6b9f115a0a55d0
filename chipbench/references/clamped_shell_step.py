"""The plain reference of a step of clamped, force-carrying fibers inside an
ellipsoidal shell and NO body (`examples/ellipsoid`), float64, matrix-free.

It imports nothing of the program and takes none of its operators. It loads
its two sibling references by path, as `coupled_step.py` loads
`free_fiber_step.py`, and edits neither: the derivative matrices and
`FiberStep`'s interior rows and free-end rows from the one, the pairwise
`Sums` from the other. Given the state BEFORE a step and the program's
answer, it builds the step's linear system and returns

* ``ref_residual``          ||b - A x|| / ||b|| of the whole vector, what
                            ``gmres_tol`` bounds;
* ``ref_residual_shell``    the shell's rows against their own right-hand
                            side (the whole norm is nearly all fiber rows);
* ``ref_residual_fiber_bc`` the 14 boundary rows of every fiber against
                            their own right-hand side: a wrong clamped row
                            cannot hide under the interior rows.

Equations (SkellySim `fiber_finite_difference.cpp:347-513`, `periphery.cpp`,
`system.cpp prep_state_for_solver / apply_matvec`; Nazockdast et al. 2017).

**Fiber rows.** Interior rows are `FiberStep`'s. A fiber whose
``minus_clamped`` flag (read per fiber from the snapshot, never assumed) is
set has, at its minus end, in place of the force and torque balance:

  velocity          beta/dt X_0 = x_0 / dt                         (3 rows)
  tension           6 E c0 xss_0 . X'''_0 + 2 c0 T'_0 + xs_0 . v_0
                    = -xs_0 . v_0^explicit - 2 c0 xs_0 . f_0^wall  (1 row)
  angular velocity  beta/dt X'_0 = xs_0 / dt                       (3 rows)

with v_0 the flow at node 0 that the ANSWER's forces and the shell's density
drive (implicit, on the left) and v_0^explicit the flow of the wall forces
(on the right). The plus end stays free; a fiber whose ``plus_pinned`` flag
is set, that is bound to a body, or that is inactive, is refused: this
reference does not write those rows.

**Shell rows** are `coupled_step.py`'s second-kind rows (double layer,
singularity subtraction, null-space completion), summed pair by pair over
the given nodes. The quadrature is GIVEN (the precompute step's
discretisation, from the state the program built) and held to an
ELLIPSOID's facts (`check_quadrature`): nodes on (x/a')^2 + (y/b')^2 +
(z/c')^2 = 1 with (a', b', c') = 1.04 x the configuration's own semi-axes
(upstream inflates the node surface by 1.04, `precompute.py:34`, and lays
the fibers' minus ends on the surface SHRUNK by 1.04), normals unit and
along the surface's gradient, weights summing to the spheroid's area
(closed form, b = c) within 1e-3. The dense operator and its inverse, which
the program precomputed, are not taken.

**Flows.** Fibers -> shell: the Stokeslet of the answer's weighted force
f = -E X'''' + (T xs)'. Shell -> fibers: the double layer of the density.
Fiber -> fiber: the Stokeslet over every node of every OTHER fiber. The
motor force ``force_scale * xs`` is on the right-hand side, as in
`FiberStep.rhs`. No body, no explicit body flow.

**The steric wall force is NOT left out** (the issue that asked for this
file expected it to be: it read `periphery_interaction_flag`, which gates
post-processing only). Upstream's `prep_state_for_solver` (`system.cpp:422`)
and the program apply it on every solve: on every fiber node but a clamped
node 0, inside the wall,

  f^wall = f_0 (r - r_c) / |r - r_c| exp(-(|r_c| - |r|) / l_0),
  r_c = (a, b, c) * s / |s|,  s = r / (a, b, c)   (`periphery.cpp:232-263`)

with the configuration's own semi-axes and (f_0, l_0) = (20, 0.05) unless
the configuration's ``params`` says otherwise. On the first nodes of a
fiber laid 0.04 of the local radius inside the wall it is up to 0.59 in
`ellipsoid_256`'s scene, twelve times the motor force's 0.05. It enters
the right-hand side with the motor force, the free end's boundary rows,
and, through its Stokeslet, the explicit flow on the other fibers and on
the shell (the shell's whole right-hand side). Upstream adds 1e-12 inside
its `atan2` / `acos` of s; that is left out (a relative 1e-10 of the
force).

**Left out, and said.** The kernels' near-field regularisation (pairs
closer than 1e-5): in `ellipsoid_256`'s scene the closest fiber node to a
shell node is 0.3276 away (the minus ends stand on the surface shrunk by
1.04, the shell's nodes on the surface inflated by 1.04) and the closest
nodes of two fibers 0.0694 (`closest_pairs` states both for any scene), so
it never acts at t = 0, nor while the fibers move 1e-3 a step.
"""

from __future__ import annotations

import hashlib
import importlib.util
import os

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))

NODE_SCALE = 1.04    # node surface / attachment surface (precompute.py:34)
WALL_F0, WALL_L0 = 20.0, 0.05   # fiber_periphery_interaction's defaults


def _sibling(name: str):
    spec = importlib.util.spec_from_file_location(
        "chipbench_reference_" + name, os.path.join(_HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_FIB = _sibling("free_fiber_step")
_CPL = _sibling("coupled_step")


# ------------------------------------------------------------ the quadrature

def spheroid_area(a: float, b: float) -> float:
    """Area of the ellipsoid of semi-axes (a, b, b)."""
    if a == b:
        return 4.0 * np.pi * a * a
    if a > b:                                   # prolate
        e = np.sqrt(1.0 - (b / a) ** 2)
        return 2.0 * np.pi * b * b * (1.0 + a / (b * e) * np.arcsin(e))
    e = np.sqrt(1.0 - (a / b) ** 2)             # oblate
    return 2.0 * np.pi * b * b + np.pi * a * a / e * np.log((1 + e) / (1 - e))


def check_quadrature(nodes, normals, weights, abc) -> None:
    """The facts the given quadrature is held to: an ellipsoid's."""
    a, b, c = (NODE_SCALE * float(v) for v in abc)
    if b != c:
        raise ValueError("shell: the area has a closed form for b = c only")
    level = np.sum((nodes / np.array([a, b, c])) ** 2, axis=1)
    if np.abs(level - 1.0).max() > 1e-9:
        raise ValueError("shell: nodes are not on the ellipsoid of semi-axes "
                         f"{NODE_SCALE} x {tuple(abc)}")
    grad = nodes / np.array([a, b, c]) ** 2
    grad /= np.linalg.norm(grad, axis=1, keepdims=True)
    along = np.abs(np.sum(normals * grad, axis=1))
    if (np.abs(np.linalg.norm(normals, axis=1) - 1).max() > 1e-9
            or np.abs(along - 1).max() > 1e-9):
        raise ValueError("shell: normals are not unit and along the "
                         "surface's gradient")
    area = spheroid_area(a, b)
    if abs(weights.sum() / area - 1) > 1e-3:
        raise ValueError(f"shell: weights sum to {weights.sum()}, the "
                         f"spheroid's area is {area}")


def wall_force(x, abc, f_0, l_0, minus_clamped):
    """The steric wall force on fiber nodes [F, n, 3] (module docstring)."""
    abc = np.asarray(abc, float)
    s = x / abc
    r_c = abc * s / np.linalg.norm(s, axis=-1, keepdims=True)
    r_mag, c_mag = np.linalg.norm(x, axis=-1), np.linalg.norm(r_c, axis=-1)
    dr = x - r_c
    f = (f_0 * dr / np.linalg.norm(dr, axis=-1, keepdims=True)
         * np.exp(-(c_mag - r_mag) / l_0)[..., None])
    f = np.where((r_mag < c_mag)[..., None], f, 0.0)
    f[np.asarray(minus_clamped, bool), 0] = 0.0
    return f


def closest_pairs(x, shell_nodes) -> dict:
    """The smallest distance from a fiber node to a shell node, and between
    nodes of two fibers: what the 1e-5 regularisation would have to reach."""
    r = x.reshape(-1, 3)
    to_shell = min(np.linalg.norm(r[lo:lo + 1024, None] - shell_nodes[None],
                                  axis=2).min()
                   for lo in range(0, len(r), 1024))
    fid = np.repeat(np.arange(x.shape[0]), x.shape[1])
    between = np.inf
    for lo in range(0, len(r), 1024):
        d = np.linalg.norm(r[lo:lo + 1024, None] - r[None], axis=2)
        d[fid[lo:lo + 1024, None] == fid[None]] = np.inf
        between = min(between, d.min())
    return {"fiber_to_shell": float(to_shell),
            "fiber_to_fiber": float(between)}


# ---------------------------------------------------------------- fiber rows

def _no_flow(r, wf, fiber_id, eta):
    return np.zeros_like(np.asarray(r, float))


def _dot(p, q):
    return np.sum(p * q, axis=1)


class ClampedFibers(_FIB.FiberStep):
    """`FiberStep` with the minus end of each ``minus_clamped`` fiber
    clamped and the wall force ``f_wall`` [F, n, 3] beside the motor force.
    Every flow is handed in (``explicit_flow``, `apply`'s ``v``): the
    coupled step below sums them."""

    def __init__(self, x_old, length, bending, radius, force_scale, *, dt,
                 eta, explicit_flow, minus_clamped, f_wall):
        super().__init__(x_old, length, bending, radius, force_scale, dt=dt,
                         eta=eta, flow=_no_flow, explicit_flow=explicit_flow)
        self.mc = np.asarray(minus_clamped, bool)
        self.f_wall = np.asarray(f_wall, float)

    def rhs(self):
        s = 2.0 / self.L
        c0, c1 = self.c0[:, None], self.c1[:, None]
        xs, xss, v, fw = self.xs, self.xss, self.v_explicit, self.f_wall
        f = self.fs[:, None, None] * xs + fw       # motor + wall
        xsf = np.sum(xs * f, axis=2, keepdims=True)
        rx = (self.x / self.dt + v + c0[..., None] * (f + xs * xsf)
              + c1[..., None] * (f - xs * xsf))
        rt = (-_FIB.PENALTY + np.sum(xs * self._d("D1", v, s), axis=2)
              + 2.0 * c0 * np.sum(xs * self._d("D1", f, s), axis=2)
              + (c0 - c1) * np.sum(xss * f, axis=2))
        bc = np.zeros((self.F, 14))
        # free ends balance the wall force; a clamped end is held
        bc[:, 0:3], bc[:, 3] = fw[:, 0], _dot(fw[:, 0], xs[:, 0])
        bc[:, 7:10], bc[:, 10] = fw[:, -1], _dot(fw[:, -1], xs[:, -1])
        mc = self.mc
        bc[mc, 0:3] = self.x[mc, 0] / self.dt
        bc[mc, 3] = (-_dot(xs[mc, 0], v[mc, 0])
                     - 2.0 * self.c0[mc] * _dot(xs[mc, 0], fw[mc, 0]))
        bc[mc, 4:7] = xs[mc, 0] / self.dt
        return np.concatenate([self._down(rx, rt), bc], axis=1)

    def apply(self, X, T, v):
        """A [X, T] with ``v`` [F, n, 3] every implicit flow on the nodes."""
        rows = super().apply(X, T, v_other=v)        # free rows at both ends
        X, T, mc = np.asarray(X, float), np.asarray(T, float), self.mc
        s = 2.0 / self.L
        X1, X3 = self._d("D1", X, s), self._d("D3", X, s ** 3)
        T1 = self._d("D1", T, s)
        bc = rows[:, -14:]
        bod = _FIB.BETA_TSTEP / self.dt
        bc[mc, 0:3] = bod * X[mc, 0]
        bc[mc, 3] = (6.0 * self.E[mc] * self.c0[mc]
                     * _dot(self.xss[mc, 0], X3[mc, 0])
                     + 2.0 * self.c0[mc] * T1[mc, 0]
                     + _dot(self.xs[mc, 0], np.asarray(v, float)[mc, 0]))
        bc[mc, 4:7] = bod * X1[mc, 0]
        return rows


# ------------------------------------------------------------ the whole step

_SING = {}   # singularity-subtraction vectors by quadrature: fixed in a run


class ClampedShellStep:
    """One step's linear system: fibers (clamped or free at the minus end)
    inside an ellipsoidal shell. ``shell``: {nodes, normals, weights}."""

    def __init__(self, cfg, pre, shell, *, dt, eta, sums, fiber_flow):
        self.eta, self.sums, self.fiber_flow = float(eta), sums, fiber_flow
        peri = cfg["periphery"]
        abc = (peri["a"], peri["b"], peri["c"])
        self.shell = shell
        check_quadrature(shell["nodes"], shell["normals"], shell["weights"],
                         abc)
        groups = pre["fibers"]
        cat = lambda k: np.concatenate([g[k] for g in groups])  # noqa: E731
        if (cat("plus_pinned").any() or (cat("binding_body") >= 0).any()
                or not cat("active").all()):
            raise ValueError("this reference writes a free plus end and no "
                             "body link on active fibers only")
        x, mc = cat("x"), cat("minus_clamped").astype(bool)
        self.r_fib = x.reshape(-1, 3)
        self.fid = np.repeat(np.arange(x.shape[0]), x.shape[1])
        self.ns = shell["nodes"].shape[0]
        params = cfg.get("params", {})
        f_wall = wall_force(
            x, abc, params.get("fiber_periphery_interaction.f_0", WALL_F0),
            params.get("fiber_periphery_interaction.l_0", WALL_L0), mc)
        # explicit flow: the wall forces' Stokeslet on the other fibers and
        # on the shell
        w0 = _FIB.fiber_matrices(x.shape[1])["w0"]
        w = 0.5 * cat("length")[:, None] * w0
        wfw = (w[..., None] * f_wall).reshape(-1, 3)
        v_fib = np.asarray(fiber_flow(self.r_fib, wfw, self.fid, eta))
        self.v_exp_shell = sums.stokeslet(self.r_fib, shell["nodes"], wfw, eta)
        self.fib = ClampedFibers(
            x, cat("length"), cat("bending_rigidity"), cat("radius"),
            cat("force_scale"), dt=dt, eta=eta,
            explicit_flow=v_fib.reshape(x.shape), minus_clamped=mc,
            f_wall=f_wall)
        key = hashlib.sha1(shell["nodes"].tobytes()
                           + shell["normals"].tobytes()
                           + shell["weights"].tobytes()).hexdigest()
        if key not in _SING:
            _SING.clear()
            _SING[key] = self._sing()
        self.e_shell = _SING[key]

    def _sing(self):
        """The double layer of the weights along each axis, on the shell's
        own nodes."""
        sh, out = self.shell, []
        for k in range(3):
            e = np.zeros_like(sh["nodes"])
            e[:, k] = sh["weights"]
            out.append(self.sums.double_layer(sh["nodes"], sh["normals"], e,
                                              sh["nodes"]))
        return out

    def rhs(self):
        return np.concatenate([self.fib.rhs().ravel(),
                               -self.v_exp_shell.ravel()])

    def apply(self, X, T, rho_shell):
        s, sh, eta = self.sums, self.shell, self.eta
        rho = np.asarray(rho_shell, float).reshape(-1, 3)
        shape = np.asarray(X).shape
        wf = self.fib.weighted_force(X, T).reshape(-1, 3)
        v_fib = (np.asarray(self.fiber_flow(self.r_fib, wf, self.fid, eta))
                 + s.double_layer(sh["nodes"], sh["normals"], rho, self.r_fib))
        v_shell = s.stokeslet(self.r_fib, sh["nodes"], wf, eta)
        w, n_s = sh["weights"][:, None], sh["normals"]
        shell_rows = (
            s.double_layer(sh["nodes"], n_s, rho, sh["nodes"])
            - sum(rho[:, k:k + 1] * self.e_shell[k] for k in range(3)) / w
            - rho / w + n_s * np.sum(n_s * rho) + v_shell)
        return np.concatenate(
            [self.fib.apply(X, T, v_fib.reshape(shape)).ravel(),
             shell_rows.ravel()])

    def residual(self, X, T, rho_shell) -> dict:
        b = self.rhs()
        r = b - self.apply(X, T, rho_shell)
        n_f = self.fib.F * 4 * self.fib.n
        bc = lambda v: v[:n_f].reshape(self.fib.F, -1)[:, -14:]  # noqa: E731
        norm = np.linalg.norm
        return {"ref_residual": float(norm(r) / norm(b)),
                "ref_residual_shell": float(norm(r[n_f:]) / norm(b[n_f:])),
                "ref_residual_fiber_bc": float(norm(bc(r)) / norm(bc(b)))}


def step_residual(cfg, pre, post, *, dt, eta, flow=None) -> dict:
    """The three numbers of the answer ``post`` for the step from ``pre``
    (`run.snapshot` dicts; ``pre["geometry"]["shell"]`` is the given
    quadrature)."""
    import jax.numpy as jnp

    if "bodies" in pre or "bodies" in pre["geometry"]:
        raise ValueError("this reference has no body: the configuration "
                         "needs another")
    step = ClampedShellStep(cfg, pre, pre["geometry"]["shell"], dt=dt,
                            eta=eta, sums=_CPL.Sums(jnp),
                            fiber_flow=flow or _FIB.jax_flow())
    X, T = (np.concatenate([g[k] for g in post["fibers"]])
            for k in ("x", "tension"))
    return step.residual(X, T, post["shell_density"])
