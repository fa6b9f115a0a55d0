"""The plain reference of a coupled step: free fibers inside a spherical
shell with rigid bodies (the walkthrough), float64, matrix-free.

It imports nothing of the program. The fiber rows are
`free_fiber_step.FiberStep`'s; this file adds the second-kind boundary
integral rows of the shell and of the bodies and every flow between the
three (SkellySim: `periphery.cpp`, `body_spherical.cpp`, `system.cpp
apply_matvec / prep_state_for_solver`; Nazockdast et al. 2017):

* double layer of a surface with normals n, weights folded into the
  density rho:  u(x) = -3/(4 pi) sum_j (d.n_j)(d.rho_j) d / |d|^5,
  d = x - y_j, coincident points dropped;
* shell rows:  [D rho]_i - (1/w_i) sum_k rho_ik e_k,i - rho_i / w_i
  + n_i sum_j n_j.rho_j + v_i = -v_i^explicit, with e_k = D applied to the
  weights along axis k (singularity subtraction) and n n^T the null-space
  completion;
* body rows:  -(1/w_i) sum_k d_ik e_k,i - (U + Omega x (y_i - c)) + v_i
  = -v_i^explicit and  U6 - [sum_i d_i ; sum_i (y_i - c) x d_i] = 0;
* explicit flow: the Stokeslet (and rotlet) of each body's external force
  (and torque) at its centre.

The surface quadrature (nodes, normals, weights of the shell and of each
body) is GIVEN: it is the configuration's geometry as the precompute step
discretised it, taken from the state the program built from those files,
and held here to three facts the discretisation has to meet (weights sum to
the sphere's area within 1e-3, normals are unit and radial). The dense
operator and its inverse, which the program precomputed, are NOT taken:
the shell rows are summed pair by pair here. The near-field regularisation
of the program's kernels (pairs closer than 1e-5) never acts at these
node spacings, and the steric wall force on fiber nodes (20 exp(-gap/0.05),
gap >= 2 in this configuration: < 1e-16) is left out.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def _fiber_module():
    spec = importlib.util.spec_from_file_location(
        "chipbench_reference_free_fiber_step",
        os.path.join(_HERE, "free_fiber_step.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------- pairwise sums

def _blocks(n, block):
    return [(lo, min(lo + block, n)) for lo in range(0, n, block)]


class Sums:
    """The three pairwise sums, float64, through numpy or jax.numpy (one
    plain formula each; target rows in blocks, each block one jitted call
    where ``xp`` is jax.numpy)."""

    def __init__(self, xp=None, block=1024):
        self.xp = np if xp is None else xp
        self.block = block
        if self.xp is np:
            self._wrap = lambda fn: fn
        else:
            import jax

            self._wrap = jax.jit
        self._kernels = {k: self._wrap(getattr(self, "_" + k))
                         for k in ("stokeslet", "rotlet", "double_layer")}

    def _geom(self, trg, src):
        xp = self.xp
        dx = trg[:, 0:1] - src[None, :, 0]
        dy = trg[:, 1:2] - src[None, :, 1]
        dz = trg[:, 2:3] - src[None, :, 2]
        r2 = dx * dx + dy * dy + dz * dz
        ok = r2 > 0.0
        rinv = xp.where(ok, 1.0 / xp.sqrt(xp.where(ok, r2, 1.0)), 0.0)
        return dx, dy, dz, rinv

    def _stokeslet(self, trg, src, f):
        xp = self.xp
        dx, dy, dz, rinv = self._geom(trg, src)
        df = (dx * f[None, :, 0] + dy * f[None, :, 1]
              + dz * f[None, :, 2]) * rinv ** 3
        return xp.stack([(rinv * f[None, :, k] + df * d).sum(axis=1)
                         for k, d in enumerate((dx, dy, dz))], axis=1)

    def _rotlet(self, trg, src, t):
        xp = self.xp
        dx, dy, dz, rinv = self._geom(trg, src)
        r3 = rinv ** 3
        cx = t[None, :, 1] * dz - t[None, :, 2] * dy
        cy = t[None, :, 2] * dx - t[None, :, 0] * dz
        cz = t[None, :, 0] * dy - t[None, :, 1] * dx
        return xp.stack([(r3 * c).sum(axis=1) for c in (cx, cy, cz)], axis=1)

    def _double_layer(self, trg, src, n, rho):
        xp = self.xp
        dx, dy, dz, rinv = self._geom(trg, src)
        dn = dx * n[None, :, 0] + dy * n[None, :, 1] + dz * n[None, :, 2]
        dr = dx * rho[None, :, 0] + dy * rho[None, :, 1] + dz * rho[None, :, 2]
        c = dn * dr * rinv ** 5
        return xp.stack([(c * d).sum(axis=1) for d in (dx, dy, dz)], axis=1)

    def _sum(self, kind, r_trg, r_src, *arrays):
        xp = self.xp
        f64 = lambda a: xp.asarray(a, dtype=xp.float64)  # noqa: E731
        trg, src = f64(r_trg), f64(r_src)
        arrays = [f64(a) for a in arrays]
        out = [self._kernels[kind](trg[lo:hi], src, *arrays)
               for lo, hi in _blocks(trg.shape[0], self.block)]
        return np.asarray(xp.concatenate(out, axis=0), dtype=np.float64)

    def stokeslet(self, r_src, r_trg, f, eta):
        """u = 1/(8 pi eta) sum_j [f_j / |d| + d (d.f_j) / |d|^3]."""
        return self._sum("stokeslet", r_trg, r_src, f) / (8 * np.pi * eta)

    def rotlet(self, r_src, r_trg, torque, eta):
        """u = 1/(8 pi eta) sum_j (t_j x d) / |d|^3."""
        return self._sum("rotlet", r_trg, r_src, torque) / (8 * np.pi * eta)

    def double_layer(self, r_src, normals, rho, r_trg):
        """u = -3/(4 pi) sum_j (d.n_j)(d.rho_j) d / |d|^5."""
        return self._sum("double_layer", r_trg, r_src, normals,
                         rho) * (-3.0 / (4.0 * np.pi))


def rotation(q):
    """Rotation matrix of a unit quaternion (w, x, y, z)."""
    w, x, y, z = q
    return np.array([
        [1 - 2 * (y * y + z * z), 2 * (x * y - w * z), 2 * (x * z + w * y)],
        [2 * (x * y + w * z), 1 - 2 * (x * x + z * z), 2 * (y * z - w * x)],
        [2 * (x * z - w * y), 2 * (y * z + w * x), 1 - 2 * (x * x + y * y)]])


def check_quadrature(nodes, normals, weights, what: str) -> None:
    """The three facts the given surface quadrature is held to."""
    radius = np.linalg.norm(nodes, axis=1)
    if np.ptp(radius) > 1e-9 * radius.mean():
        raise ValueError(f"{what}: nodes are not on one sphere")
    area = 4 * np.pi * radius.mean() ** 2
    if abs(weights.sum() / area - 1) > 1e-3:
        raise ValueError(f"{what}: weights sum to {weights.sum()}, the "
                         f"sphere's area is {area}")
    radial = np.abs(np.sum(normals * nodes, axis=1)) / radius
    if (np.abs(np.linalg.norm(normals, axis=1) - 1).max() > 1e-9
            or np.abs(radial - 1).max() > 1e-9):
        raise ValueError(f"{what}: normals are not unit and radial")


class CoupledStep:
    """One step's linear system for fibers + shell + bodies.

    ``geometry``: {"shell": {nodes, normals, weights}, "bodies": {nodes_ref,
    normals_ref, weights [nb, n], external_force [nb, 3],
    external_torque [nb, 3]}}."""

    def __init__(self, pre, geometry, *, dt, eta, sums, fiber_flow):
        fmod = _fiber_module()
        self.eta, self.dt, self.sums = float(eta), float(dt), sums
        self.shell = geometry["shell"]
        check_quadrature(self.shell["nodes"], self.shell["normals"],
                         self.shell["weights"], "shell")
        g = geometry["bodies"]
        self.nb = g["nodes_ref"].shape[0]
        self.body_pos = np.asarray(pre["bodies"]["position"], float)
        self.body_nodes, self.body_normals = [], []
        for b in range(self.nb):
            check_quadrature(g["nodes_ref"][b], g["normals_ref"][b],
                             g["weights"][b], f"body {b}")
            R = rotation(pre["bodies"]["orientation"][b])
            self.body_nodes.append(self.body_pos[b] + g["nodes_ref"][b] @ R.T)
            self.body_normals.append(g["normals_ref"][b] @ R.T)
        self.body_w = np.asarray(g["weights"], float)
        self.body_geom = g
        # singularity-subtraction vectors: the double layer of the weights
        # along each axis, on the surface's own nodes
        self.e_shell = self._sing(self.shell["nodes"], self.shell["normals"],
                                  self.shell["weights"])
        self.e_body = [self._sing(self.body_nodes[b], self.body_normals[b],
                                  self.body_w[b]) for b in range(self.nb)]

        groups = pre["fibers"]
        cat = lambda k: np.concatenate([gr[k] for gr in groups])  # noqa: E731
        self.r_fib = cat("x").reshape(-1, 3)
        # explicit flow: each body's external force and torque at its centre
        self.r_all = np.concatenate([self.r_fib, self.shell["nodes"]]
                                    + self.body_nodes)
        v_exp = (sums.stokeslet(self.body_pos, self.r_all,
                                g["external_force"], eta)
                 + sums.rotlet(self.body_pos, self.r_all,
                               g["external_torque"], eta))
        self.nf, self.ns = self.r_fib.shape[0], self.shell["nodes"].shape[0]
        self.v_exp = v_exp
        self.fib = fmod.FiberStep(
            cat("x"), cat("length"), cat("bending_rigidity"), cat("radius"),
            cat("force_scale"), dt=dt, eta=eta, flow=fiber_flow,
            explicit_flow=v_exp[:self.nf].reshape(cat("x").shape))

    def _sing(self, nodes, normals, w):
        out = []
        for k in range(3):
            e = np.zeros_like(nodes)
            e[:, k] = w
            out.append(self.sums.double_layer(nodes, normals, e, nodes))
        return out                                  # [3][n, 3]

    def _split(self, v):
        nf, ns = self.nf, self.ns
        bodies, off = [], nf + ns
        for b in range(self.nb):
            n = self.body_nodes[b].shape[0]
            bodies.append(v[off:off + n])
            off += n
        return v[:nf], v[nf:nf + ns], bodies

    def rhs(self):
        _, v_s, v_b = self._split(self.v_exp)
        parts = [self.fib.rhs().ravel(), -v_s.ravel()]
        for b in range(self.nb):
            parts += [-v_b[b].ravel(), np.zeros(6)]
        return np.concatenate(parts)

    def apply(self, X, T, rho_shell, body_solution):
        """A applied to an answer: fibers (X, T), shell density [3 Ns],
        each body's [3 n density | U | Omega]."""
        s, eta = self.sums, self.eta
        rho = np.asarray(rho_shell, float).reshape(-1, 3)
        sol = [np.asarray(b, float) for b in body_solution]
        dens = [b[:-6].reshape(-1, 3) for b in sol]
        nf, ns = self.nf, self.ns
        r_fb = np.concatenate([self.r_fib] + self.body_nodes)
        v = np.zeros_like(self.r_all)
        # fibers -> shell and bodies (fiber -> fiber is FiberStep's own)
        wf = self.fib.weighted_force(X, T).reshape(-1, 3)
        v[nf:] += s.stokeslet(self.r_fib, self.r_all[nf:], wf, eta)
        # shell -> fibers and bodies (its self part is in its own rows)
        v_s2fb = s.double_layer(self.shell["nodes"], self.shell["normals"],
                                rho, r_fb)
        v[:nf] += v_s2fb[:nf]
        v[nf + ns:] += v_s2fb[nf:]
        # bodies -> everything (coincident nodes dropped)
        for b in range(self.nb):
            v += s.double_layer(self.body_nodes[b], self.body_normals[b],
                                dens[b], self.r_all)
        v_f, v_s, v_b = self._split(v)

        parts = [self.fib.apply(X, T, v_other=v_f.reshape(
            np.asarray(X).shape)).ravel()]
        w = self.shell["weights"][:, None]
        n_s = self.shell["normals"]
        shell_rows = (
            s.double_layer(self.shell["nodes"], n_s, rho,
                           self.shell["nodes"])
            - sum(rho[:, k:k + 1] * self.e_shell[k] for k in range(3)) / w
            - rho / w + n_s * np.sum(n_s * rho) + v_s)
        parts.append(shell_rows.ravel())
        for b in range(self.nb):
            U, Om = sol[b][-6:-3], sol[b][-3:]
            arm = self.body_nodes[b] - self.body_pos[b]
            wb = self.body_w[b][:, None]
            c = sum(dens[b][:, k:k + 1] * self.e_body[b][k]
                    for k in range(3)) / wb
            rigid = U[None, :] + np.cross(Om[None, :], arm)
            parts.append((-c - rigid + v_b[b]).ravel())
            kt = np.concatenate([dens[b].sum(axis=0),
                                 np.cross(arm, dens[b]).sum(axis=0)])
            parts.append(sol[b][-6:] - kt)
        return np.concatenate(parts)

    def residual(self, X, T, rho_shell, body_solution) -> dict:
        """The whole vector's residual (what ``gmres_tol`` bounds) and,
        beside it, the shell's and the bodies' rows against their own
        right-hand sides: the whole norm is nearly all fiber rows
        (x / dt, the tension penalty), so an error in the shell's density
        or a body's would not show in it."""
        b = self.rhs()
        r = b - self.apply(X, T, rho_shell, body_solution)
        n_f = self.fib.F * 4 * self.fib.n
        n_s = 3 * self.ns
        norm = np.linalg.norm
        return {"ref_residual": float(norm(r) / norm(b)),
                "ref_residual_shell": float(norm(r[n_f:n_f + n_s])
                                            / norm(b[n_f:n_f + n_s])),
                "ref_residual_body": float(norm(r[n_f + n_s:])
                                           / norm(b[n_f + n_s:]))}


def step_residual(cfg, pre, post, *, dt, eta, flow=None, geometry=None,
                  xp=None) -> dict:
    """||b - A x|| / ||b|| of the answer ``post`` for the step from
    ``pre``; ``pre["geometry"]`` holds the given surface quadrature."""
    fmod = _fiber_module()
    if xp is None:
        import jax.numpy as xp
    geometry = geometry or pre["geometry"]
    step = CoupledStep(pre, geometry, dt=dt, eta=eta, sums=Sums(xp),
                       fiber_flow=flow or fmod.jax_flow())
    cat = lambda k: np.concatenate([g[k] for g in post["fibers"]])  # noqa: E731
    return step.residual(cat("x"), cat("tension"), post["shell_density"],
                         list(post["bodies"]["solution"]))
