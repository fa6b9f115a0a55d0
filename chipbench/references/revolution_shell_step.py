"""The plain reference of a step of clamped, force-carrying fibers inside a
SURFACE-OF-REVOLUTION shell and no body (`examples/oocyte`), float64,
matrix-free.

It imports nothing of the program and takes none of its operators. It loads
its three sibling references by path, as they load each other, and edits
none of them: `clamped_shell_step`'s clamped fiber rows (`ClampedFibers`)
and its whole step (`ClampedShellStep`: the shell's second-kind rows, the
flows between shell and fibers, the singularity subtraction), whose
ellipsoid-only parts this file replaces; `coupled_step`'s pairwise `Sums`;
`free_fiber_step`'s flow between fibers. Given the state BEFORE a step and
the program's answer, it builds the step's linear system and returns

* ``ref_residual``          ||b - A x|| / ||b|| of the whole vector, what
                            ``gmres_tol`` bounds;
* ``ref_residual_shell``    the shell's rows against the flow they cancel
                            (below: their right-hand side is zero);
* ``ref_residual_fiber_bc`` the 14 boundary rows of every fiber against
                            their own right-hand side.

Equations (SkellySim `fiber_finite_difference.cpp:347-513`, `periphery.cpp`,
`system.cpp prep_state_for_solver / apply_matvec`; Nazockdast et al. 2017):
`clamped_shell_step`'s, with two parts of the ellipsoid's replaced.

**The quadrature** is GIVEN (the precompute step's discretisation, from the
state the program built) and held to a surface of revolution's facts
(`check_quadrature`), computed here from the configuration's own
``periphery.envelope``: its ``height`` expression of x and the numbers it
names, evaluated by this file (`Envelope`), never by the program's fit.
Upstream inflates the node surface by 1.04 (`precompute.py:34`) and lays
the fibers' minus ends on the envelope itself, so

  nodes      on r = 1.04 h(x / 1.04), r = sqrt(y^2 + z^2), x within 1.04 x
             the bounds (the two tips on the axis);
  normals    unit and along the inward gradient of that surface's level
             function (1.04 h(x / 1.04))^2 - y^2 - z^2, i.e. along
             (R R', -y, -z) with R(x) = 1.04 h(x / 1.04), R' = h'(x / 1.04);
             along the axis, inward, at the tips;
  weights    summing to the surface's area 2 pi int R ds (frustums of a
             polyline of the profile, 2,000,001 points spaced as cos)
             within 2e-3.

The slope h' is a complex step of the expression (exact to rounding for an
expression that numpy evaluates in complex arithmetic, as every published
envelope is). Tolerances: 1e-9 of the largest radius for the nodes, 1e-9
for the normals; 2e-3 for the area because a coarse quadrature is not exact
(at a test's 287 nodes the weights sum 1.0e-3 under the area, at the
cell's 6,431 nodes 1.1e-8; a normal tilted as a global Chebyshev fit's
derivative tilts it near the ends, up to 0.08 in 1 - cos, moves the sum
by 3.8e-3).

**No steric wall force.** A surface of revolution is upstream's
`GenericPeriphery`: its `fiber_interaction` force is zero and its
`check_collision` is false (`periphery.cpp`, the generic shape's stubs).
So where `clamped_shell_step` puts the wall force on the right-hand side,
in the explicit flow on the other fibers and on the shell, and in the free
ends' boundary rows, here every one of those terms is zero, and the
shell's right-hand side is EXACTLY zero (no body, no wall force, no
external flow: `update_RHS` of a zero flow). ``ref_residual_shell`` can
then not be measured against it: it is ||r_shell|| / ||A_sf x||, the
shell's rows against the Stokeslet of the answer's fiber forces on the
shell's nodes, the one flow those rows cancel. A solve that stops at
``gmres_tol`` of the WHOLE norm may leave gmres_tol x ||b|| in them, so its
limit is gmres_tol x ||b|| / ||A_sf x|| (`scales` gives both norms).

**Fiber rows** as `clamped_shell_step`: the clamped minus end (velocity,
tension and angular-velocity rows), the free plus end, the motor force
``force_scale * xs`` on the right-hand side; ``minus_clamped`` read per
fiber from the snapshot; a plus-pinned, body-bound or inactive fiber is
refused.

**Left out, and said.** The kernels' near-field regularisation (pairs
closer than 1e-5): in `oocyte_1024`'s scene at t = 0 the closest fiber node
to a shell node is 0.1074 away and the closest nodes of two fibers 0.0284
(`closest_pairs` states both for any scene), so it never acts while the
fibers move ~1e-4 a step.
"""

from __future__ import annotations

import hashlib
import importlib.util
import os

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))

NODE_SCALE = 1.04        # node surface / attachment surface (precompute.py:34)
NODES_TOL = 1e-9         # of the largest radius
NORMALS_TOL = 1e-9
AREA_TOL = 2e-3


def _sibling(name: str):
    spec = importlib.util.spec_from_file_location(
        "chipbench_reference_" + name, os.path.join(_HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_CSS = _sibling("clamped_shell_step")
_FIB = _CSS._FIB
_CPL = _CSS._CPL

closest_pairs = _CSS.closest_pairs


# ------------------------------------------------------------ the quadrature

class Envelope:
    """The configuration's height function h(x) on [lower_bound,
    upper_bound]: its ``height`` expression with every number of the table
    as a name (the toolkit's contract)."""

    def __init__(self, table: dict):
        self.lower, self.upper = (float(table["lower_bound"]),
                                  float(table["upper_bound"]))
        names = {k: v for k, v in table.items()
                 if isinstance(v, (int, float)) and not isinstance(v, bool)}
        self._h = eval("lambda x: " + table["height"],   # noqa: S307
                       dict(names, np=np))

    def __call__(self, x):
        return self._h(np.clip(np.asarray(x, float), self.lower, self.upper))

    def slope(self, x):
        step = 1e-30
        x = np.clip(np.asarray(x, float), self.lower, self.upper)
        with np.errstate(all="ignore"):
            return np.imag(self._h(x + 1j * step)) / step

    def area(self, scale: float = NODE_SCALE, n: int = 2_000_001) -> float:
        """2 pi int R ds of the surface r = scale h(x / scale)."""
        t = np.linspace(0.0, np.pi, n)
        x = 0.5 * (self.lower + self.upper) - 0.5 * (self.upper
                                                      - self.lower) * np.cos(t)
        r = self(x)
        seg = np.hypot(np.diff(x), np.diff(r))
        return float(scale * scale * np.pi * np.sum((r[1:] + r[:-1]) * seg))


def check_quadrature(nodes, normals, weights, envelope: dict) -> None:
    """The facts the given quadrature is held to: a surface of
    revolution's, from the configuration's envelope (module docstring)."""
    env, s = Envelope(envelope), NODE_SCALE
    x, r = nodes[:, 0], np.hypot(nodes[:, 1], nodes[:, 2])
    xu = x / s
    span = env.upper - env.lower
    tip = ((np.abs(xu - env.lower) <= 1e-12 * span)
           | (np.abs(xu - env.upper) <= 1e-12 * span))
    xu = np.where(np.abs(xu - env.lower) <= 1e-12 * span, env.lower, xu)
    xu = np.where(np.abs(xu - env.upper) <= 1e-12 * span, env.upper, xu)
    R = s * env(xu)
    scale = max(float(np.max(R)), 1e-300)
    if (np.any(xu < env.lower) or np.any(xu > env.upper)
            or np.abs(r - R).max() > NODES_TOL * scale):
        raise ValueError("shell: nodes are not on the surface of revolution "
                         f"r = {s} h(x / {s}) of the configuration's envelope")
    inner = ~tip
    want = np.zeros_like(nodes)
    want[inner, 0] = R[inner] * env.slope(xu[inner])
    want[inner, 1:] = -nodes[inner, 1:]
    want[inner] /= np.linalg.norm(want[inner], axis=1, keepdims=True)
    want[tip, 0] = -np.sign(x[tip])          # the tips: inward, on the axis
    if (np.abs(np.linalg.norm(normals, axis=1) - 1).max() > NORMALS_TOL
            or np.abs(normals - want).max() > NORMALS_TOL):
        raise ValueError("shell: normals are not unit and along the inward "
                         "gradient of the surface's level function")
    area = env.area(s)
    if abs(weights.sum() / area - 1) > AREA_TOL:
        raise ValueError(f"shell: weights sum to {weights.sum()}, the "
                         f"surface of revolution's area is {area}")


# ------------------------------------------------------------ the whole step

_SING = {}   # singularity-subtraction vectors by quadrature: fixed in a run


class RevolutionShellStep(_CSS.ClampedShellStep):
    """One step's linear system: fibers (clamped or free at the minus end)
    inside a surface-of-revolution shell, no wall force. ``shell``:
    {nodes, normals, weights}."""

    def __init__(self, cfg, pre, shell, *, dt, eta, sums, fiber_flow):
        self.eta, self.sums, self.fiber_flow = float(eta), sums, fiber_flow
        self.shell = shell
        check_quadrature(shell["nodes"], shell["normals"], shell["weights"],
                         cfg["periphery"]["envelope"])
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
        none = np.zeros_like(x)          # no wall force, so no explicit flow
        self.v_exp_shell = np.zeros_like(shell["nodes"])
        self.fib = _CSS.ClampedFibers(
            x, cat("length"), cat("bending_rigidity"), cat("radius"),
            cat("force_scale"), dt=dt, eta=eta, explicit_flow=none,
            minus_clamped=mc, f_wall=none)
        key = hashlib.sha1(shell["nodes"].tobytes()
                           + shell["normals"].tobytes()
                           + shell["weights"].tobytes()).hexdigest()
        if key not in _SING:
            _SING.clear()
            _SING[key] = self._sing()
        self.e_shell = _SING[key]

    def fiber_flow_on_shell(self, X, T):
        """A_sf x: the Stokeslet of the answer's fiber forces on the shell's
        nodes, the flow the shell's rows cancel."""
        wf = self.fib.weighted_force(X, T).reshape(-1, 3)
        return self.sums.stokeslet(self.r_fib, self.shell["nodes"], wf,
                                   self.eta)

    def residual(self, X, T, rho_shell) -> dict:
        b = self.rhs()
        r = b - self.apply(X, T, rho_shell)
        n_f = self.fib.F * 4 * self.fib.n
        bc = lambda v: v[:n_f].reshape(self.fib.F, -1)[:, -14:]  # noqa: E731
        norm = np.linalg.norm
        return {"ref_residual": float(norm(r) / norm(b)),
                "ref_residual_shell": float(
                    norm(r[n_f:]) / norm(self.fiber_flow_on_shell(X, T))),
                "ref_residual_fiber_bc": float(norm(bc(r)) / norm(bc(b)))}


def _step(cfg, pre, *, dt, eta, flow=None):
    import jax.numpy as jnp

    if "bodies" in pre or "bodies" in pre["geometry"]:
        raise ValueError("this reference has no body: the configuration "
                         "needs another")
    return RevolutionShellStep(cfg, pre, pre["geometry"]["shell"], dt=dt,
                               eta=eta, sums=_CPL.Sums(jnp),
                               fiber_flow=flow or _FIB.jax_flow())


def _answer(post):
    return tuple(np.concatenate([g[k] for g in post["fibers"]])
                 for k in ("x", "tension"))


def step_residual(cfg, pre, post, *, dt, eta, flow=None) -> dict:
    """The three numbers of the answer ``post`` for the step from ``pre``
    (`run.snapshot` dicts; ``pre["geometry"]["shell"]`` is the given
    quadrature)."""
    step = _step(cfg, pre, dt=dt, eta=eta, flow=flow)
    X, T = _answer(post)
    return step.residual(X, T, post["shell_density"])


def scales(cfg, pre, post, *, dt, eta, flow=None) -> dict:
    """The norms the limits of the rows are set from: ||b||, ||b_shell||
    (zero), ||b_bc|| and ||A_sf x|| of the answer ``post``."""
    step = _step(cfg, pre, dt=dt, eta=eta, flow=flow)
    X, T = _answer(post)
    b = step.rhs()
    n_f = step.fib.F * 4 * step.fib.n
    norm = np.linalg.norm
    return {"b": float(norm(b)), "b_shell": float(norm(b[n_f:])),
            "b_bc": float(norm(b[:n_f].reshape(step.fib.F, -1)[:, -14:])),
            "fiber_flow_on_shell": float(norm(step.fiber_flow_on_shell(X,
                                                                      T)))}
