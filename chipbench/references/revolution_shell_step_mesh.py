"""`revolution_shell_step` with its pair sums spread over every device JAX
sees: the reference of `oocyte_mesh4`, whose step has 96,000 fiber nodes.

It is that file's step, unedited and loaded by path (the quadrature facts,
the clamped fiber rows, the shell's second-kind rows, the three numbers),
handed two pair-sum seams that compute the same float64 formulas on more
devices than one: `Sums`, `coupled_step.Sums` through jax.numpy with its
blocks of target rows dealt to the devices in turn, and `fiber_flow`,
`free_fiber_step`'s flow between fibers (the Stokeslet of every OTHER
fiber's nodes) with the targets cut into one contiguous part a device, each
part summed against every source on its own device. A sum of one target
row is the same sum on any device; only where its rows are computed
changes. On one device both are the sibling's own arithmetic.

Why: the flow between 3,000 fibers is 96,000^2 = 9.2e9 pairs a checked
step, float64 on the chip, and the check runs three steps after the
window; on four chips each takes a quarter. It imports nothing of the
program and takes none of its operators.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def _sibling(name: str):
    spec = importlib.util.spec_from_file_location(
        "chipbench_reference_" + name, os.path.join(_HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_REV = _sibling("revolution_shell_step")
_CPL = _REV._CPL

RevolutionShellStep = _REV.RevolutionShellStep
check_quadrature = _REV.check_quadrature


class Sums(_CPL.Sums):
    """`coupled_step.Sums` through jax.numpy, block ``i`` of the target rows
    summed on device ``i mod D`` (the sources go to every device once a
    sum)."""

    def __init__(self, block=1024):
        import jax
        import jax.numpy as jnp

        super().__init__(jnp, block)
        self._devices = jax.devices()

    def _sum(self, kind, r_trg, r_src, *arrays):
        import jax

        f64 = lambda a: np.asarray(a, dtype=np.float64)  # noqa: E731
        trg = f64(r_trg)
        on = {d: [jax.device_put(f64(a), d) for a in (r_src,) + arrays]
              for d in self._devices}
        out = []
        for i, (lo, hi) in enumerate(_CPL._blocks(trg.shape[0],
                                                  self.block)):
            d = self._devices[i % len(self._devices)]
            out.append(self._kernels[kind](jax.device_put(trg[lo:hi], d),
                                           *on[d]))
        return np.concatenate([np.asarray(o, dtype=np.float64)
                               for o in out], axis=0)


def fiber_flow(block: int = 512):
    """``flow(r, wf, fiber_id, eta)`` -> [n, 3]: at every node the Stokeslet
    of the weighted forces ``wf`` of every node of ANOTHER fiber,
    1/(8 pi eta) sum_j [f_j / |d| + d (d.f_j) / |d|^3] (`free_fiber_step`'s
    `oseen_flow_other_fibers`), float64, the target rows in one contiguous
    part a device."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def part(rt, tid, r, wf, fid):
        n = rt.shape[0]
        nb = -(-n // block)
        pad = nb * block - n
        # padded target rows belong to no fiber; their flows are cut off
        rt = jnp.concatenate([rt, jnp.zeros((pad, 3), rt.dtype)])
        tid = jnp.concatenate([tid, jnp.full((pad,), -1, tid.dtype)])
        sx, sy, sz = r[:, 0], r[:, 1], r[:, 2]
        fx, fy, fz = wf[:, 0], wf[:, 1], wf[:, 2]

        def one(args):
            tb, tb_id = args
            dx = tb[:, 0:1] - sx[None, :]
            dy = tb[:, 1:2] - sy[None, :]
            dz = tb[:, 2:3] - sz[None, :]
            other = tb_id[:, None] != fid[None, :]
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
                              tid.reshape(nb, block)))
        return u.reshape(nb * block, 3)[:n]

    def flow(r, wf, fiber_id, eta):
        if not jax.config.jax_enable_x64:
            raise RuntimeError("the reference needs jax_enable_x64")
        devices = jax.devices()
        r = np.asarray(r, dtype=np.float64)
        wf = np.asarray(wf, dtype=np.float64)
        fid = np.asarray(fiber_id, dtype=np.int32)
        cuts = np.linspace(0, r.shape[0], len(devices) + 1).astype(int)
        out = []
        for d, lo, hi in zip(devices, cuts[:-1], cuts[1:]):
            if hi > lo:
                put = lambda a: jax.device_put(a, d)  # noqa: E731
                out.append(part(put(r[lo:hi]), put(fid[lo:hi]), put(r),
                                put(wf), put(fid)))
        u = np.concatenate([np.asarray(o, dtype=np.float64) for o in out])
        return u / (8.0 * np.pi * eta)

    return flow


def _step(cfg, pre, *, dt, eta, flow=None):
    if "bodies" in pre or "bodies" in pre["geometry"]:
        raise ValueError("this reference has no body: the configuration "
                         "needs another")
    return RevolutionShellStep(cfg, pre, pre["geometry"]["shell"], dt=dt,
                               eta=eta, sums=Sums(),
                               fiber_flow=flow or fiber_flow())


def step_residual(cfg, pre, post, *, dt, eta, flow=None) -> dict:
    """`revolution_shell_step.step_residual`, the pair sums spread."""
    step = _step(cfg, pre, dt=dt, eta=eta, flow=flow)
    X, T = _REV._answer(post)
    return step.residual(X, T, post["shell_density"])

