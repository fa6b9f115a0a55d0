"""Deterministic fixture scenes for the auditable-program matrix.

Small enough to trace in seconds, structurally complete enough that the
lowered programs exercise every contract surface: the free-fiber scene
drives the fiber-only paths (and the retrace probes, which must *run* the
program twice), the coupled scene (56-node shell, node-aligned on the 2/4/8
meshes, plus one forced body) drives the row-sharded shell operators whose
collectives the SPMD contracts pin. Mirrors `tests/test_spmd.py`'s scene so
the audit contracts and the sharded-parity tests describe the same program.
"""

from __future__ import annotations

import numpy as np

#: baseline parameter set shared by every audited entry point (adaptive gate
#: off: the audited program is the pure trial step, like the SPMD tests)
BASE_PARAMS = dict(eta=1.0, dt_initial=1e-3, t_final=1e-2, gmres_tol=1e-10,
                   adaptive_timestep_flag=False)

#: shell node count for the coupled scene — divides 2/4/8 node-aligned
SHELL_NODES = 56
#: ... and for its padded twin: 54 nodes, which a mesh of four pads to 56
PADDED_SHELL_NODES = 54
BODY_NODES = 50


def make_fibers(n_fibers=16, n_nodes=16, seed=5, box=4.0, dtype=None):
    import jax.numpy as jnp

    from ..fibers import container as fc

    rng = np.random.default_rng(seed)
    t = np.linspace(0, 1, n_nodes)
    origins = rng.uniform(-box, box, size=(n_fibers, 3))
    dirs = rng.normal(size=(n_fibers, 3))
    dirs /= np.linalg.norm(dirs, axis=1, keepdims=True)
    x = origins[:, None, :] + t[None, :, None] * dirs[:, None, :]
    return fc.make_group(x, lengths=1.0, bending_rigidity=0.01,
                         radius=0.0125, dtype=dtype or jnp.float64)


def make_system(shell: bool = False, **param_overrides):
    """A `System` (optionally with the spherical periphery) on the audit's
    baseline parameters."""
    from ..params import Params
    from ..periphery.periphery import PeripheryShape
    from ..system import System

    shape = PeripheryShape(kind="sphere", radius=6.0) if shell else None
    return System(Params(**dict(BASE_PARAMS, **param_overrides)),
                  shell_shape=shape)


def free_state(system, seed=5):
    """16 free fibers in a uniform background flow (divides the 2/4/8
    meshes; same scene as tests/test_spmd.py's free variant)."""
    import jax.numpy as jnp

    from ..system import BackgroundFlow

    return system.make_state(
        fibers=make_fibers(seed=seed),
        background=BackgroundFlow.make(uniform=(1.0, 0.0, 0.0),
                                       dtype=jnp.float64))


def coupled_state(system, seed=7, shell_nodes=SHELL_NODES):
    """16 fibers + the 56-node shell (or ``shell_nodes``) + one externally
    forced body."""
    import jax.numpy as jnp

    from ..testing import make_coupled_parts

    shell, _, bodies = make_coupled_parts(shell_nodes, BODY_NODES,
                                          jnp.float64)
    return system.make_state(fibers=make_fibers(seed=seed, box=2.0),
                             shell=shell, bodies=bodies)
