"""Row-sharded shell operators: coupled fiber+shell solve on the 8-device mesh
matches the single-program solve.

Mirrors the reference's periphery row decomposition
(`periphery.cpp:408-442`: shell operator rows Scatterv'd, matvec =
Allgatherv + local GEMV) with GSPMD row sharding.
"""

import pytest
import jax
import jax.numpy as jnp
import numpy as np

from skellysim_tpu.fibers import container as fc
from skellysim_tpu.params import Params
from skellysim_tpu.parallel import make_mesh, shard_state
from skellysim_tpu.periphery import periphery as peri
from skellysim_tpu.periphery.precompute import precompute_periphery
from skellysim_tpu.system import System

N_DEV = 8


def _coupled_state(system, shell_data, n_fibers=8, n_nodes=16):
    rng = np.random.default_rng(2)
    t = np.linspace(0, 1, n_nodes)
    # fibers inside the radius-4 shell, pointing inward from random origins
    origins = rng.uniform(-1.5, 1.5, size=(n_fibers, 3))
    dirs = rng.normal(size=(n_fibers, 3))
    dirs /= np.linalg.norm(dirs, axis=1, keepdims=True)
    x = origins[:, None, :] + t[None, :, None] * dirs[:, None, :]
    fibers = fc.make_group(x, lengths=1.0, bending_rigidity=0.01, radius=0.0125,
                           force_scale=-0.1, dtype=jnp.float64)
    shell = peri.make_state(shell_data["nodes"], shell_data["normals"],
                            shell_data["quadrature_weights"],
                            shell_data["stresslet_plus_complementary"],
                            shell_data["M_inv"])
    return system.make_state(fibers=fibers, shell=shell)


@pytest.mark.slow  # heavy coupled-solve integration; sibling fast tests keep the seam covered (ISSUE-9 870s-budget re-triage)
def test_sharded_shell_solve_matches_replicated():
    # 3*96 = 288 rows divide the 8-device mesh evenly
    shell_data = precompute_periphery("sphere", n_nodes=96, radius=4.0,
                                      eta=1.0)
    params = Params(eta=1.0, dt_initial=1e-3, t_final=1e-2, gmres_tol=1e-10,
                    adaptive_timestep_flag=False)
    shape = peri.PeripheryShape(kind="sphere", radius=4.0)

    sys_ref = System(params, shell_shape=shape)
    s_ref, sol_ref, info_ref = sys_ref.step(_coupled_state(sys_ref, shell_data))
    assert bool(info_ref.converged)

    mesh = make_mesh(N_DEV)
    sys_sh = System(params, shell_shape=shape)
    state = shard_state(_coupled_state(sys_sh, shell_data), mesh)
    # the dense operators really are distributed row-wise
    assert len(state.shell.M_inv.sharding.device_set) == N_DEV
    with jax.set_mesh(mesh):
        s_sh, sol_sh, info_sh = sys_sh.step(state)
        jax.block_until_ready(sol_sh)

    assert bool(info_sh.converged)
    np.testing.assert_allclose(np.asarray(sol_sh), np.asarray(sol_ref),
                               atol=1e-9)
    np.testing.assert_allclose(np.asarray(s_sh.fibers.x),
                               np.asarray(s_ref.fibers.x), atol=1e-11)
    np.testing.assert_allclose(np.asarray(s_sh.shell.density),
                               np.asarray(s_ref.shell.density), atol=1e-9)


def test_indivisible_shell_rows_raise():
    """Silent O(n^2)-replication fallback is forbidden (VERDICT weak #3): an
    indivisible shell row count must fail with an actionable message."""
    import pytest

    shell_data = precompute_periphery("sphere", n_nodes=100, radius=4.0,
                                      eta=1.0)  # 300 rows % 8 != 0
    params = Params(eta=1.0, dt_initial=1e-3, t_final=1e-2, gmres_tol=1e-10,
                    adaptive_timestep_flag=False)
    shape = peri.PeripheryShape(kind="sphere", radius=4.0)
    sys_sh = System(params, shell_shape=shape)
    mesh = make_mesh(N_DEV)
    state = _coupled_state(sys_sh, shell_data)
    with pytest.raises(ValueError, match="multiple of 8"):
        shard_state(state, mesh)
    # explicit opt-in replicates instead
    sharded = shard_state(state, mesh, allow_replicated_shell=True)
    assert len(sharded.shell.M_inv.sharding.device_set) in (1, N_DEV)


def test_schema_placement_ignores_shape_collision():
    """Placement is schema-driven off field names, not shapes: a shell
    density whose length happens to equal a bucket's n_fibers must stay
    replicated (the old shape-sniffing heuristic fiber-sharded any
    [n_fibers]-long leaf, mis-sharding replicated shell vectors)."""
    # 16-node shell -> density [48]; 48 fibers (divisible by the 8-mesh):
    # the collision the old heuristic tripped on
    from skellysim_tpu.fibers import container as fc
    from skellysim_tpu.testing import make_coupled_parts

    shell, shape, _ = make_coupled_parts(16, 50, jnp.float64)
    params = Params(eta=1.0, dt_initial=1e-3, t_final=1e-2, gmres_tol=1e-10,
                    adaptive_timestep_flag=False)
    system = System(params, shell_shape=shape)
    rng = np.random.default_rng(3)
    nf, n_nodes = 48, 16
    t = np.linspace(0, 1, n_nodes)
    x = (rng.uniform(-1.5, 1.5, size=(nf, 3))[:, None, :]
         + t[None, :, None] * np.array([0.0, 0.0, 1.0])[None, None, :])
    fibers = fc.make_group(x, lengths=1.0, bending_rigidity=0.01,
                           radius=0.0125, dtype=jnp.float64)
    state = system.make_state(fibers=fibers, shell=shell)
    assert state.shell.density.shape[0] == state.fibers.n_fibers  # collision

    mesh = make_mesh(N_DEV)
    sharded = shard_state(state, mesh)
    # shell vectors replicate by schema regardless of the shape collision
    assert len(sharded.shell.density.sharding.device_set) == 1 \
        or sharded.shell.density.sharding.is_fully_replicated
    assert sharded.shell.weights.sharding.is_fully_replicated
    # the fiber bucket and the shell operator rows still shard
    assert len(sharded.fibers.x.sharding.device_set) == N_DEV
    assert not sharded.fibers.x.sharding.is_fully_replicated
    assert len(sharded.shell.M_inv.sharding.device_set) == N_DEV
    assert not sharded.shell.M_inv.sharding.is_fully_replicated


def test_multihost_initialize_noop_single_process():
    """Single-process runs skip distributed init and report sane process
    info (the multi-host bring-up path, parallel/multihost.py)."""
    from skellysim_tpu.parallel import multihost

    assert multihost.initialize() is False
    info = multihost.process_info()
    assert info["process_index"] == 0
    assert info["process_count"] == 1
    assert info["global_device_count"] >= 1
