"""Explicitly-sharded full-step program (`parallel.spmd`) vs the
single-program ground truth on the 8-device virtual CPU mesh.

Distributed-correctness strategy per SURVEY.md §4.3: real sharded execution,
no mocks. Beyond value parity, the lowered program is audited (via the
skelly-audit engine, `skellysim_tpu.audit`) to pin the SPMD collective
contract: psum (all-reduce) reductions, ppermute (collective-permute)
rings, and NO all-gather larger than the shell density — the failure mode
this subsystem exists to rule out is GSPMD silently all-gathering a
fiber-cache-sized operand onto every chip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skellysim_tpu.fibers import container as fc
from skellysim_tpu.params import Params
from skellysim_tpu.parallel import make_mesh, shard_state
from skellysim_tpu.parallel.spmd import spmd_shell_mode
from skellysim_tpu.periphery.periphery import PeripheryShape
from skellysim_tpu.system import BackgroundFlow, System
from skellysim_tpu.testing import make_coupled_parts

N_DEV = 8
#: the reference's backend-agreement gate (`kernel_test.cpp:93`)
GATE = 5e-9

PARAMS = dict(eta=1.0, dt_initial=1e-3, t_final=1e-2, gmres_tol=1e-10,
              adaptive_timestep_flag=False)
SHAPE = PeripheryShape(kind="sphere", radius=6.0)


def _fibers(n_fibers=16, n_nodes=16, seed=5, box=4.0):
    rng = np.random.default_rng(seed)
    t = np.linspace(0, 1, n_nodes)
    origins = rng.uniform(-box, box, size=(n_fibers, 3))
    dirs = rng.normal(size=(n_fibers, 3))
    dirs /= np.linalg.norm(dirs, axis=1, keepdims=True)
    x = origins[:, None, :] + t[None, :, None] * dirs[:, None, :]
    return fc.make_group(x, lengths=1.0, bending_rigidity=0.01,
                         radius=0.0125, dtype=jnp.float64)


def _free_state(system):
    return system.make_state(
        fibers=_fibers(),
        background=BackgroundFlow.make(uniform=(1.0, 0.0, 0.0),
                                       dtype=jnp.float64))


@pytest.fixture(scope="module")
def coupled_parts():
    # 56 shell nodes split node-aligned over the 8-mesh: the row-sharded
    # shell path is under test, not the replicated fallback
    return make_coupled_parts(56, 50, jnp.float64)


def _coupled_state(system, parts):
    shell, _, bodies = parts
    return system.make_state(fibers=_fibers(seed=7, box=2.0), shell=shell,
                             bodies=bodies)


@pytest.mark.slow  # the coupled parity test below is the per-commit gate;
# this free-space variant rides the full tier (tier-1 runs near its timeout)
def test_spmd_free_fiber_solve_matches_single_program():
    sys_ref = System(Params(**PARAMS))
    s_ref, sol_ref, info_ref = sys_ref.step(_free_state(sys_ref))

    mesh = make_mesh(N_DEV)
    sys_sp = System(Params(**PARAMS))
    state = shard_state(_free_state(sys_sp), mesh)
    s_sp, sol_sp, info_sp = sys_sp.step_spmd(state, mesh)

    assert bool(info_sp.converged)
    assert abs(float(info_sp.residual_true)
               - float(info_ref.residual_true)) <= GATE
    np.testing.assert_allclose(np.asarray(sol_sp), np.asarray(sol_ref),
                               atol=GATE)
    np.testing.assert_allclose(np.asarray(s_sp.fibers.x),
                               np.asarray(s_ref.fibers.x), atol=GATE)
    # fiber state stays sharded across the step (no implicit gather)
    assert len(s_sp.fibers.x.sharding.device_set) == N_DEV


@pytest.mark.slow  # heavy coupled-solve integration; sibling fast tests keep the seam covered (ISSUE-9 870s-budget re-triage)
def test_spmd_coupled_solve_matches_single_program(coupled_parts):
    sys_ref = System(Params(**PARAMS), shell_shape=SHAPE)
    s_ref, sol_ref, info_ref = sys_ref.step(
        _coupled_state(sys_ref, coupled_parts))
    assert bool(info_ref.converged)

    mesh = make_mesh(N_DEV)
    sys_sp = System(Params(**PARAMS), shell_shape=SHAPE)
    state = shard_state(_coupled_state(sys_sp, coupled_parts), mesh)
    assert spmd_shell_mode(state, mesh) == "sharded"
    s_sp, sol_sp, info_sp = sys_sp.step_spmd(state, mesh)

    assert bool(info_sp.converged)
    assert abs(float(info_sp.residual_true)
               - float(info_ref.residual_true)) <= GATE
    np.testing.assert_allclose(np.asarray(sol_sp), np.asarray(sol_ref),
                               atol=GATE)
    np.testing.assert_allclose(np.asarray(s_sp.shell.density),
                               np.asarray(s_ref.shell.density), atol=GATE)
    np.testing.assert_allclose(np.asarray(s_sp.bodies.position),
                               np.asarray(s_ref.bodies.position), atol=1e-10)
    # the dense shell operators stay row-sharded through the step
    assert len(s_sp.shell.M_inv.sharding.device_set) == N_DEV


@pytest.mark.slow
def test_spmd_mixed_refinement_inside_mesh(coupled_parts):
    """Mixed precision (f32 Krylov + f64 refinement through the double-float
    ring tiles) composes inside the same shard_map program — refinement
    sweeps never leave the mesh. (slow-marked: the per-commit gate covers
    this path via the graft-entry dryrun's mixed leg.)"""
    pm = dict(PARAMS, solver_precision="mixed", refine_pair_impl="df")
    sys_ref = System(Params(**pm), shell_shape=SHAPE)
    _, _, info_ref = sys_ref.step(_coupled_state(sys_ref, coupled_parts))

    mesh = make_mesh(N_DEV)
    sys_sp = System(Params(**pm), shell_shape=SHAPE)
    state = shard_state(_coupled_state(sys_sp, coupled_parts), mesh)
    _, _, info_sp = sys_sp.step_spmd(state, mesh)

    assert bool(info_sp.converged)
    assert float(info_sp.residual_true) <= pm["gmres_tol"]
    # residual parity at the backend-agreement gate; the solutions agree
    # only to the tolerance ball (different f32 Krylov trajectories)
    assert abs(float(info_sp.residual_true)
               - float(info_ref.residual_true)) <= GATE
    assert int(info_sp.refines) == int(info_ref.refines)


@pytest.mark.slow
def test_spmd_replicated_shell_fallback():
    """A shell that cannot split node-aligned is padded by the mesh step
    (an unpadded one is refused in words); the explicit replicated opt-in
    still matches the single program."""
    parts = make_coupled_parts(100, 50, jnp.float64)  # 100 % 8 != 0
    mesh = make_mesh(N_DEV)
    sys_sp = System(Params(**PARAMS), shell_shape=SHAPE)
    state = _coupled_state(sys_sp, parts)
    with pytest.raises(ValueError, match="padded to 312 rows"):
        spmd_shell_mode(state, mesh)

    sys_ref = System(Params(**PARAMS), shell_shape=SHAPE)
    _, sol_ref, info_ref = sys_ref.step(_coupled_state(sys_ref, parts))

    state = shard_state(state, mesh, allow_replicated_shell=True)
    _, sol_sp, info_sp = sys_sp.step_spmd(state, mesh,
                                          allow_replicated_shell=True)
    assert bool(info_sp.converged)
    assert abs(float(info_sp.residual_true)
               - float(info_ref.residual_true)) <= GATE
    np.testing.assert_allclose(np.asarray(sol_sp), np.asarray(sol_ref),
                               atol=GATE)


def test_spmd_indivisible_fibers_raise():
    mesh = make_mesh(N_DEV)
    sys_sp = System(Params(**PARAMS))
    state = sys_sp.make_state(
        fibers=_fibers(n_fibers=12),  # 12 % 8 != 0
        background=BackgroundFlow.make(uniform=(1.0, 0.0, 0.0),
                                       dtype=jnp.float64))
    with pytest.raises(ValueError, match="grow_capacity"):
        spmd_shell_mode(state, mesh)


def test_spmd_indivisible_shell_raises():
    """A node-misaligned shell is never silently replicated: the mesh step
    takes it PADDED (its operators at 3 x 104 rows, as `shard_state`
    places them, the O(n) vectors replicated at 100 nodes), refuses in words
    a state whose operators are not padded, and replicates it only on the
    explicit opt-in."""
    parts = make_coupled_parts(100, 50, jnp.float64)  # 100 % 8 != 0
    mesh = make_mesh(N_DEV)
    sys_sp = System(Params(**PARAMS), shell_shape=SHAPE)
    state = _coupled_state(sys_sp, parts)
    with pytest.raises(ValueError, match="padded to 312 rows"):
        spmd_shell_mode(state, mesh)
    assert spmd_shell_mode(state, mesh,
                           allow_replicated_shell=True) == "replicated"
    placed = shard_state(state, mesh, step="spmd")
    assert spmd_shell_mode(placed, mesh) == "padded"
    assert placed.shell.M_inv.shape == (312, 312)
    assert placed.shell.nodes.shape == (100, 3)
    assert placed.shell.density.sharding.is_fully_replicated


# ------------------------------------------------- lowered-program contracts
# Ported onto the skelly-audit API (docs/audit.md): the collective
# inventory, the all-gather size bound, and the donation markers that used
# to live here as ad-hoc HLO regexes are now pinned by
# audit/contracts/step_spmd_d8.toml (+ step_single*.toml). These wrappers
# keep the per-commit pin in the test tier while the audit engine owns the
# single source of truth (ci/run_ci.sh gates the full program matrix).

@pytest.fixture(scope="module")
def spmd_audit():
    """(findings, contract) for the d8 coupled SPMD step — the same scene
    this module's parity tests run, traced + lowered once by the audit
    engine."""
    from skellysim_tpu.audit.engine import load_contract, run_program_audit
    from skellysim_tpu.audit.programs import get_program

    # run_program_audit re-loads the contract and already includes any
    # contract-validation findings; load_contract here only fetches the
    # parsed dict for the invariant assertions below
    contract, _ = load_contract("step_spmd_d8")
    return run_program_audit(get_program("step_spmd_d8")), contract


def test_spmd_collectives_bounded(spmd_audit):
    """The GMRES inner iteration issues a bounded, documented collective
    set: all-reduces (psum reductions), collective-permutes (source-block
    rings), and all-gathers of AT MOST shell-density size — never a
    fiber-cache-sized operand (the GSPMD failure mode)."""
    findings, contract = spmd_audit
    assert [f.render() for f in findings] == []
    # the contract itself must keep pinning the invariant this test exists
    # for: psum + ppermute present, and nothing gathered beyond the density
    colls = contract["collectives"]
    assert colls["all_reduce"]["count"] > 0
    assert colls["collective_permute"]["count"] > 0
    assert colls["all_gather"]["max_elems"] == 3 * 56  # the density vector


def test_spmd_state_donation_marked(spmd_audit):
    """The input state's buffers are marked donated at lowering time, so the
    sharded step does not double-buffer the pass-through leaves (the dense
    shell operators) per step."""
    findings, contract = spmd_audit
    assert [f.render() for f in findings] == []
    assert contract["donation"]["donated"] is True


def test_run_loop_donating_jit_marks_consumption():
    """`System._solve_jit_donated` (selected by the run loop when the
    adaptive gate is off) records input->output aliasing at lowering time —
    and the non-donating twin must NOT alias (rollback safety). Both pins
    live in the audit donation contracts now; this runs just that check."""
    from skellysim_tpu.audit.engine import run_program_audit
    from skellysim_tpu.audit.programs import get_program

    for name in ("step_single_donated", "step_single"):
        findings = run_program_audit(get_program(name), checks=["donation"])
        assert [f.render() for f in findings] == [], name


@pytest.mark.slow
def test_spmd_block_s4_coupled_parity(coupled_parts):
    """ISSUE 8: the communication-avoiding solver (gmres_block_s=4 — the
    configuration the d8 audit contract pins) solves the coupled scene on
    the mesh to the same backend-agreement gate as the single program, at
    the sequential cycle's iteration count."""
    params = Params(**PARAMS, gmres_block_s=4)
    sys_ref = System(params, shell_shape=SHAPE)
    _, sol_ref, info_ref = sys_ref.step(_coupled_state(sys_ref,
                                                       coupled_parts))
    assert bool(info_ref.converged)

    mesh = make_mesh(N_DEV)
    sys_sp = System(params, shell_shape=SHAPE)
    state = shard_state(_coupled_state(sys_sp, coupled_parts), mesh)
    _, sol_sp, info_sp = sys_sp.step_spmd(state, mesh)
    assert bool(info_sp.converged)
    assert abs(float(info_sp.residual_true)
               - float(info_ref.residual_true)) <= GATE
    np.testing.assert_allclose(np.asarray(sol_sp), np.asarray(sol_ref),
                               atol=GATE)

    # sequential-cycle reference on the SAME mesh scene: the s-step basis
    # must not cost extra iterations (ISSUE 8 acceptance: within 10%)
    sys_s1 = System(Params(**PARAMS), shell_shape=SHAPE)
    state1 = shard_state(_coupled_state(sys_s1, coupled_parts), mesh)
    _, _, info_s1 = sys_s1.step_spmd(state1, mesh)
    assert int(info_sp.iters) <= int(np.ceil(1.1 * int(info_s1.iters) / 4) * 4)


def test_spmd_contract_pins_batched_gram_rounds(spmd_audit):
    """The updated d8 contract IS the s-step pin (ISSUE 8 acceptance): the
    largest psum operand is the batched [(m+1)+s, s] Gram block — the
    sequential [m+1] per-iteration reduction shape is gone from the
    inventory, and the solver loop pays 2 rounds per s=4 iterations
    instead of 3 per iteration (>= 3x fewer rounds per cycle)."""
    findings, contract = spmd_audit
    assert [f.render() for f in findings] == []
    ar = contract["collectives"]["all_reduce"]
    # (gmres_restart rounded to a block multiple + 1 + s) * s = 420
    assert ar["max_elems"] == (100 + 1 + 4) * 4
