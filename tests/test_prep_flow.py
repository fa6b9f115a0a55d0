"""`prep`'s explicit flow of the wall force, evaluated only where the wall
pushes (`System.wall_force`, `periphery.exerts_steric_force`).

The steric wall force is the only source of `prep`'s fiber flow, and it is
zero by construction without a shell or for a shape kind with no force
law (``generic``, ``revolution``): both `prep`s (`System._prep` on one
device, the mesh step's in `parallel.spmd`) then skip the pair sum, on a
mesh its ring and the replicated rows' ``psum``.

* parity: the right-hand side, and one step's new state, solution and
  diagnostics, are bitwise those of the same step with `wall_force`
  patched to report a force (which evaluates the flow of the zero force,
  as the program did before the skip), on one device for the free-fiber
  scene and the toy cut of `oocyte_1024` (a ``revolution`` shell), on
  four devices for the free-fiber scene and the padded toy shell of
  `tests/test_spmd_padded_shell.py`; `velocity_at_targets`' steric term
  likewise;
* count: the pair sums under the ``prep`` scope of the traced step, 0
  without a shell and for ``revolution``, 1 for ``sphere`` and
  ``ellipsoid``, on one device and on four, with the ``prep_flow`` event
  that says so.
"""

import copy
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from skellysim_tpu.audit import fixtures
from skellysim_tpu.fibers import container as fc
from skellysim_tpu.obs import tracer as obs_tracer
from skellysim_tpu.obs.summarize import Summary
from skellysim_tpu.parallel import shard_state
from skellysim_tpu.parallel.mesh import make_mesh
from skellysim_tpu.parallel.spmd import build_spmd_step
from skellysim_tpu.periphery import periphery as peri
from skellysim_tpu.system import System
from skellysim_tpu.testing import make_coupled_parts

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "chipbench")
if BENCH not in sys.path:       # the benchmark's modules name each other bare
    sys.path.insert(0, BENCH)

import run as harness  # noqa: E402
import scene  # noqa: E402

CELL = scene.load_json(os.path.join(BENCH, "configs", "oocyte_1024.json"))


def _forced(monkeypatch):
    """`wall_force` reports a force whatever the shell: `prep` evaluates
    the flow of the (zero) wall force."""
    monkeypatch.setattr(System, "wall_force",
                        lambda self, state: (True, "force_law"))


def _recording_rhs(monkeypatch):
    """Every right-hand side `prep` assembles (each fiber bucket's, the
    shell's), as the device computed it: a list the step fills."""
    got = []

    def record(fn, pick):
        def wrapped(*args, **kwargs):
            out = fn(*args, **kwargs)
            jax.debug.callback(lambda a: got.append(np.asarray(a)),
                               pick(out))
            return out
        return wrapped

    monkeypatch.setattr(fc, "update_rhs_and_bc",
                        record(fc.update_rhs_and_bc, lambda c: c.RHS))
    monkeypatch.setattr(peri, "update_RHS", record(peri.update_RHS,
                                                   lambda r: r))
    return got


def _bits(a):
    a = np.asarray(a)
    return a.dtype.str, a.shape, a.tobytes()


def _step(system, state, monkeypatch, forced, mesh=None):
    """(rhs, (new state, solution, info)) of one step of a fresh copy of
    ``system``, with `wall_force` forced or as the program answers."""
    tr = obs_tracer.Tracer()
    with monkeypatch.context() as mp, obs_tracer.use(tr):
        if forced:
            _forced(mp)
        rhs = _recording_rhs(mp)
        fresh = System(system.params, shell_shape=system.shell_shape,
                       mesh=system.mesh)
        if mesh is None:
            out = fresh.step(state)
        else:
            out = fresh.step_spmd(state, mesh, donate=False)
        out = jax.block_until_ready(out)
        jax.effects_barrier()
    assert [e["wall_force"] for e in tr.events if e["ev"] == "prep_flow"] \
        == ["evaluated" if forced else "skipped"]
    # on a mesh each device reports its own rows, in no fixed order
    return sorted(map(_bits, rhs)), out


def _assert_bitwise(system, state, monkeypatch, mesh=None):
    rhs, out = _step(system, state, monkeypatch, False, mesh)
    rhs_f, out_f = _step(system, state, monkeypatch, True, mesh)
    assert rhs and rhs == rhs_f
    leaves = jax.tree_util.tree_leaves(out)
    leaves_f = jax.tree_util.tree_leaves(out_f)
    assert len(leaves) == len(leaves_f)
    for a, b in zip(leaves, leaves_f):
        assert _bits(a) == _bits(b)


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    old, scene.CACHE_DIR = scene.CACHE_DIR, str(
        tmp_path_factory.mktemp("cache"))
    old_log, harness.log = harness.log, lambda *_: None
    yield
    scene.CACHE_DIR, harness.log = old, old_log


def _oocyte_toy(tmp, n_fibers, mesh_devices=None):
    """The toy cut of `oocyte_1024` (287 shell nodes) through the
    harness's own build: (system, state)."""
    cfg = copy.deepcopy(CELL)
    cfg["fibers"]["n_fibers"] = n_fibers
    cfg["periphery"]["envelope"]["n_nodes_target"] = 300
    if mesh_devices:
        cfg["params"]["mesh_devices"] = mesh_devices
    system, state, *_ = harness.build(cfg, 2**31 + 7, str(tmp / "scene"))
    assert system.shell_shape.kind == "revolution"
    assert state.shell.n_nodes == 287
    return system, state


# ----------------------------------------------------------------- parity

@pytest.mark.parametrize("tier", ["full", "mixed"])
def test_free_fibers_step_is_bitwise_the_evaluated_step(monkeypatch, tier):
    system = fixtures.make_system(solver_precision=tier)
    _assert_bitwise(system, fixtures.free_state(system), monkeypatch)


def test_revolution_shell_step_is_bitwise_the_evaluated_step(cache,
                                                             tmp_path,
                                                             monkeypatch):
    system, state = _oocyte_toy(tmp_path, 12)
    _assert_bitwise(system, state, monkeypatch)


def test_velocity_at_targets_steric_term_is_bitwise(cache, tmp_path,
                                                    monkeypatch):
    system, state = _oocyte_toy(tmp_path, 12)
    system.params = dataclasses.replace(system.params,
                                        periphery_interaction_flag=True)
    _, (state, solution, _) = _step(system, state, monkeypatch, False)
    probes = np.asarray(state.shell.nodes)[:64] * 0.9

    def velocity(forced):
        with monkeypatch.context() as mp:
            if forced:
                _forced(mp)
            fresh = System(system.params, shell_shape=system.shell_shape)
            return np.asarray(fresh.velocity_at_targets(state, solution,
                                                        probes))

    v = velocity(False)
    assert np.all(np.isfinite(v)) and np.abs(v).max() > 0
    assert _bits(v) == _bits(velocity(True))


def test_mesh_free_fibers_step_is_bitwise_the_evaluated_step(monkeypatch):
    system = fixtures.make_system()
    mesh = make_mesh(4)
    state = shard_state(fixtures.free_state(system), mesh, step="spmd")
    _assert_bitwise(system, state, monkeypatch, mesh)


def test_mesh_padded_shell_step_is_bitwise_the_evaluated_step(cache,
                                                              tmp_path,
                                                              monkeypatch):
    system, state = _oocyte_toy(tmp_path, 16, mesh_devices=4)
    assert system.mesh.size == 4
    state = shard_state(state, system.mesh, step="spmd")
    _assert_bitwise(system, state, monkeypatch, system.mesh)


# ------------------------------------------------------------------ count

def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (list, tuple)) else (v,)):
            if isinstance(x, jcore.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jcore.Jaxpr):
                yield x


def _pair_sums_under(jaxpr, scope: str, stack=()):
    """Pair-sum evaluations under the named scope ``scope``: each call of
    a `stokeslet_direct` / `stresslet_direct` tile outside a ring, and
    each ring (the loop whose hops ``ppermute`` under ``ring-step``)."""
    n = 0
    for e in jaxpr.eqns:
        path = stack + tuple(str(e.source_info.name_stack).split("/"))
        name = str(e.params.get("name", ""))
        if scope in path and name in ("stokeslet_direct",
                                      "stresslet_direct"):
            n += 1
            continue
        if scope in path and e.primitive.name in ("scan", "while") and any(
                _has_ring_hop(j) for j in _sub_jaxprs(e)):
            n += 1
            continue
        for j in _sub_jaxprs(e):
            n += _pair_sums_under(j, scope, path + (name,))
    return n


def _has_ring_hop(jaxpr):
    for e in jaxpr.eqns:
        if (e.primitive.name == "ppermute"
                and "ring-step" in str(e.source_info.name_stack)):
            return True
        if any(_has_ring_hop(j) for j in _sub_jaxprs(e)):
            return True
    return False


def _shell_scene(kind):
    """(system, state): the audit's 16 fibers in its 56-node shell (no
    body), the wall of ``kind``; ``None``: no shell."""
    if kind is None:
        system = fixtures.make_system()
        return system, fixtures.free_state(system)
    shell, _, _ = make_coupled_parts(fixtures.SHELL_NODES,
                                     fixtures.BODY_NODES, jnp.float64)
    shape = {"sphere": peri.PeripheryShape(kind="sphere", radius=6.0),
             "ellipsoid": peri.PeripheryShape(kind="ellipsoid",
                                              abc=(6.0, 6.0, 6.0)),
             "revolution": peri.PeripheryShape(kind="revolution",
                                               bounds=(-6.0, 6.0))}[kind]
    system = System(fixtures.make_system().params, shell_shape=shape)
    return system, system.make_state(
        fibers=fixtures.make_fibers(seed=7, box=2.0), shell=shell)


@pytest.mark.parametrize("n_dev", [1, 4])
@pytest.mark.parametrize("kind,pair_sums,wall_force,reason", [
    (None, 0, "skipped", "no_shell"),
    ("revolution", 0, "skipped", "no_force_law"),
    ("sphere", 1, "evaluated", "force_law"),
    ("ellipsoid", 1, "evaluated", "force_law"),
])
def test_prep_pair_sums_follow_the_wall_force(n_dev, kind, pair_sums,
                                              wall_force, reason):
    system, state = _shell_scene(kind)
    assert peri.exerts_steric_force(system.shell_shape) == (pair_sums == 1)
    tr = obs_tracer.Tracer()
    with obs_tracer.use(tr):
        if n_dev == 1:
            closed = jax.make_jaxpr(system._solve_impl)(state)
        else:
            mesh = make_mesh(n_dev)
            step = build_spmd_step(system, mesh, state, donate=False)
            closed = step.trace(shard_state(state, mesh,
                                            step="spmd")).jaxpr
    assert _pair_sums_under(closed.jaxpr, "prep") == pair_sums
    # the Krylov loop keeps its pair sums whatever the wall
    assert _pair_sums_under(closed.jaxpr, "gmres") > 0
    events = [e for e in tr.events if e["ev"] == "prep_flow"]
    assert len(events) == 1
    ev = events[0]
    assert (ev["wall_force"], ev["reason"], ev["shape"]) == (
        wall_force, reason, kind or "-")
    assert (ev["evaluator"], ev["impl"]) == (
        ("direct" if n_dev == 1 else "ring"), "exact")
    report = Summary()
    report.add_line(json.dumps(ev))
    text = report.render()
    assert "== prep flow (explicit wall force) ==" in text
    assert (f"wall_force={wall_force} reason={reason} shape={kind or '-'} "
            f"evaluator={ev['evaluator']} impl=exact") in text
