"""`kernel_impl = "auto"`: the f32 pair tile follows the backend and the
operands' dtype (`ops.kernels.resolve_impl`), at the seam where the tile is
chosen.

* on a CPU "auto" is "exact" to the bit; with the backend read as a TPU, f32
  operands take "pallas" and an f64 operand "exact" without a fault, while
  "pallas" BY NAME with an f64 operand still says `pallas_tile_fallback`;
  every other name passes through;
* `Params` and the TOML schema default to it, `System` admits it;
* the ring's own dispatch (`_ring_block`, `fused_ring_mode`) never sees it;
* the toy cut of `examples/ellipsoid` steps to the same bits under "auto"
  and "exact" on a CPU;
* a built `System` says which tile its loop takes once a build
  (`pair_tile`), `obs summarize` prints it, and a loop that takes another
  tile than the name resolved to says so as a fault;
* the stresslet tile against `stresslet_block` on a shell's own kind of
  source (`2 eta n (x) rho`) at sizes that are no multiples of the tile.
"""

import copy
import logging
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from skellysim_tpu.obs import tracer as obs_tracer
from skellysim_tpu.obs.summarize import Summary
from skellysim_tpu.ops import kernels
from skellysim_tpu.parallel import ring
from skellysim_tpu.parallel.mesh import FIBER_AXIS, make_mesh
from skellysim_tpu.params import Params
from skellysim_tpu.system import System

KINDS = {"stokeslet": (kernels.stokeslet_direct, (3,)),
         "stresslet": (kernels.stresslet_direct, (3, 3))}


def _cloud(kind, dtype, n_src=150, n_trg=70, seed=5):
    rng = np.random.default_rng(seed)
    tail = KINDS[kind][1]
    return (jnp.asarray(rng.uniform(-2, 2, (n_src, 3)), dtype),
            jnp.asarray(rng.uniform(-2, 2, (n_trg, 3)), dtype),
            jnp.asarray(rng.standard_normal((n_src,) + tail), dtype))


def _as_tpu(monkeypatch):
    """A TPU in name only: resolution and tracing need no chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _faults(tr):
    return [e for e in tr.events if e["ev"] == "fault"]


# ------------------------------------------------------------- the resolver

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_auto_is_exact_to_the_bit_on_a_cpu(kind, dtype):
    fn = KINDS[kind][0]
    r_src, r_trg, pay = _cloud(kind, dtype)
    assert kernels.resolve_impl("auto", r_trg, r_src, pay) == "exact"
    np.testing.assert_array_equal(
        np.asarray(fn(r_src, r_trg, pay, 1.3, impl="auto")),
        np.asarray(fn(r_src, r_trg, pay, 1.3, impl="exact")))


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_auto_on_a_tpu_follows_the_operands(monkeypatch, kind):
    """f32 operands take the Pallas tile; ONE f64 operand takes the exact
    one, and that is the rule, not a fallback: no fault. "pallas" by name
    keeps its warning and its `pallas_tile_fallback` event."""
    _as_tpu(monkeypatch)
    r_src, r_trg, pay = _cloud(kind, jnp.float32)
    tr = obs_tracer.Tracer()
    with obs_tracer.use(tr):
        assert kernels.resolve_impl("auto", r_trg, r_src, pay) == "pallas"
        assert kernels.resolve_impl(
            "auto", r_trg, r_src, pay.astype(jnp.float64)) == "exact"
        assert kernels.resolve_impl(
            "auto", r_trg.astype(jnp.float64), r_src, pay) == "exact"
    assert not _faults(tr)
    with obs_tracer.use(tr):
        assert kernels.resolve_impl("pallas", r_trg, r_src, pay) == "pallas"
        assert not _faults(tr)
        assert kernels.resolve_impl(
            "pallas", r_trg, r_src, pay.astype(jnp.float64)) == "exact"
    (fault,) = _faults(tr)
    assert (fault["kind"], fault["reason"]) == ("pallas_tile_fallback",
                                                "float64-operand")


@pytest.mark.parametrize("backend", ["cpu", "gpu", "tpu"])
@pytest.mark.parametrize("name", ["exact", "mxu", "df", "pallas",
                                  "pallas_df"])
def test_explicit_names_pass_through(monkeypatch, backend, name):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert kernels.resolve_impl(name, jnp.float32) == name
    # the resolver takes dtypes as well as arrays
    assert kernels.resolve_impl("auto", jnp.float32, jnp.float32) == (
        "pallas" if backend == "tpu" else "exact")
    assert kernels.resolve_impl("auto", jnp.float32, jnp.float64) == "exact"


def test_the_default_is_auto_in_params_and_in_the_schema():
    from skellysim_tpu.config import schema

    assert Params().kernel_impl == "auto"
    assert schema.Params().kernel_impl == Params().kernel_impl
    assert schema.to_runtime_params(schema.Params()).kernel_impl == "auto"
    System(Params(adaptive_timestep_flag=False))
    with pytest.raises(ValueError, match="'auto'"):
        System(Params(kernel_impl="automatic", adaptive_timestep_flag=False))


# ------------------------------------------------------------ the ring seam

def test_ring_dispatch_refuses_an_unresolved_name():
    with pytest.raises(ValueError, match="no 'auto' tile"):
        ring._ring_block("auto", kernels.stokeslet_block,
                         kernels.stokeslet_block_mxu,
                         "stokeslet_pallas_block")


@pytest.mark.parametrize("backend,taken", [("cpu", "exact"),
                                           ("tpu", "pallas")])
def test_the_ring_seam_never_sees_auto(monkeypatch, backend, taken):
    """`ring_stokeslet`, `ring_stresslet` and `ring_flow_local` resolve the
    name before `_ring_block`, `_pallas_interpret` and `fused_ring_mode`
    compare it. Traced only (`eval_shape`): as a TPU nothing is lowered."""
    seen = []
    for name in ("_ring_block", "fused_ring_mode", "_pallas_interpret"):
        real = getattr(ring, name)

        def spy(impl, *a, _real=real, _name=name, **kw):
            seen.append((_name, impl))
            return _real(impl, *a, **kw)

        monkeypatch.setattr(ring, name, spy)
    monkeypatch.setenv("SKELLY_FUSED_RING", "0")   # the ppermute ring
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    mesh = make_mesh(4)
    # shapes of this test's own: a jitted ring caches its trace by shape
    n = {"cpu": 96, "tpu": 104}[backend]
    r_src, r_trg, f = _cloud("stokeslet", jnp.float32, n, n)
    S = _cloud("stresslet", jnp.float32, n, n)[2]

    def local(trg, src, pay):
        return ring.ring_flow_local("stokeslet", "auto", trg, src, pay, 1.0,
                                    axis_name=FIBER_AXIS, n_dev=4)

    spec = P(FIBER_AXIS)
    jax.eval_shape(lambda: ring.ring_stokeslet(r_src, r_trg, f, 1.0,
                                               mesh=mesh, impl="auto"))
    jax.eval_shape(lambda: ring.ring_stresslet(r_src, r_trg, S, 1.0,
                                               mesh=mesh, impl="auto"))
    jax.eval_shape(lambda: jax.shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)(r_trg, r_src, f))
    assert {name for name, _ in seen} == {"_ring_block", "fused_ring_mode",
                                          "_pallas_interpret"}
    assert {impl for _, impl in seen} == {taken}


# ------------------------------------------------------- the step, the toy

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "chipbench")


@pytest.fixture(scope="module")
def harness(tmp_path_factory):
    if BENCH not in sys.path:   # the benchmark's modules name each other bare
        sys.path.insert(0, BENCH)
    import run
    import scene

    old, scene.CACHE_DIR = scene.CACHE_DIR, str(
        tmp_path_factory.mktemp("cache"))
    log, run.log = run.log, lambda *_: None
    yield run, scene
    scene.CACHE_DIR, run.log = old, log


def test_ellipsoid_toy_steps_to_the_same_bits(harness, tmp_path):
    """8 clamped fibers on a 300-node ellipsoid, two `System.run` steps in
    the mixed tier (the f32 loop is where the name is read) and one in the
    full: "auto", the default the toy's file leaves alone, against "exact"."""
    run, scene = harness
    toy = scene.load_json(os.path.join(BENCH, "tests", "toy",
                                       "ellipsoid_toy.json"))
    assert "kernel_impl" not in toy["params"]
    ends = {}
    for tier, steps in (("mixed", 2), ("full", 1)):
        for impl in ("auto", "exact"):
            cfg = copy.deepcopy(toy)
            cfg["params"].update(solver_precision=tier)
            if impl != "auto":
                cfg["params"]["kernel_impl"] = impl
            system, state, rng, writer, _, _ = run.build(
                cfg, 2**31 + 9, str(tmp_path / f"{tier}_{impl}"))
            assert system.params.kernel_impl == impl
            state = system.run(state, writer=writer.write_frame, rng=rng,
                               max_steps=steps)
            writer.close()
            ends[tier, impl] = run.snapshot(state)
        a, tree_a = jax.tree_util.tree_flatten(ends[tier, "auto"])
        b, tree_b = jax.tree_util.tree_flatten(ends[tier, "exact"])
        assert tree_a == tree_b and len(a) > 8
        for leaf_a, leaf_b in zip(a, b):
            np.testing.assert_array_equal(leaf_a, leaf_b)


def _tiny_system(dtype=jnp.float64, **params):
    from __graft_entry__ import _make_system

    params.setdefault("kernel_impl", "auto")
    params.setdefault("n_fibers", 2)
    return _make_system(n_nodes=16, dtype=dtype, **params)


def _announced(system, state, caplog):
    tr = obs_tracer.Tracer()
    with obs_tracer.use(tr), caplog.at_level(logging.INFO, "skellysim_tpu"):
        jax.eval_shape(system._solve_impl, state)
    (ev,) = [e for e in tr.events if e["ev"] == "pair_tile"]
    return ev, tr


@pytest.mark.parametrize("backend,requested,dtype,tier,impl", [
    ("tpu", "auto", jnp.float64, "mixed", "pallas"),
    ("tpu", "pallas", jnp.float64, "mixed", "pallas"),
    ("tpu", "auto", jnp.float32, "full", "pallas"),
    ("tpu", "exact", jnp.float64, "mixed", "exact"),
    ("cpu", "auto", jnp.float64, "mixed", "exact"),
    ("cpu", "auto", jnp.float64, "full", "exact")])
def test_pair_tile_is_announced_once_a_build(monkeypatch, caplog, backend,
                                             requested, dtype, tier, impl):
    """One `pair_tile` event a trace of the solve: the tile, the name asked
    for, the backend and the loop's dtype; the same line in the log and in
    `obs summarize`; no fault where the loop takes what the name came to."""
    system, state = _tiny_system(dtype, solver_precision=tier,
                                 kernel_impl=requested)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    ev, tr = _announced(system, state, caplog)
    loop = "float32" if tier == "mixed" else jnp.dtype(dtype).name
    want = dict(impl=impl, requested=requested, backend=backend, dtype=loop)
    assert {k: ev[k] for k in want} == want
    line = (f"pair_tile impl={impl} requested={requested} "
            f"backend={backend} dtype={loop}")
    assert line in caplog.text
    assert not _faults(tr)
    report = Summary()
    for e in tr.events:
        report.add_record(e)
    assert line in report.render()
    if tier == "mixed" and dtype == jnp.float64:
        assert "refine_tile impl=" in report.render()


def test_pair_tile_mismatch_is_a_fault(monkeypatch, caplog):
    """The full tier on a TPU: the name resolves to the Pallas tile for f32
    operands and the loop's f64 sums take the exact one; the run says so."""
    system, state = _tiny_system(solver_precision="full")
    _as_tpu(monkeypatch)
    ev, tr = _announced(system, state, caplog)
    assert (ev["impl"], ev["dtype"]) == ("exact", "float64")
    (fault,) = _faults(tr)
    assert fault["kind"] == "pair_tile_mismatch"
    assert (fault["resolved"], fault["taken"]) == ("pallas", "exact")
    report = Summary()
    report.add_record(fault)
    assert "pair_tile_mismatch" in report.render()


def test_mesh_step_announces_its_pair_tile():
    """`parallel/spmd.py` goes through the same function."""
    from skellysim_tpu.parallel.mesh import shard_state

    mesh = make_mesh(4)
    system, state = _tiny_system(solver_precision="mixed", n_fibers=4,
                                 pair_evaluator="ring", mesh=mesh)
    tr = obs_tracer.Tracer()
    with obs_tracer.use(tr):
        system.step_spmd(shard_state(state, mesh), mesh)
    (ev,) = [e for e in tr.events if e["ev"] == "pair_tile"]
    assert (ev["impl"], ev["requested"], ev["dtype"]) == ("exact", "auto",
                                                          "float32")
    assert not _faults(tr)


# ------------------------------------------------------- the stresslet tile

@pytest.mark.parametrize("n_src,n_trg", [(300, 77), (2100, 130)])
def test_stresslet_tile_on_a_shell_shaped_source(n_src, n_trg):
    """The shell's double layer as `periphery.flow` hands it over: `f_dl =
    2 eta n (x) rho` on a closed surface's nodes, targets inside, through
    the seam at the tile's own shape (128 x 2,048; interpret mode), sizes
    that are no multiples of it, against `stresslet_block`."""
    rng = np.random.default_rng(11)
    eta = 0.7
    n = rng.standard_normal((n_src, 3))
    n /= np.linalg.norm(n, axis=1, keepdims=True)
    nodes = n * np.array([7.8, 4.16, 4.16])
    rho = rng.standard_normal((n_src, 3))
    f_dl = 2.0 * eta * n[:, :, None] * rho[:, None, :]
    r_trg = rng.uniform(-2, 2, (n_trg, 3))
    args = [jnp.asarray(a, jnp.float32) for a in (nodes, r_trg, f_dl)]
    u = kernels.stresslet_direct(*args, eta, impl="pallas")
    ref = kernels.stresslet_block(args[1], args[0], args[2]) / (
        8.0 * math.pi * eta)
    assert u.shape == (n_trg, 3) and u.dtype == jnp.float32
    err = np.abs(np.asarray(u) - np.asarray(ref)).max()
    assert err < 2e-5 * np.abs(np.asarray(ref)).max()
