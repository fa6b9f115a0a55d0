"""skelly-scenario: device-side dynamic instability on the batched paths.

Pins the ISSUE-13 acceptance criteria:

* the device DI update (`scenarios.di_device`) applies EXACTLY the host
  oracle's nucleation/catastrophe update under injected deterministic
  draws (shared `system.di_rates` math; node geometry to XLA-vs-libm
  roundoff);
* a B-member confined (periphery + body + growing/shrinking fibers)
  dynamic-instability sweep runs on the ensemble vmap path with member
  trajectories matching sequential host-loop `System.run` executions at
  the vmap-plan tolerance (rtol 1e-9 — the same pin test_ensemble.py uses
  for vmap-vs-unroll);
* within-bucket nucleation/catastrophe produce ZERO `observed_jit`
  compile events, and a capacity overflow reseats onto the next bucket
  rung with exactly one new trace per rung (`trace_counting_jit`);
* guard quarantine semantics are intact under DI: a poisoned DI lane
  retires ``failed`` while its siblings' trajectories continue untouched.
"""

import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

from skellysim_tpu.bodies import bodies as bd
from skellysim_tpu.ensemble.runner import EnsembleRunner
from skellysim_tpu.ensemble.scheduler import EnsembleScheduler, MemberSpec
from skellysim_tpu.fibers import container as fc
from skellysim_tpu.obs import tracer as obs_tracer
from skellysim_tpu.params import DynamicInstability, Params
from skellysim_tpu.periphery.precompute import precompute_body
from skellysim_tpu.scenarios import (ScenarioEnsemble, di_device,
                                     ensure_di_capacity)
from skellysim_tpu.system import System, apply_dynamic_instability
from skellysim_tpu.testing import trace_counting_jit
from skellysim_tpu.utils.rng import SimRNG

N_SITES = 6
BODY_R = 0.5


@pytest.fixture(scope="module")
def body_group():
    pre = precompute_body("sphere", 40, radius=BODY_R)
    rng = np.random.default_rng(11)
    sites = rng.standard_normal((N_SITES, 3))
    sites = BODY_R * sites / np.linalg.norm(sites, axis=1, keepdims=True)
    return bd.make_group(pre["node_positions_ref"], pre["node_normals_ref"],
                         pre["node_weights"],
                         nucleation_sites_ref=sites[None], radius=BODY_R)


def di_params(**kw):
    di_kw = dict(n_nodes=8, v_growth=0.2, f_catastrophe=0.5,
                 nucleation_rate=60.0, min_length=0.4,
                 radius=0.0125, bending_rigidity=0.01)
    di_kw.update(kw.pop("di", {}))
    base = dict(eta=1.0, dt_initial=0.02, dt_write=0.02, t_final=0.08,
                gmres_tol=1e-10, adaptive_timestep_flag=False,
                dynamic_instability=DynamicInstability(**di_kw))
    base.update(kw)
    return Params(**base)


def seed_fibers(capacity=8, n_active=2, n_nodes=8, shift=0.0):
    """`n_active` live unbound fibers in a `capacity`-slot batch."""
    x = np.tile(np.linspace(0.0, 1.0, n_nodes)[None, :, None],
                (n_active, 1, 3))
    x += (1.5 + shift + np.arange(n_active))[:, None, None]
    g = fc.make_group(x, lengths=1.0, bending_rigidity=0.01, radius=0.0125)
    return fc.grow_capacity(g, capacity)


def device_group(g):
    """Round-trip every array leaf to a device array (grow_capacity edits
    host-side; stacked ensembles want jnp leaves)."""
    return type(g)(*[jnp.asarray(leaf) if name != "rt_mats"
                     and leaf is not None else leaf
                     for name, leaf in zip(g._fields, g)])


# ------------------------------------------------- injected deterministic draws
#
# One pseudo-draw schedule consumed by BOTH paths: the device sample_fn
# derives (member key, step) from the RNG carry the runner threads through
# the trace; the host stub mirrors it by counting its uniform() calls. Site
# priorities ascend, so the device argsort picks free sites in flat-table
# order — exactly the host's pop(j=0) sequence.

def _u(mkey, step, i):
    return ((mkey * 131 + step * 31 + i * 7) % 97) / 97.0


def _n_raw(mkey, step):
    return (mkey + step) % 3


def injected_sample_fn(di_rng, lam, capacity, n_sites, dtype):
    mkey = di_rng[1]
    step = di_rng[2] // di_device.DRAWS_PER_STEP
    u_cat = ((mkey * 131 + step * 31
              + jnp.arange(capacity, dtype=jnp.int32) * 7) % 97) / 97.0
    return di_device.DIDraws(
        u_cat=u_cat.astype(dtype),
        n_raw=((mkey + step) % 3).astype(jnp.int32),
        u_site=(jnp.arange(max(n_sites, 1), dtype=dtype)[:n_sites]
                / max(n_sites, 1)))


class _SeqStream:
    """Host mirror of `injected_sample_fn` with the real Stream's API."""

    def __init__(self, mkey, seed=0, stream_id=None, counter=0):
        self.mkey = mkey
        self.seed, self.stream_id = seed, mkey if stream_id is None else stream_id
        self.step = -1

    @property
    def counter(self):
        return max(self.step, 0) * di_device.DRAWS_PER_STEP

    def uniform(self, low=0.0, high=1.0, size=None):
        self.step += 1
        return np.array([_u(self.mkey, self.step, i) for i in range(size)])

    def poisson_int(self, lam, size=None):
        return int(_n_raw(self.mkey, self.step))

    def uniform_int(self, low, high, size=None):
        return 0

    def dump(self):
        return f"{self.seed}:{self.stream_id}:{self.counter}"


class _SeqRNG:
    def __init__(self, mkey):
        self.distributed = _SeqStream(mkey)
        self.shared = _SeqStream(mkey + 10_000)

    def dump_state(self):
        return [["shared", self.shared.dump()],
                ["distributed", self.distributed.dump()]]


def member_rng_pair(i, seed=5):
    """(device SimRNG, host mirror) for ensemble member ``i`` — the device
    carry's stream id (2i+3) is the shared member key."""
    return SimRNG(seed).member(i), _SeqRNG(2 * i + 3)


# ------------------------------------------------------------ update parity

def test_device_matches_host_injected_draws(body_group):
    """One DI update, same injected draws: every per-fiber field matches
    the host oracle bitwise except nucleated node geometry (XLA vs libm
    normalization, <= a few ulp)."""
    params = di_params()
    system = System(params)
    fibers = seed_fibers(capacity=8, n_active=3)
    # bind fiber 0 to site 0 so occupancy/rate bookkeeping is exercised
    bb = np.asarray(fibers.binding_body).copy()
    bs = np.asarray(fibers.binding_site).copy()
    bb[0], bs[0] = 0, 0
    fibers = device_group(fibers._replace(binding_body=bb, binding_site=bs))
    state = system.make_state(fibers=fibers, bodies=body_group)

    stats = {}
    host = apply_dynamic_instability(state, params, _SeqRNG(3), stats=stats)
    dev, info = di_device.di_update(
        state, params, jnp.asarray([0, 3, 0], jnp.int32),
        sample_fn=injected_sample_fn)
    hf, df = host.fibers, dev.fibers
    for name in ("active", "binding_body", "binding_site", "config_rank",
                 "minus_clamped", "plus_pinned"):
        np.testing.assert_array_equal(np.asarray(getattr(hf, name)),
                                      np.asarray(getattr(df, name)), name)
    for name in ("length", "length_prev", "v_growth", "bending_rigidity",
                 "radius", "penalty", "beta_tstep", "tension"):
        np.testing.assert_array_equal(np.asarray(getattr(hf, name)),
                                      np.asarray(getattr(df, name)), name)
    act = np.asarray(hf.active)
    np.testing.assert_allclose(np.asarray(df.x)[act], np.asarray(hf.x)[act],
                               rtol=1e-14, atol=1e-15)
    assert int(info.nucleations) == stats["nucleations"]
    assert int(info.catastrophes) == stats["catastrophes"]
    assert int(info.active_fibers) == act.sum()
    assert not bool(info.needs_growth)


def test_device_catastrophe_statistics():
    """Natural draws: the survival fraction over one step reproduces
    exp(-dt * f_cat) (the host oracle's statistical pin, device-side)."""
    params = di_params(di=dict(n_nodes=16, f_catastrophe=1.0,
                               nucleation_rate=0.0), dt_initial=0.05)
    system = System(params)
    nf = 2000
    x = np.tile(np.linspace(0, 1, 16)[None, :, None], (nf, 1, 3))
    fibers = device_group(fc.make_group(x, lengths=1.0,
                                        bending_rigidity=0.01,
                                        radius=0.0125))
    state = system.make_state(fibers=fibers)
    state = state._replace(dt=jnp.asarray(0.05, jnp.float64))
    _, info = di_device.di_update(
        state, params, jnp.asarray([0, 3, 0], jnp.int32))
    frac = float(info.active_fibers) / nf
    expected = np.exp(-0.05 * 1.0)
    assert frac == pytest.approx(expected, abs=3 * np.sqrt(expected / nf))


def test_needs_growth_aborts_update_bitwise(body_group):
    """A nucleation burst beyond the free slots aborts the WHOLE update:
    the state comes back bitwise identical and the info reports only the
    flag (the lane freeze + reseat contract)."""
    params = di_params(di=dict(n_nodes=8, f_catastrophe=0.0,
                               nucleation_rate=60.0))
    system = System(params)
    fibers = device_group(seed_fibers(capacity=2, n_active=2))
    state = system.make_state(fibers=fibers, bodies=body_group)

    def burst(di_rng, lam, capacity, n_sites, dtype):
        d = injected_sample_fn(di_rng, lam, capacity, n_sites, dtype)
        return d._replace(n_raw=jnp.int32(3), u_cat=jnp.zeros_like(d.u_cat))

    out, info = di_device.di_update(
        state, params, jnp.asarray([0, 3, 0], jnp.int32), sample_fn=burst)
    assert bool(info.needs_growth)
    assert int(info.nucleations) == 0 and int(info.catastrophes) == 0
    for name, leaf in zip(state.fibers._fields, state.fibers):
        if name == "rt_mats" or leaf is None:
            continue
        np.testing.assert_array_equal(np.asarray(leaf),
                                      np.asarray(getattr(out.fibers, name)),
                                      name)


def test_ensure_di_capacity_and_validation(body_group):
    params = di_params()
    system = System(params)
    # fiber-less scene: placeholder group seeded from the first site
    state = ensure_di_capacity(system.make_state(bodies=body_group), params)
    g = state.fibers
    assert isinstance(g, fc.FiberGroup) and g.n_fibers >= 1
    assert not np.asarray(g.active).any()
    assert np.isfinite(np.asarray(g.x)).all()
    # a resolution mismatch fails loudly at assembly
    bad = di_params(di=dict(n_nodes=16))
    with pytest.raises(ValueError, match="resolution"):
        di_device.check_di_state(state, bad)
    # mixed-resolution tuples are a host-loop-only configuration
    two = (seed_fibers(capacity=2), seed_fibers(capacity=2, n_nodes=16))
    with pytest.raises(ValueError, match="single"):
        ensure_di_capacity(
            system.make_state(fibers=two, bodies=body_group), params)


# ----------------------------------------------------- batched sweep pins

def _scenario_members(system, body_group, n, capacity=8, rng_pairs=None):
    members, hosts = [], {}
    for i in range(n):
        fibers = device_group(seed_fibers(capacity=capacity, n_active=2,
                                          shift=0.2 * i))
        state = system.make_state(fibers=fibers, bodies=body_group)
        dev_rng, host_rng = (rng_pairs[i] if rng_pairs
                             else member_rng_pair(i))
        members.append(MemberSpec(member_id=f"m{i}", state=state,
                                  t_final=system.params.t_final,
                                  rng=dev_rng))
        hosts[f"m{i}"] = (state, host_rng)
    return members, hosts


@pytest.mark.slow  # two compiled coupled programs (solo + vmap batch), ~1 min
def test_vmap_sweep_matches_host_loop_injected(body_group):
    """Ensemble-leg acceptance pin (free-space half): B=3 DI members on the
    vmap path, injected deterministic draws — per-member trajectories match
    three sequential host-loop `System.run` executions at the vmap-plan
    tolerance, and the scheduler's metrics carry the population
    trajectory."""
    params = di_params()
    system = System(params)
    members, hosts = _scenario_members(system, body_group, 3)

    seq = {}
    for mid, (state, host_rng) in hosts.items():
        frames = []
        system.run(state, rng=host_rng,
                   writer=lambda s, sol, **kw: frames.append(s))
        seq[mid] = frames

    runner = EnsembleRunner(system, di_sample_fn=injected_sample_fn)
    got = {m.member_id: [] for m in members}
    records = []
    se = ScenarioEnsemble(
        system, members, batch=3, runner=runner, metrics=records.append,
        writer=lambda mid, s, rng_state=None: got[mid].append(s))
    finished = se.run(max_rounds=50)
    assert sorted(finished) == sorted(got)
    assert se.reseats == 0

    for mid, frames in got.items():
        ref = seq[mid]
        assert len(ref) == len(frames) > 0, mid
        for k, (a, b) in enumerate(zip(ref, frames)):
            assert float(a.time) == float(b.time)
            np.testing.assert_array_equal(np.asarray(a.fibers.active),
                                          np.asarray(b.fibers.active),
                                          f"{mid} frame {k} active")
            np.testing.assert_array_equal(np.asarray(a.fibers.binding_site),
                                          np.asarray(b.fibers.binding_site))
            act = np.asarray(a.fibers.active)
            np.testing.assert_allclose(
                np.asarray(b.fibers.x)[act], np.asarray(a.fibers.x)[act],
                rtol=1e-9, atol=1e-12,
                err_msg=f"{mid} frame {k} positions")
            np.testing.assert_allclose(
                np.asarray(b.fibers.length), np.asarray(a.fibers.length),
                rtol=1e-12, atol=0)
    steps = [r for r in records if r.get("event") == "step"]
    assert sum(r["nucleations"] for r in steps) > 0
    assert all("active_fibers" in r for r in steps)


@pytest.fixture(scope="module")
def shell_pair():
    """(PeripheryState, PeripheryShape): a small confining sphere."""
    import jax

    from skellysim_tpu.periphery import periphery as peri
    from skellysim_tpu.periphery.precompute import precompute_periphery

    assert jax.config.jax_enable_x64
    data = precompute_periphery("sphere", n_nodes=60, radius=2.5, eta=1.0)
    state = peri.make_state(data["nodes"], data["normals"],
                            data["quadrature_weights"],
                            data["stresslet_plus_complementary"],
                            data["M_inv"], dtype=jnp.float64)
    return state, peri.PeripheryShape(kind="sphere", radius=2.5)


@pytest.mark.slow  # coupled periphery programs, solo + vmap (~2 min on CPU)
def test_confined_sweep_matches_host_loop(body_group, shell_pair):
    """THE oocyte-class acceptance pin (ROADMAP item 5, ensemble leg): a
    B-member CONFINED dynamic-instability sweep — periphery + nucleating
    body + growing/shrinking fibers — runs on the ensemble vmap path, and
    with injected deterministic draws each member's trajectory matches the
    sequential host-loop `System.run` at the vmap-plan tolerance."""
    shell, shape = shell_pair
    params = di_params(t_final=0.06)
    system = System(params, shell_shape=shape)

    B = 2
    members, hosts = [], {}
    for i in range(B):
        fibers = device_group(seed_fibers(capacity=8, n_active=2,
                                          shift=0.15 * i))
        # keep the seeded fibers inside the confining sphere
        fibers = fibers._replace(x=fibers.x * 0.4)
        state = system.make_state(fibers=fibers, bodies=body_group,
                                  shell=shell)
        dev_rng, host_rng = member_rng_pair(i)
        members.append(MemberSpec(member_id=f"m{i}", state=state,
                                  t_final=params.t_final, rng=dev_rng))
        hosts[f"m{i}"] = (state, host_rng)

    seq = {}
    for mid, (state, host_rng) in hosts.items():
        frames = []
        system.run(state, rng=host_rng,
                   writer=lambda s, sol, **kw: frames.append(s))
        seq[mid] = frames
        assert any(np.asarray(f.fibers.active).sum()
                   > np.asarray(state.fibers.active).sum()
                   for f in frames), "confined host run never nucleated"

    runner = EnsembleRunner(system, di_sample_fn=injected_sample_fn)
    got = {m.member_id: [] for m in members}
    se = ScenarioEnsemble(
        system, members, batch=B, runner=runner,
        writer=lambda mid, s, rng_state=None: got[mid].append(s))
    finished = se.run(max_rounds=40)
    assert sorted(finished) == sorted(got)

    for mid, frames in got.items():
        ref = seq[mid]
        assert len(ref) == len(frames) > 0, mid
        for k, (a, b) in enumerate(zip(ref, frames)):
            assert float(a.time) == float(b.time)
            np.testing.assert_array_equal(np.asarray(a.fibers.active),
                                          np.asarray(b.fibers.active))
            act = np.asarray(a.fibers.active)
            np.testing.assert_allclose(
                np.asarray(b.fibers.x)[act], np.asarray(a.fibers.x)[act],
                rtol=1e-9, atol=1e-12,
                err_msg=f"{mid} confined frame {k}")
            np.testing.assert_allclose(
                np.asarray(b.shell.density), np.asarray(a.shell.density),
                rtol=1e-8, atol=1e-11)


@pytest.mark.slow  # compiles one rung program per capacity (~2 min on CPU)
def test_growth_reseat_zero_compiles_one_trace_per_rung(body_group):
    """THE warm-program pin: within-bucket nucleation/catastrophe produce
    ZERO observed_jit compile events after a rung warms, and a capacity
    overflow reseats onto the next geometric rung with EXACTLY one new
    trace (trace_counting_jit over the shared batched step)."""
    params = di_params(di=dict(n_nodes=8, f_catastrophe=0.2,
                               nucleation_rate=80.0), t_final=0.08)
    system = System(params)
    members, _ = _scenario_members(system, body_group, 2, capacity=2)

    runner = EnsembleRunner(system)
    step = trace_counting_jit(runner.step_impl)
    tracer = obs_tracer.Tracer(None)
    records = []
    with obs_tracer.use(tracer):
        se = ScenarioEnsemble(system, members, batch=2, runner=runner,
                              step_fn=step, metrics=records.append)
        finished = se.run(max_rounds=60)
    assert sorted(finished) == ["m0", "m1"]
    assert se.reseats >= 1, "sweep never outgrew its 2-slot rung"
    rungs = sorted(se._scheds)
    # one trace per capacity rung, ever — reseats and later steps reuse them
    assert step.trace_count == len(rungs), (step.trace_count, rungs)
    growth_events = [e for e in tracer.events
                     if e.get("ev") == "lane" and e.get("action") == "growth"]
    assert growth_events, "no growth events surfaced in telemetry"
    # the fiber population grew in-trace (mask flips, not reshapes):
    # members seeded 2 live fibers, the recorded steps carry more
    steps = [r for r in records if r.get("event") == "step"]
    assert sum(r["nucleations"] for r in steps) >= 1
    assert max(r["active_fibers"] for r in steps) > 2


@pytest.mark.slow  # one vmap coupled compile (~40 s on CPU)
def test_di_failed_lane_quarantine(body_group):
    """Guard semantics under DI: a poisoned lane retires ``failed`` with a
    nonfinite verdict while its sibling finishes its whole trajectory."""
    from skellysim_tpu.guard import chaos, verdict

    params = di_params()
    system = System(params)
    members, _ = _scenario_members(system, body_group, 2)
    runner = EnsembleRunner(system)
    records = []
    sched = EnsembleScheduler(runner, members, 2, metrics=records.append,
                              on_failure="retire", on_growth="retire")
    sched.ens = chaos.poison_lane(sched.ens, sched.lane_of("m0"))
    retired = sched.run()
    fails = [r for r in records if r.get("event") == "failed"]
    assert [f["member"] for f in fails] == ["m0"]
    assert fails[0]["health"] & verdict.NONFINITE
    assert "m1" in retired
    m1_steps = [r for r in records
                if r.get("event") == "step" and r["member"] == "m1"]
    assert m1_steps and m1_steps[-1]["t"] + m1_steps[-1]["dt"] \
        >= params.t_final - 1e-12


def test_scheduler_requires_member_rng(body_group):
    params = di_params()
    system = System(params)
    members, _ = _scenario_members(system, body_group, 1)
    runner = EnsembleRunner(system)
    spec = dataclasses.replace(members[0], rng=None)
    with pytest.raises(ValueError, match="SimRNG"):
        EnsembleScheduler(runner, [spec], 1)
    with pytest.raises(ValueError, match="SimRNG"):
        ScenarioEnsemble(system, [spec], 1, runner=runner)


def test_summarize_renders_scenario_table():
    """`obs summarize` renders the dynamic-instability table from ensemble
    step records carrying the new fields."""
    import json

    from skellysim_tpu.obs.summarize import Summary

    s = Summary()
    base = {"event": "step", "lane": 0, "round": 0, "step": 0, "t": 0.0,
            "dt": 0.02, "iters": 3, "gmres_cycles": 1, "residual": 1e-11,
            "residual_true": 1e-11, "fiber_error": 0.0, "accepted": True,
            "refines": 0, "loss_of_accuracy": False, "health": 0,
            "guard_retries": 0, "wall_s": 0.1,
            "gmres_history": []}
    for step, (n, c, a) in enumerate([(2, 0, 4), (1, 1, 4), (0, 2, 2)]):
        s.add_line(json.dumps(dict(base, member="m0", step=step, round=step,
                                   nucleations=n, catastrophes=c,
                                   active_fibers=a)))
    s.add_line(json.dumps({"ev": "lane", "action": "growth", "lane": 0,
                           "member": "m0", "capacity": 4}))
    out = s.render()
    assert "dynamic instability" in out
    assert "nucleations=3" in out and "catastrophes=3" in out
    assert "growth-reseats=1" in out
    assert "4 -> 2, max 4" in out


def test_summarize_omits_scenario_table_without_di():
    import json

    from skellysim_tpu.obs.summarize import Summary

    s = Summary()
    s.add_line(json.dumps({"step": 0, "t": 0.0, "dt": 0.01, "iters": 4,
                           "accepted": True, "nucleations": 0,
                           "catastrophes": 0, "active_fibers": 0}))
    assert "dynamic instability" not in s.render()
