"""skelly-flight: the device-side physics flight recorder + anomaly
provenance (obs/flight.py, docs/observability.md "Flight recorder").

Pins the ISSUE-15 acceptance surface:

* `Params.flight_window = 0` (the default) is the PRE-FLIGHT program:
  `SimState.flight` is absent and the armed twin's physics is bitwise
  identical to the disabled one (the recorder must observe, never
  perturb);
* ring wrap chronology under the ensemble vmap path (the gmres-history
  wrap test's mirror), including per-member counts through the scheduler;
* anomaly provenance names the poisoned field/fiber/node, on the
  single-chip step, the ensemble failure records, and the fault events;
* the SPMD ring analyzes replication-clean (`audit.repflow`) and matches
  the single-chip row;
* host tooling: torn-trailing-line tolerance, the summarize physics
  table, the `obs flight` blast-radius report, timeline counter tracks.
"""

from __future__ import annotations

import json

import jax.numpy as jnp
import numpy as np
import pytest

from skellysim_tpu.audit import fixtures
from skellysim_tpu.obs import flight as flight_mod


@pytest.fixture(scope="module")
def armed_system():
    """One armed (K=4) system + its compiled step, shared by the
    single-chip tests (the fixture step compiles once per module)."""
    system = fixtures.make_system(flight_window=4)
    return system


def _poisoned(state, field, fiber, node):
    x = np.asarray(state.fibers.x).copy()
    t = np.asarray(state.fibers.tension).copy()
    if field == "fiber_x":
        x[fiber, node, 1] = np.nan
    elif field == "fiber_tension":
        t[fiber, node] = np.inf
    return state._replace(fibers=state.fibers._replace(
        x=jnp.asarray(x), tension=jnp.asarray(t)))


# ------------------------------------------------------------ host decode

def test_ring_rows_wrap_chronology_host():
    """Wrap decode mirrors `history_rows`: count > K keeps the LAST K
    rows, rotated oldest-first; ids decode to ints, NaN floats to None."""
    K, D = 4, len(flight_mod.FLIGHT_FIELDS)
    rows = np.full((K, D), np.nan, dtype=np.float32)
    for c in range(6):  # rows written at t = c
        rows[c % K] = np.arange(D, dtype=np.float32) * 0 + c
    decoded = flight_mod.ring_rows(rows, 6)
    assert [r["t"] for r in decoded] == [2.0, 3.0, 4.0, 5.0]
    assert decoded[-1]["strain_fiber"] == 5          # id column -> int
    assert flight_mod.ring_rows(rows, 0) == []
    assert flight_mod.ring_rows(None, 3) == []
    # NaN floats decode to None; provenance decodes from the id columns
    one = np.full(D, np.nan, dtype=np.float32)
    one[flight_mod.FLIGHT_FIELDS.index("prov_field")] = 1
    one[flight_mod.FLIGHT_FIELDS.index("prov_fiber")] = 2
    one[flight_mod.FLIGHT_FIELDS.index("prov_node")] = 3
    d = flight_mod.decode_row(one)
    assert d["max_strain"] is None
    assert d["provenance"] == {"field": "fiber_x", "fiber": 2, "node": 3}
    # ±inf decodes to JSON-safe strings: the blow-up signal survives while
    # the JSONL streams stay RFC-8259 (no bare `Infinity` tokens)
    one[flight_mod.FLIGHT_FIELDS.index("max_strain")] = np.inf
    one[flight_mod.FLIGHT_FIELDS.index("min_clearance")] = -np.inf
    d = flight_mod.decode_row(one)
    assert d["max_strain"] == "inf" and d["min_clearance"] == "-inf"
    assert "Infinity" not in json.dumps(d)


def test_window_zero_state_is_preflight(armed_system):
    """flight_window=0 keeps SimState.flight ABSENT (None leaf ⇒ the
    pytree, and so the compiled program, is the pre-flight one) and
    ensure_flight arms/strips/re-arms across window changes."""
    off = fixtures.make_system()
    st = fixtures.free_state(off)
    assert st.flight is None
    armed = fixtures.free_state(armed_system)
    assert armed.flight is not None
    assert armed.flight.rows.shape == (4, len(flight_mod.FLIGHT_FIELDS))
    # ensure_flight normalization: strip, arm, re-arm on size mismatch
    assert off.ensure_flight(armed).flight is None
    re = armed_system.ensure_flight(st)
    assert re.flight is not None and int(re.flight.count) == 0
    bigger = fixtures.make_system(flight_window=8)
    assert bigger.ensure_flight(armed).flight.rows.shape[0] == 8


def test_armed_step_bitwise_physics_and_ring(armed_system):
    """The recorder observes, never perturbs: K=4 vs K=0 trajectories are
    BITWISE identical, while the ring records one chronological row per
    trial with the expected diagnostics."""
    off = fixtures.make_system()
    s_off = fixtures.free_state(off)
    s_on = fixtures.free_state(armed_system)
    for i in range(3):
        n_off, sol_off, i_off = off.step(s_off)
        n_on, sol_on, i_on = armed_system.step(s_on)
        assert np.array_equal(np.asarray(sol_off), np.asarray(sol_on))
        assert np.array_equal(np.asarray(n_off.fibers.x),
                              np.asarray(n_on.fibers.x))
        s_off = n_off._replace(time=n_off.time + n_off.dt)
        s_on = n_on._replace(time=n_on.time + n_on.dt)
    rows = flight_mod.ring_rows(s_on.flight.rows, s_on.flight.count)
    assert int(s_on.flight.count) == 3 and len(rows) == 3
    ts = [r["t"] for r in rows]
    assert ts == sorted(ts)
    last = rows[-1]
    assert last["health"] == 0 and last["provenance"] is None
    assert last["solution_norm"] > 0
    assert last["max_speed"] > 0
    assert last["min_clearance"] is None      # free-space scene: no wall
    assert 0 <= last["strain_fiber"] < 16
    assert last["dt_used"] == pytest.approx(float(s_on.dt), rel=1e-6)


def test_provenance_names_field_fiber_node(armed_system):
    """A NaN planted at fiber 2 / node 3 localizes as (fiber_x, 2, 3) —
    exact coordinates, not just 'a lane died'; with BOTH a position and a
    tension poisoned, the scan's priority order names fiber_x first. Same
    compiled program throughout (poison changes values, not shapes)."""
    base = fixtures.free_state(armed_system)
    for fiber, node in ((2, 3), (0, 7)):
        bad = _poisoned(base, "fiber_x", fiber, node)
        new_state, _, info = armed_system.step(bad)
        assert int(info.health) & 1            # NONFINITE
        row = flight_mod.last_row(np.asarray(new_state.flight.rows),
                                  new_state.flight.count)
        assert row["provenance"] == {"field": "fiber_x", "fiber": fiber,
                                     "node": node}, row
    both = _poisoned(_poisoned(base, "fiber_tension", 1, 5),
                     "fiber_x", 2, 3)
    new_state, _, info = armed_system.step(both)
    assert int(info.health) & 1
    row = flight_mod.last_row(np.asarray(new_state.flight.rows),
                              new_state.flight.count)
    assert row["provenance"] == {"field": "fiber_x", "fiber": 2, "node": 3}


@pytest.mark.slow
def test_provenance_shell_nodes_vs_benign_density():
    """On the coupled scene: poisoned shell GEOMETRY (the wall every flow
    evaluates against) fails the solve and localizes as shell_nodes with
    the node index, while a poisoned shell DENSITY alone is benign — the
    Krylov solve starts from zero and overwrites it, so health stays 0
    and the recorder must not cry wolf."""
    system = fixtures.make_system(shell=True, flight_window=4)
    state = fixtures.coupled_state(system)
    nodes = np.asarray(state.shell.nodes).copy()
    nodes[5, 2] = np.nan
    bad = state._replace(shell=state.shell._replace(
        nodes=jnp.asarray(nodes)))
    nb, _, ib = system.step(bad)
    assert int(ib.health) & 1
    row = flight_mod.last_row(np.asarray(nb.flight.rows), nb.flight.count)
    assert row["provenance"] == {"field": "shell_nodes", "fiber": -1,
                                 "node": 5}
    rho = np.asarray(state.shell.density).copy()
    rho[17] = np.inf
    benign = state._replace(shell=state.shell._replace(
        density=jnp.asarray(rho)))
    n2, _, i2 = system.step(benign)
    assert int(i2.health) == 0
    assert np.isfinite(np.asarray(n2.shell.density)).all()


# --------------------------------------------------------- ensemble front

def test_ensemble_vmap_ring_wrap_and_failure_payload():
    """The gmres-history wrap test's mirror on the ensemble path: K=3
    per-member rings ride the vmapped state, wrap chronologically, reject
    /quarantine keeps the fatal row, and the scheduler's failure record +
    fault event carry the tail + provenance while the sibling finishes."""
    from skellysim_tpu.ensemble.runner import EnsembleRunner
    from skellysim_tpu.ensemble.scheduler import (EnsembleScheduler,
                                                  MemberSpec)
    from skellysim_tpu.guard import chaos
    from skellysim_tpu.io.ensemble_io import ENSEMBLE_FAILURE_FIELDS
    from skellysim_tpu.obs import tracer as obs_tracer
    from skellysim_tpu.system import BackgroundFlow

    system = fixtures.make_system(flight_window=3)
    runner = EnsembleRunner(system)

    def member(seed):
        return system.make_state(
            fibers=fixtures.make_fibers(n_fibers=4, n_nodes=8, seed=seed),
            background=BackgroundFlow.make(uniform=(1.0, 0.0, 0.0),
                                           dtype=jnp.float64))

    records = []
    tracer = obs_tracer.Tracer()
    with obs_tracer.use(tracer):
        sched = EnsembleScheduler(
            runner, [MemberSpec("m0", member(1), 6e-3),
                     MemberSpec("m1", member(2), 6e-3)],
            2, metrics=records.append, on_failure="retire")
        sched.poll()
        sched.poll()
        # rings wrapped past K=3 need >3 rounds for m1; poison m0 now
        sched.ens = chaos.poison_lane(sched.ens, 0)
        sched.run()

    steps = [r for r in records if r.get("event") == "step"]
    assert steps and all("flight" in r for r in steps)
    healthy = [r["flight"] for r in steps if r["member"] == "m1"]
    assert all(f["health"] == 0 for f in healthy)
    # wrap chronology per member: m1 ran 6 rounds into a K=3 ring
    fl = sched.ens.states.flight
    lane1 = sched.retired.index("m1") >= 0  # m1 retired; read its record
    del lane1
    fails = [r for r in records if r.get("event") == "failed"]
    assert len(fails) == 1 and fails[0]["member"] == "m0"
    assert set(fails[0]) == set(ENSEMBLE_FAILURE_FIELDS)
    payload = fails[0]["flight"]
    assert payload["provenance"] == {"field": "fiber_x", "fiber": 0,
                                     "node": 0}
    assert payload["tail"] and payload["tail"][-1]["health"] & 1
    # the quarantined round's row SURVIVED the lane freeze (the fatal row
    # is the evidence — the runner merges rings on `running`, not accept)
    ts = [r["t"] for r in payload["tail"]]
    assert ts == sorted(ts)
    faults = [e for e in tracer.events if e.get("ev") == "fault"
              and e.get("kind") == "lane_failed"]
    assert faults and faults[0]["prov_field"] == "fiber_x"
    assert faults[0]["prov_fiber"] == 0
    # flight telemetry events rode the stream (timeline counter source)
    assert any(e.get("ev") == "flight" for e in tracer.events)
    assert fl is not None


# ------------------------------------------------------------- SPMD front

def test_spmd_armed_build_analyzes_replication_clean():
    """The armed mesh program writes a REPLICATED ring: every reduction
    is psum'd/pmax'd, the provenance tie-break is an index-min — the
    replication analyzer proves the build deadlock-free with zero
    findings (the ISSUE-15 'repflow analyzes the SPMD ring clean' pin)."""
    from skellysim_tpu.audit import repflow
    from skellysim_tpu.parallel import shard_state
    from skellysim_tpu.parallel.mesh import make_mesh
    from skellysim_tpu.parallel.spmd import build_spmd_step

    mesh = make_mesh(2)
    system = fixtures.make_system(flight_window=32)
    state = shard_state(fixtures.free_state(system), mesh)
    fn = build_spmd_step(system, mesh, state, donate=False)
    report = repflow.analyze(fn.trace(state).jaxpr)
    assert report.findings == []
    assert len(report.regions) == 1
    assert report.regions[0].replicated_outputs > 0


@pytest.mark.slow
def test_spmd_ring_matches_single_chip():
    """One d2 step's flight row agrees with the single-chip row: same
    argmax fiber id (globalized across shards), same extrema to
    f32-reduction roundoff — all shards having written the identical
    replicated ring."""
    from skellysim_tpu.parallel import shard_state
    from skellysim_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(2)
    system = fixtures.make_system(flight_window=4)
    state = shard_state(fixtures.free_state(system), mesh)
    new_state, _, _ = system.step_spmd(state, mesh, donate=False)
    row = flight_mod.last_row(np.asarray(new_state.flight.rows),
                              np.asarray(new_state.flight.count))

    s1 = fixtures.make_system(flight_window=4)
    n1, _, _ = s1.step(fixtures.free_state(s1))
    ref = flight_mod.last_row(np.asarray(n1.flight.rows), n1.flight.count)
    assert row["strain_fiber"] == ref["strain_fiber"]
    assert row["max_speed"] == pytest.approx(ref["max_speed"], rel=1e-5)
    assert row["solution_norm"] == pytest.approx(ref["solution_norm"],
                                                 rel=1e-4)
    assert row["health"] == ref["health"] == 0


# ----------------------------------------------------------- host tooling

def _metrics_line(member=None, flight=None, **over):
    rec = {"step": 0, "t": 0.1, "dt": 0.01, "iters": 3, "gmres_cycles": 1,
           "collective_rounds": 11, "gram_rows": 96, "residual": 1e-11,
           "residual_true": 1e-11, "fiber_error": 1e-9, "accepted": True,
           "refines": 0, "loss_of_accuracy": False, "health": 0,
           "guard_retries": 0, "nucleations": 0, "catastrophes": 0,
           "active_fibers": 0, "wall_s": 0.1,
           "gmres_history": [], "flight": flight}
    if member is not None:
        rec.update(event="step", member=member, lane=0, round=0)
    rec.update(over)
    return json.dumps(rec)


def _flight_dict(**over):
    d = {"t": 0.1, "dt_used": 0.01, "max_strain": 1e-9, "strain_fiber": 3,
         "max_speed": 0.5, "min_clearance": 0.8, "body_norm": 0.0,
         "solution_norm": 12.5, "residual_true": 1e-11, "health": 0,
         "prov_field": 0, "prov_fiber": -1, "prov_node": -1,
         "provenance": None}
    d.update(over)
    return d


def test_summarize_torn_tail_and_physics_table(tmp_path):
    """A kill-9-torn trailing line is tolerated (reported, never a crash
    or an 'unparseable' count), and flight rows render the physics table;
    a metrics flight column and its telemetry-event twin dedupe."""
    from skellysim_tpu.obs.summarize import summarize_files

    path = tmp_path / "metrics.jsonl"
    flight = _flight_dict(max_strain=2e-3, min_clearance=-0.25)
    lines = [_metrics_line(flight=flight),
             json.dumps(dict({"ev": "flight", "member": "run"}, **flight)),
             _metrics_line(flight=None, t=0.2)[:37]]  # torn mid-record
    path.write_text("\n".join(lines) + "\n")
    out = summarize_files([str(path)])
    assert "torn trailing line" in out
    assert "unparseable" not in out
    assert "physics diagnostics" in out
    # 1 step, not 2: the metrics column and the flight event are one trial
    line = next(ln for ln in out.splitlines() if ln.startswith("run "))
    assert line.split()[1] == "1"
    assert "-0.25" in line
    # mid-file garbage is still reported as unparseable
    path2 = tmp_path / "garbled.jsonl"
    path2.write_text("{nope}\n" + _metrics_line(flight=None) + "\n")
    out2 = summarize_files([str(path2)])
    assert "1 unparseable" in out2 and "torn" not in out2


def test_flight_report_blast_radius(tmp_path):
    """`obs flight` renders the fault trajectory + offender coordinates
    from an ensemble metrics stream, tolerating a torn tail; exit paths
    covered via the CLI entry."""
    from skellysim_tpu.obs.cli import main as obs_main

    path = tmp_path / "ens.jsonl"
    tail = [_flight_dict(t=0.1), _flight_dict(t=0.11),
            _flight_dict(t=0.12, health=1, max_strain="inf",
                         prov_field=1, prov_fiber=2, prov_node=7,
                         provenance={"field": "fiber_x", "fiber": 2,
                                     "node": 7})]
    lines = [_metrics_line(member="m0", flight=tail[0]),
             _metrics_line(member="m1", flight=_flight_dict()),
             json.dumps({"event": "failed", "member": "m0", "lane": 0,
                         "t": 0.12, "steps": 3, "frames": 0, "health": 1,
                         "verdict": "nonfinite",
                         "flight": {"tail": tail,
                                    "provenance": tail[-1]["provenance"]}}),
             # the SAME fault's telemetry event (a metrics+trace pair fed
             # together must count the fault once, not twice)
             json.dumps({"ev": "fault", "ts": 2.0, "kind": "lane_failed",
                         "member": "m0", "health": 1,
                         "verdict": "nonfinite", "prov_field": "fiber_x",
                         "prov_fiber": 2, "prov_node": 7}),
             '{"torn']
    path.write_text("\n".join(lines))
    report = flight_mod.render_flight_report([str(path)])
    assert "m0: FAULT (nonfinite)" in report
    assert "field=fiber_x fiber 2 node 7" in report
    assert "trajectory into the fault" in report
    assert "healthy members (1)" in report and "m1:" in report
    assert "fiber_x=1" in report          # fault-localization counters
    assert "torn trailing line" in report
    assert obs_main(["flight", str(path)]) == 0
    assert obs_main(["flight", str(tmp_path / "missing.jsonl")]) == 2
    # no flight data at all is a clean empty report, not an error
    empty = tmp_path / "empty.jsonl"
    empty.write_text(_metrics_line(flight=None) + "\n")
    assert "no flight-recorder records" in flight_mod.render_flight_report(
        [str(empty)])


def test_timeline_flight_counter_tracks(tmp_path):
    """`obs timeline` renders flight telemetry events as perfetto COUNTER
    tracks next to the span slices."""
    from skellysim_tpu.obs.timeline import write_timeline

    trace = tmp_path / "trace.jsonl"
    evs = [{"ev": "telemetry", "ts": 0.0, "version": 1},
           {"ev": "span", "ts": 1.0, "dur_s": 0.5, "name": "step",
            "path": "run/step"},
           dict({"ev": "flight", "ts": 1.0, "member": "m0"},
                **_flight_dict()),
           dict({"ev": "flight", "ts": 1.5, "member": "m0"},
                **_flight_dict(max_strain=2e-9))]
    trace.write_text("\n".join(json.dumps(e) for e in evs) + "\n")
    out = tmp_path / "tl.json"
    counts = write_timeline([str(trace)], str(out))
    assert counts["counters"] > 0
    doc = json.loads(out.read_text())
    counters = [e for e in doc["traceEvents"] if e.get("ph") == "C"]
    names = {e["name"] for e in counters}
    assert "flight:max_strain [m0]" in names
    assert all("value" in e["args"] for e in counters)


def test_serve_status_and_stats_surface_flight():
    """The serve front, in-process: a chaos-poisoned tenant's `status`
    answers the flight tail + provenance, `/stats` counts the offender
    field, and the bucket sibling finishes untouched."""
    from skellysim_tpu.config import BackgroundSource, Config, Fiber, schema
    from skellysim_tpu.config.toml_io import dumps as toml_dumps
    from skellysim_tpu.guard import chaos as chaos_mod
    from skellysim_tpu.serve.server import SimulationServer

    def scene(shift):
        cfg = Config()
        cfg.params.dt_initial = cfg.params.dt_write = 0.005
        cfg.params.t_final = 0.02
        cfg.params.gmres_tol = 1e-10
        cfg.params.adaptive_timestep_flag = False
        cfg.params.flight_window = 4
        fib = Fiber(n_nodes=8, length=1.0, bending_rigidity=0.01)
        fib.fill_node_positions(np.array([shift, 0.0, 0.0]),
                                np.array([0.0, 0.0, 1.0]))
        cfg.fibers = [fib]
        cfg.background = BackgroundSource(uniform=[1.0, 0.0, 0.0])
        return cfg

    serve_cfg = schema.ServeConfig(max_lanes=2, batch_impl="unroll")
    server = SimulationServer(scene(0.0), serve_cfg=serve_cfg)
    ta = server.handle_request(
        {"type": "submit", "config": toml_dumps(schema.unpack(scene(0.1))),
         "t_final": 0.05})["tenant"]
    tb = server.handle_request(
        {"type": "submit", "config": toml_dumps(schema.unpack(scene(0.3))),
         "t_final": 0.05})["tenant"]
    server.tick()
    sched = server.buckets[0].scheduler
    chaos_mod.nan_lane_of(sched, ta)
    for _ in range(30):
        if not server.any_live():
            break
        server.tick()
    sa = server.handle_request({"type": "status", "tenant": ta})
    sb = server.handle_request({"type": "status", "tenant": tb})
    assert sa["status"] == "failed"
    assert sa["flight"]["provenance"] == {"field": "fiber_x", "fiber": 0,
                                          "node": 0}
    assert sa["flight"]["tail"][-1]["health"] & 1
    assert sb["status"] == "finished" and sb["flight"] is None
    stats = server.handle_request({"type": "stats"})["stats"]
    assert stats["fault_fields"] == {"fiber_x": 1}
