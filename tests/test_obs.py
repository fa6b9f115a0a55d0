"""skelly-scope: span tracing, compile events, cost baselines, convergence
history (docs/observability.md).

Covers every leg of the telemetry subsystem: span nesting/attribution in
the tracer, compile events firing exactly once per compiled program
(cross-checked against `testing.trace_counting_jit`), the cost-baseline
drift gate's flag/pass/suppress/drift ladder (synthetic programs + the real
CLI on the cheapest registered program), and the GMRES convergence ring
buffer against the solver's own debug-print residuals. Multi-device
fixture compiles stay out of this module (the cost CLI test restricts to
``gmres_f32``) to protect the not-slow tier's 870 s budget.
"""

import json
import statistics

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from skellysim_tpu.obs import tracer as obs_tracer
from skellysim_tpu.obs.compile_log import observed_jit
from skellysim_tpu.obs.tracer import TELEMETRY_VERSION, Tracer


# ------------------------------------------------------------------ tracer

def test_span_nesting_and_attribution():
    tr = Tracer()  # in-memory
    with obs_tracer.use(tr):
        with obs_tracer.span("outer", kind="test"):
            with obs_tracer.span("inner") as sp:
                sp.note(iters=3)
            with obs_tracer.span("inner"):
                pass
    evs = tr.events
    assert evs[0]["ev"] == "telemetry"
    assert evs[0]["version"] == TELEMETRY_VERSION
    spans = [e for e in evs if e["ev"] == "span"]
    # children close before their parent; paths carry the open stack
    assert [s["path"] for s in spans] == ["outer/inner", "outer/inner",
                                         "outer"]
    assert spans[0]["iters"] == 3
    assert spans[2]["kind"] == "test"
    assert all(s["dur_s"] >= 0.0 and "pid" in s and "host" in s
               for s in spans)
    # the parent's duration covers its children
    assert spans[2]["dur_s"] >= spans[0]["dur_s"] + spans[1]["dur_s"]


def test_span_records_start_parent_and_step():
    """A span record says when it started (on the stream's own `ts`
    clock), which span it was opened in, and the step it belongs to —
    its own, or the nearest enclosing span's."""
    tr = Tracer()
    with obs_tracer.use(tr):
        with obs_tracer.span("run"):
            with obs_tracer.span("step", step=7):
                with obs_tracer.span("wait"):
                    pass
                with obs_tracer.span("write_frame"):
                    with obs_tracer.span("io", bytes=12):
                        pass
    spans = {s["path"]: s for s in tr.events if s["ev"] == "span"}
    assert set(spans) == {"run", "run/step", "run/step/wait",
                          "run/step/write_frame", "run/step/write_frame/io"}
    assert spans["run"]["parent"] is None and spans["run"]["step"] is None
    assert spans["run/step"]["parent"] == "run"
    assert spans["run/step/write_frame/io"]["parent"] == "run/step/write_frame"
    assert spans["run/step/write_frame/io"]["bytes"] == 12
    assert all(s["step"] == 7 for p, s in spans.items() if p != "run")
    for s in spans.values():
        # start + dur_s = the record's own ts, on one clock
        assert s["start"] + s["dur_s"] <= s["ts"] + 1e-5
    child, parent = spans["run/step/wait"], spans["run/step"]
    assert parent["start"] <= child["start"]
    assert (child["start"] + child["dur_s"]
            <= parent["start"] + parent["dur_s"] + 1e-5)


def test_span_does_not_block_at_exit():
    """A span observes when the program waits and never makes it wait:
    there is no device sync to register, and a span around an async
    dispatch closes before the result is ready."""
    tr = Tracer()
    with obs_tracer.use(tr):
        with obs_tracer.span("work") as sp:
            assert not hasattr(sp, "sync")
            y = jnp.ones((8, 8)) @ jnp.ones((8, 8))
    (span,) = [e for e in tr.events if e["ev"] == "span"]
    assert span["name"] == "work" and span["dur_s"] >= 0.0
    assert float(y[0, 0]) == 8.0


def test_span_and_emit_are_noops_without_tracer():
    assert obs_tracer.active() is None
    with obs_tracer.span("nobody-listening") as sp:
        sp.note(x=1)
    assert obs_tracer._STACK == []
    obs_tracer.emit("lane", action="admit")  # must not raise


def test_tracer_jsonl_roundtrip(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    tr = Tracer(path)
    with tr.span("a"):
        tr.emit("custom", value=7)
    tr.close()
    recs = [json.loads(ln) for ln in open(path)]
    assert [r["ev"] for r in recs] == ["telemetry", "custom", "span"]
    assert recs[1]["value"] == 7


# ----------------------------------------------------------- compile events

def test_compile_events_fire_exactly_once_per_program():
    """One compile event per (program x signature) — cross-checked against
    trace_counting_jit semantics via the shared trace counter."""
    from skellysim_tpu.testing import trace_counting_jit

    def f(x):
        return (x * 2.0).sum()

    obs = observed_jit(f, name="toy")
    ref = trace_counting_jit(f)
    tr = Tracer()
    with obs_tracer.use(tr):
        x = jnp.ones(8)
        obs(x), ref(x)
        obs(x + 1.0), ref(x + 1.0)      # same signature: no event
        obs(jnp.ones(16)), ref(jnp.ones(16))  # new shape: one more event
    compiles = [e for e in tr.events if e["ev"] == "compile"]
    assert len(compiles) == 2
    assert obs.trace_count == ref.trace_count == 2
    assert [c["name"] for c in compiles] == ["toy", "toy"]
    assert compiles[0]["arg_sig"].startswith("f64[8]")
    assert compiles[1]["arg_sig"].startswith("f64[16]")
    assert all(c["wall_s"] >= c["trace_s"] >= 0.0 for c in compiles)


def test_compile_event_skipped_when_warm():
    """A tracer installed AFTER the program compiled sees no event — only
    genuine compiles land in the timeline."""
    g = observed_jit(lambda x: x + 1.0, name="warm")
    g(jnp.ones(4))
    tr = Tracer()
    with obs_tracer.use(tr):
        g(jnp.ones(4))
    assert [e for e in tr.events if e["ev"] == "compile"] == []


def test_observed_jit_trace_passthrough_and_donation_field():
    """`built_from` consumes ObservedJit directly (the audit/cost seam) and
    the compile event carries the donated argument positions."""
    from skellysim_tpu.audit.registry import built_from

    h = observed_jit(lambda x: x * 3.0, name="donating", donate_argnums=(0,))
    built = built_from(h, jnp.ones(4))
    assert built.lowered is not None
    assert "stablehlo" in built.lowered_text or "func.func" in built.lowered_text
    tr = Tracer()
    with obs_tracer.use(tr):
        h(jnp.ones(8))
    (ev,) = [e for e in tr.events if e["ev"] == "compile"]
    assert ev["donated"] == [0]


# ------------------------------------------------------------ cost baselines

def _toy_program(name="toy_prog", scale=1.0):
    from skellysim_tpu.audit.registry import AuditProgram, built_from

    def build():
        a = jnp.ones((32, 32)) * scale
        return built_from(jax.jit(lambda x: (x @ x).sum()), a)

    return AuditProgram(name=name, layer="solver", summary="toy", build=build)


def test_cost_uncovered_then_update_then_pass(tmp_path):
    from skellysim_tpu.obs import cost

    prog = _toy_program()
    bdir = str(tmp_path)
    rows, findings = cost.audit_costs([prog], baseline_dir=bdir)
    assert any("no cost baseline" in f.message for f in findings)
    assert rows[0]["flops"] > 0 and rows[0]["peak_bytes"] > 0

    rows, findings = cost.audit_costs([prog], baseline_dir=bdir, update=True)
    assert findings == []
    rows, findings = cost.audit_costs([prog], baseline_dir=bdir)
    assert findings == []  # measured == baseline: deterministic static analysis


def test_cost_drift_flagged_and_suppressible(tmp_path):
    from skellysim_tpu.config import toml_io
    from skellysim_tpu.obs import cost

    prog = _toy_program()
    bdir = str(tmp_path)
    cost.audit_costs([prog], baseline_dir=bdir, update=True)
    path = cost.baseline_path(prog.name, bdir)
    data = toml_io.load(path)
    data["cost"]["flops"] = data["cost"]["flops"] * 2.0  # fake a regression
    toml_io.dump(data, path)
    _, findings = cost.audit_costs([prog], baseline_dir=bdir)
    assert any("flops drifted" in f.message and "improvement" in f.message
               for f in findings)

    # suppression with a reason absorbs it; an unused one is itself a finding
    data["suppress"] = [{"check": "cost-baseline", "match": "flops drifted",
                         "reason": "testing the suppress path"}]
    toml_io.dump(data, path)
    _, findings = cost.audit_costs([prog], baseline_dir=bdir)
    assert findings == []
    data["cost"]["flops"] = data["cost"]["flops"] / 2.0  # back to truth
    toml_io.dump(data, path)
    _, findings = cost.audit_costs([prog], baseline_dir=bdir)
    assert any("unused suppression" in f.message for f in findings)


def test_cost_suppress_requires_reason_and_match(tmp_path):
    from skellysim_tpu.config import toml_io
    from skellysim_tpu.obs import cost

    prog = _toy_program()
    bdir = str(tmp_path)
    cost.audit_costs([prog], baseline_dir=bdir, update=True)
    path = cost.baseline_path(prog.name, bdir)
    data = toml_io.load(path)
    data["suppress"] = [{"check": "cost-baseline", "match": "flops"}]
    toml_io.dump(data, path)
    _, findings = cost.audit_costs([prog], baseline_dir=bdir)
    assert any("missing its reason" in f.message for f in findings)


def test_cost_stale_baseline_and_tol_pct(tmp_path):
    from skellysim_tpu.config import toml_io
    from skellysim_tpu.obs import cost

    prog = _toy_program()
    bdir = str(tmp_path)
    cost.audit_costs([prog], baseline_dir=bdir, update=True)
    # a generous tol_pct absorbs a small nudge (and --update preserves it)
    path = cost.baseline_path(prog.name, bdir)
    data = toml_io.load(path)
    data["cost"]["tol_pct"] = 90.0
    data["cost"]["flops"] = data["cost"]["flops"] * 1.5
    toml_io.dump(data, path)
    _, findings = cost.audit_costs([prog], baseline_dir=bdir)
    assert findings == []
    cost.audit_costs([prog], baseline_dir=bdir, update=True)
    assert toml_io.load(path)["cost"]["tol_pct"] == 90.0
    # a baseline whose program vanished is a finding
    _, findings = cost.audit_costs([_toy_program(name="other")],
                                   baseline_dir=bdir)
    assert any("stale baseline" in f.message for f in findings)
    assert any("no cost baseline" in f.message for f in findings)


def test_cost_cli_exit_codes(tmp_path):
    """`obs cost --check` exits 1 on drift/uncovered, 0 once baselined —
    on the real registry restricted to its cheapest program (gmres_f32;
    the multi-device programs stay in the CI gate, not the test tier)."""
    from skellysim_tpu.obs.cli import main

    bdir = str(tmp_path)
    assert main(["cost", "--check", "--program", "gmres_f32",
                 "--baseline-dir", bdir]) == 1  # uncovered
    # findings exit 1 with or without --check (mirrors lint/audit)
    assert main(["cost", "--program", "gmres_f32",
                 "--baseline-dir", bdir]) == 1
    assert main(["cost", "--update", "--program", "gmres_f32",
                 "--baseline-dir", bdir]) == 0
    assert main(["cost", "--check", "--program", "gmres_f32",
                 "--baseline-dir", bdir]) == 0
    assert main(["cost", "--check", "--update"]) == 2  # usage error
    assert main(["cost", "--check", "--program", "nope",
                 "--baseline-dir", bdir]) == 2
    # against the REAL baseline dir, a single-program run must not read
    # the other programs' baselines as stale (the --program workflow)
    assert main(["cost", "--check", "--program", "gmres_f32"]) == 0


def test_cost_stale_scan_uses_full_registry_names(tmp_path):
    from skellysim_tpu.obs import cost

    a, b = _toy_program(name="prog_a"), _toy_program(name="prog_b")
    bdir = str(tmp_path)
    cost.audit_costs([a, b], baseline_dir=bdir, update=True)
    # auditing only prog_a with the full name set: prog_b's baseline is fine
    _, findings = cost.audit_costs([a], baseline_dir=bdir,
                                   registry_names={"prog_a", "prog_b"})
    assert findings == []
    # without the full set (a caller that filtered and forgot): stale
    _, findings = cost.audit_costs([a], baseline_dir=bdir)
    assert any("stale baseline" in f.message for f in findings)


def test_every_registered_program_has_a_checked_in_baseline():
    """Acceptance pin: the registry and obs/baselines/ agree exactly (the
    full drift check runs in CI; here only the cheap file<->name match)."""
    import os

    from skellysim_tpu.audit.programs import all_programs
    from skellysim_tpu.obs.cost import BASELINE_DIR

    names = {p.name for p in all_programs()}
    files = {os.path.splitext(f)[0] for f in os.listdir(BASELINE_DIR)
             if f.endswith(".toml")}
    assert names == files


# ------------------------------------------------- gmres convergence history

def _dense_problem(n=80, seed=3, dtype=jnp.float64):
    rng = np.random.default_rng(seed)
    A = jnp.asarray(np.eye(n) + 0.3 * rng.standard_normal((n, n)) / np.sqrt(n),
                    dtype=dtype)
    b = jnp.asarray(rng.standard_normal(n), dtype=dtype)
    return A, b


def test_gmres_history_matches_debug_print(capsys):
    """The device-side ring buffer records the SAME per-restart residuals
    the solver's debug path prints — without any host callback in the
    compiled program (the debug path adds one; history must not)."""
    from skellysim_tpu.solver.gmres import gmres, history_rows

    A, b = _dense_problem()
    r = gmres(lambda x: A @ x, b, tol=1e-12, restart=5, maxiter=200,
              history=16, debug=True)
    jax.effects_barrier()
    printed = []
    for ln in capsys.readouterr().out.splitlines():
        if "gmres restart" in ln:
            printed.append((int(ln.split("iters=")[1].split(" ")[0]),
                            float(ln.split("implicit=")[1].split(" ")[0]),
                            float(ln.split("explicit=")[1])))
    rows = history_rows(r.history, r.cycles)
    assert len(rows) == len(printed) == int(r.cycles) >= 3
    for (it_h, imp_h, exp_h), (it_p, imp_p, exp_p) in zip(rows, printed):
        assert it_h == it_p
        assert imp_h == pytest.approx(imp_p, rel=2e-3)  # print is %.3e
        assert exp_h == pytest.approx(exp_p, rel=2e-3)
    assert rows[-1][2] == float(r.residual_true)


def test_gmres_history_ring_wraps_chronologically():
    from skellysim_tpu.solver.gmres import gmres, history_rows

    A, b = _dense_problem()
    full = gmres(lambda x: A @ x, b, tol=1e-12, restart=5, maxiter=200,
                 history=32)
    wrapped = gmres(lambda x: A @ x, b, tol=1e-12, restart=5, maxiter=200,
                    history=3)
    all_rows = history_rows(full.history, full.cycles)
    last3 = history_rows(wrapped.history, wrapped.cycles)
    assert int(full.cycles) > 3  # the wrap actually happened
    assert len(last3) == 3
    assert last3 == all_rows[-3:]  # ring holds the LAST cycles, oldest first
    # disabled history costs nothing and changes nothing
    off = gmres(lambda x: A @ x, b, tol=1e-12, restart=5, maxiter=200)
    assert off.history is None
    np.testing.assert_array_equal(np.asarray(off.x), np.asarray(full.x))


def test_gmres_ir_history_one_row_per_sweep():
    from skellysim_tpu.solver.gmres import gmres_ir, history_rows

    A, b = _dense_problem()
    r = gmres_ir(lambda x: A @ x, lambda x: A @ x, b, tol=1e-12,
                 inner_tol=1e-4, restart=30, maxiter=200, history=8)
    rows = history_rows(r.history, r.cycles)
    assert len(rows) == int(r.refines) == int(r.cycles) >= 2
    assert rows[-1][2] == float(r.residual_true)
    exps = [row[2] for row in rows]
    assert exps == sorted(exps, reverse=True)  # sweeps contract the residual


def test_history_rows_handles_empty_and_none():
    from skellysim_tpu.solver.gmres import history_rows

    assert history_rows(None, 5) == []
    assert history_rows(np.zeros((4, 3)), 0) == []
    assert history_rows(np.zeros((0, 3)), 3) == []


def test_vmapped_gmres_history_is_per_member():
    """The ring buffer is an ordinary carry: vmap gives each member its own
    buffer (the ensemble runner's per-lane convergence history)."""
    from skellysim_tpu.solver.gmres import gmres, history_rows

    A, b = _dense_problem()
    bb = jnp.stack([b, 2.0 * b])
    vr = jax.vmap(lambda bi: gmres(lambda x: A @ x, bi, tol=1e-12,
                                   restart=5, maxiter=200, history=8))(bb)
    assert vr.history.shape[0] == 2
    r0 = history_rows(vr.history[0], vr.cycles[0])
    r1 = history_rows(vr.history[1], vr.cycles[1])
    # scaled RHS: same relative trajectory, per-member buffers decode alone
    assert len(r0) == len(r1) == int(vr.cycles[0])
    assert r0[-1][2] == pytest.approx(float(vr.residual_true[0]))


# ---------------------------------------------------- run-loop + ensemble

def test_run_metrics_and_trace_render_through_summarize(tmp_path):
    """Acceptance criterion: System.run(metrics_path, trace_path) -> `obs
    summarize` renders per-span timings, compile events, and convergence
    stats from the pair."""
    from skellysim_tpu.audit import fixtures
    from skellysim_tpu.obs.summarize import summarize_files
    from skellysim_tpu.system.system import METRICS_FIELDS

    system = fixtures.make_system()
    state = fixtures.free_state(system)
    m = str(tmp_path / "metrics.jsonl")
    t = str(tmp_path / "trace.jsonl")
    system.run(state, max_steps=2, metrics_path=m, trace_path=t)

    recs = [json.loads(ln) for ln in open(m)]
    assert len(recs) == 2
    for rec in recs:
        assert set(rec) == set(METRICS_FIELDS)
        assert rec["gmres_cycles"] >= 1
        # the step's record: `wall_s` (dispatch + wait) lies inside it
        assert rec["loop_s"] >= rec["wall_s"] - 1e-4
        hist = rec["gmres_history"]
        assert len(hist) == rec["gmres_cycles"]
        # last ring row's explicit residual is the step's residual_true
        assert hist[-1][2] == pytest.approx(rec["residual_true"])
        assert hist[-1][0] == rec["iters"]
        # the Gram passes walk the live rows of the basis, not all of it
        assert 0 < rec["gram_rows"] < 2 * rec["iters"] * (
            system.params.gmres_restart + 1)

    evs = [json.loads(ln) for ln in open(t)]
    kinds = [e["ev"] for e in evs]
    assert kinds[0] == "telemetry"
    assert "compile" in kinds and "span" in kinds
    (compile_ev,) = [e for e in evs if e["ev"] == "compile"]
    assert compile_ev["name"] == "system.solve"  # compiled exactly once
    step_spans = [e for e in evs if e["ev"] == "span"
                  and e["name"] == "step"]
    assert len(step_spans) == 2
    assert all(s["path"] == "run/step" for s in step_spans)

    report = summarize_files([m, t])
    for section in ("== spans ==", "== compile events ==",
                    "== solver convergence =="):
        assert section in report
    assert "run/step" in report and "system.solve" in report
    rows = [r["gram_rows"] / (2 * r["iters"]) for r in recs]
    assert (f"gram rows/pass: mean {sum(rows) / 2:.1f}  max {max(rows):.1f}"
            in report)


@pytest.mark.slow
def test_scheduler_lane_events_and_no_backfill_retrace(tmp_path):
    """Lane admit/backfill/retire events flow through the tracer, occupancy
    renders in summarize, and the telemetry does not break the
    backfill-never-retraces invariant (trace_counting_jit cross-check).

    Slow-marked (a 4-member batched-step compile) to keep the not-slow
    tier inside the driver's 870 s budget; the full tier runs it."""
    from skellysim_tpu.audit import fixtures
    from skellysim_tpu.ensemble import (EnsembleRunner, EnsembleScheduler,
                                        MemberSpec)
    from skellysim_tpu.io.ensemble_io import ENSEMBLE_STEP_FIELDS
    from skellysim_tpu.obs.summarize import summarize_files
    from skellysim_tpu.system import BackgroundFlow
    from skellysim_tpu.testing import trace_counting_jit

    system = fixtures.make_system()
    states = [system.make_state(
        fibers=fixtures.make_fibers(n_fibers=2, n_nodes=8, seed=i),
        background=BackgroundFlow.make(uniform=(1.0, 0.0, 0.0),
                                       dtype=jnp.float64))
        for i in range(4)]
    members = [MemberSpec(member_id=f"m{i}", state=s, t_final=2e-3)
               for i, s in enumerate(states)]
    runner = EnsembleRunner(system)
    counting = trace_counting_jit(runner.step_impl)

    metrics_records = []
    t = str(tmp_path / "trace.jsonl")
    tr = Tracer(t)
    with obs_tracer.use(tr):
        sched = EnsembleScheduler(runner, members, 2,
                                  metrics=metrics_records.append,
                                  step_fn=counting)
        retired = sched.run()
    tr.close()
    assert sorted(retired) == ["m0", "m1", "m2", "m3"]
    # lane events: 2 admits (initial seats), 2 backfills, 4 retires — and
    # backfill swapped member leaves without a retrace
    evs = [json.loads(ln) for ln in open(t)]
    lanes = [e for e in evs if e["ev"] == "lane"]
    actions = [e["action"] for e in lanes]
    assert actions.count("admit") == 2
    assert actions.count("backfill") == 2
    assert actions.count("retire") == 4
    assert counting.trace_count == 1
    steps = [r for r in metrics_records if r["event"] == "step"]
    assert steps and all(set(r) == set(ENSEMBLE_STEP_FIELDS) for r in steps)
    assert all(len(r["gmres_history"]) == r["gmres_cycles"] for r in steps)

    report = summarize_files([t])
    assert "== ensemble lanes ==" in report
    assert "mean occupancy" in report
    assert "admit=2" in report and "backfill=2" in report


def test_summarize_tolerates_mixed_and_garbage_lines(tmp_path):
    from skellysim_tpu.obs.summarize import summarize_files

    p = str(tmp_path / "mixed.jsonl")
    with open(p, "w") as fh:
        fh.write("not json at all\n")
        fh.write(json.dumps({"resume": True, "t": 0.5}) + "\n")
        fh.write(json.dumps({"ev": "span", "name": "a", "path": "a",
                             "dur_s": 0.5}) + "\n")
        fh.write(json.dumps({"step": 0, "iters": 4, "accepted": True,
                             "residual_true": 1e-11}) + "\n")
    report = summarize_files([p])
    assert "== spans ==" in report
    assert "trial steps: 1" in report
    assert "resume markers: 1" in report
    assert "1 unparseable line(s) skipped" in report


def test_summarize_dedupes_shared_round_wall(tmp_path):
    """Ensemble step records share one batched round's wall across lanes;
    the wall total must count each round once, not lanes x wall."""
    from skellysim_tpu.obs.summarize import summarize_files

    p = str(tmp_path / "ens.jsonl")
    with open(p, "w") as fh:
        for rnd in range(2):
            for lane in range(4):
                fh.write(json.dumps({
                    "event": "step", "member": f"m{lane}", "lane": lane,
                    "round": rnd, "step": rnd, "iters": 3, "accepted": True,
                    "wall_s": 0.010}) + "\n")
    report = summarize_files([p])
    # 2 rounds x 10 ms = 0.020 s — NOT 8 records x 10 ms = 0.080 s
    assert "batched-round wall: total 0.020s" in report
    # two runs' files summarized together: per-run round ids both start at
    # 0, so the dedupe must key per stream — totals ADD across files
    import shutil

    p2 = str(tmp_path / "ens2.jsonl")
    shutil.copy(p, p2)
    assert "batched-round wall: total 0.040s" in summarize_files([p, p2])


# ----------------------------------------------- skelly-pulse: profile dumps

import os

PROFILE_FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "golden", "profile_fixture")


def test_profile_fixture_phase_attribution():
    """Phase-table parsing on the checked-in miniature trace-event fixture
    (a 2-virtual-device shard_map program using the real phase vocabulary;
    no TPU, no profiling at test time)."""
    from skellysim_tpu.obs import profile as profile_mod

    trace = profile_mod.load_device_trace(PROFILE_FIXTURE)
    assert trace.total_us > 0
    phases = {g["key"]: g for g in trace.by_phase()}
    for key in ("prep", "gmres/arnoldi", "gmres/psum-dots", "advance"):
        assert key in phases, sorted(phases)
    # the fixture's psum lands as an all_reduce, split out by kind under
    # the audit contract's spelling
    assert "all_reduce" in phases["gmres/psum-dots"]["collectives"]
    kinds = {g["key"] for g in trace.by_collective()}
    assert "all_reduce" in kinds and "(computation)" in kinds
    # >= 90% attributed, unattributed reported (not hidden)
    assert trace.attributed_frac >= 0.9
    assert "(unattributed)" in phases or trace.attributed_frac == 1.0
    # shares are a partition of the total
    assert sum(g["share"] for g in trace.by_phase()) == pytest.approx(1.0)


def test_profile_render_and_json():
    from skellysim_tpu.obs import profile as profile_mod

    trace = profile_mod.load_device_trace(PROFILE_FIXTURE)
    table = profile_mod.render_table(trace, by="phase")
    assert "attributed to named phases" in table
    assert "gmres/psum-dots" in table
    doc = profile_mod.profile_json(trace)
    assert doc["total_us"] > 0
    assert {"by_phase", "by_collective", "by_op"} <= set(doc)


def test_profile_cli(tmp_path, capsys):
    from skellysim_tpu.obs.cli import main

    assert main(["profile", PROFILE_FIXTURE]) == 0
    assert "prep" in capsys.readouterr().out
    assert main(["profile", PROFILE_FIXTURE, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["attributed_frac"] >= 0.9
    assert main(["profile", PROFILE_FIXTURE, "--by", "collective"]) == 0
    capsys.readouterr()
    assert main(["profile", str(tmp_path / "nope")]) == 2


def test_phase_of_and_collective_kind():
    from skellysim_tpu.obs.profile import collective_kind, phase_of

    assert phase_of("jit(step)/jit(main)/prep/dot_general") == "prep"
    assert phase_of("jit(step)/gmres/jit(gmres)/arnoldi/precond/mul") \
        == "gmres/arnoldi/precond"
    # immediate repeats dedupe (scopes re-entered per ring hop)
    assert phase_of("a/ring-step/ring-step/b") == "ring-step"
    assert phase_of("jit(f)/jit(main)/transpose/mul") is None
    assert collective_kind("all-reduce.17") == "all_reduce"
    assert collective_kind("all-gather") == "all_gather"
    assert collective_kind("collective-permute.3") == "collective_permute"
    # the TPU lowering's async pairs + fused thunks classify too
    assert collective_kind("all-reduce-start.5") == "all_reduce"
    assert collective_kind("all-gather-done.2") == "all_gather"
    assert collective_kind("all-reduce-fusion") == "all_reduce"
    assert collective_kind("dot.3") is None
    assert collective_kind("reduce-scatter-start") == "reduce_scatter"


def test_device_phase_events_and_emit(tmp_path):
    """`device_phase` telemetry records from a dump, emitted into a tracer
    (the --profile auto-append workflow) and rendered by summarize."""
    from skellysim_tpu.obs import profile as profile_mod
    from skellysim_tpu.obs.summarize import summarize_files

    recs = profile_mod.device_phase_events(PROFILE_FIXTURE)
    assert any(r["phase"] == "gmres/psum-dots" for r in recs)
    assert all(r["dur_s"] >= 0.0 and "share" in r for r in recs)

    tr = Tracer(str(tmp_path / "t.jsonl"))
    n = profile_mod.emit_device_phases(PROFILE_FIXTURE, tr)
    tr.close()
    assert n == len(recs) > 0
    report = summarize_files([str(tmp_path / "t.jsonl")])
    assert "== device time by phase ==" in report
    assert "gmres/psum-dots" in report
    # a broken dump emits a device_phase_error event, never raises
    tr2 = Tracer()
    assert profile_mod.emit_device_phases(str(tmp_path), tr2) == 0
    assert [e["ev"] for e in tr2.events[1:]] == ["device_phase_error"]


@pytest.mark.slow
def test_d2_spmd_profile_attribution(tmp_path):
    """Acceptance pin (ISSUE 14): `obs profile` on a CPU-run profile dir
    of the d2 SPMD coupled solve attributes >= 90% of device op time to a
    named phase, with collective kinds split out matching the audit
    contract inventory. Slow-marked: one d2 mesh compile."""
    import numpy as np

    from skellysim_tpu.audit import fixtures
    from skellysim_tpu.obs import profile as profile_mod
    from skellysim_tpu.parallel.mesh import make_mesh

    system = fixtures.make_system(shell=True)
    state = fixtures.coupled_state(system)
    mesh = make_mesh(2)
    _, sol, _ = system.step_spmd(state, mesh, donate=False)
    np.asarray(sol)   # compile + drain outside the capture window
    prof_dir = str(tmp_path / "prof_d2")
    with profile_mod.profile_session(prof_dir):
        _, sol, _ = system.step_spmd(state, mesh, donate=False)
        np.asarray(sol)
    trace = profile_mod.load_device_trace(prof_dir)
    assert trace.attributed_frac >= 0.9, profile_mod.render_table(trace)
    kinds = {g["key"] for g in trace.by_collective()}
    # the audit contract inventory of the SPMD step: psum'd dots/flows,
    # the density all-gather, the ppermute source rings
    assert {"all_reduce", "all_gather", "collective_permute"} <= kinds
    phases = {g["key"] for g in trace.by_phase()}
    assert {"prep", "gmres/arnoldi", "advance"} <= phases


# ------------------------------------------------ skelly-pulse: timeline

def test_timeline_roundtrip(tmp_path):
    """Emit spans -> perfetto JSON -> re-parse -> the same span tree
    (names + nesting by slice containment), with compile instants and
    process/thread metadata."""
    from skellysim_tpu.obs.timeline import HOST_PID, write_timeline

    path = str(tmp_path / "trace.jsonl")
    tr = Tracer(path)
    with obs_tracer.use(tr):
        with obs_tracer.span("run"):
            with obs_tracer.span("step", step=0):
                with obs_tracer.span("write_frame"):
                    pass
            with obs_tracer.span("step", step=1):
                pass
        tr.emit("compile", name="system.solve", wall_s=1.0, trace_s=0.5,
                traces=1)
        tr.emit("lane", action="admit", lane=0, member="m0")
    tr.close()

    out = str(tmp_path / "tl.json")
    counts = write_timeline([path], out)
    assert counts["host_slices"] == 4
    assert counts["instants"] == 2  # compile + lane

    doc = json.load(open(out))
    evs = doc["traceEvents"]
    procs = [e for e in evs if e.get("ph") == "M"
             and e.get("name") == "process_name"]
    assert any(e["args"]["name"] == "host telemetry" for e in procs)
    slices = sorted((e for e in evs if e.get("ph") == "X"
                     and e["pid"] == HOST_PID), key=lambda e: e["ts"])
    assert [s["name"] for s in slices] == ["run", "step", "write_frame",
                                           "step"]

    def contains(a, b):   # slice a covers slice b (small float slack)
        return (a["ts"] <= b["ts"] + 1e-6
                and a["ts"] + a["dur"] >= b["ts"] + b["dur"] - 1e-6)

    run, s0, wf, s1 = slices
    assert contains(run, s0) and contains(run, s1) and contains(s0, wf)
    assert not contains(s0, s1) and not contains(s1, s0)
    assert s1["args"]["step"] == 1
    (compile_i,) = [e for e in evs if e.get("ph") == "i"
                    and e["name"].startswith("compile ")]
    assert compile_i["args"]["wall_s"] == 1.0
    assert any(e.get("ph") == "i" and e["name"] == "lane:admit"
               for e in evs)


def test_timeline_with_device_track(tmp_path):
    from skellysim_tpu.obs.timeline import DEVICE_PID, write_timeline

    path = str(tmp_path / "trace.jsonl")
    tr = Tracer(path)
    with tr.span("step"):
        pass
    tr.close()
    out = str(tmp_path / "tl.json")
    counts = write_timeline([path], out, profile_dir=PROFILE_FIXTURE)
    assert counts["device_slices"] > 0
    doc = json.load(open(out))
    evs = doc["traceEvents"]
    dev_threads = {e["args"]["name"] for e in evs
                   if e.get("ph") == "M" and e.get("name") == "thread_name"
                   and e.get("pid") == DEVICE_PID}
    # multi-device-thread profiles suffix "[dev k]" per source thread
    # (per-tid slices must nest — overlapping same-phase slices from two
    # devices on one tid would render wrong in Perfetto)
    assert any(n == "gmres/psum-dots" or n.startswith("gmres/psum-dots [")
               for n in dev_threads), dev_threads
    # host and device tracks are separate processes
    procs = {e["args"]["name"] for e in evs
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    assert {"host telemetry", "device (profiler)"} <= procs


def test_timeline_cli(tmp_path, capsys):
    from skellysim_tpu.obs.cli import main

    path = str(tmp_path / "trace.jsonl")
    tr = Tracer(path)
    with tr.span("a"):
        pass
    tr.close()
    out = str(tmp_path / "out.json")
    assert main(["timeline", path, "-o", out]) == 0
    assert json.load(open(out))["traceEvents"]
    capsys.readouterr()
    assert main(["timeline", str(tmp_path / "nope.jsonl"),
                 "-o", out]) == 2


# ----------------------------------------------- skelly-pulse: histograms

def test_log_histogram_percentiles_vs_numpy():
    """Percentile math against a numpy oracle on synthetic lognormal
    latencies: the geometric-interpolation estimate must sit within one
    bucket ratio of the true quantile."""
    from skellysim_tpu.obs.hist import LogHistogram

    rng = np.random.default_rng(42)
    vals = np.exp(rng.normal(np.log(0.05), 1.2, size=50000))
    h = LogHistogram(lo=1e-4, hi=1e3, per_decade=8)
    for v in vals:
        h.observe(v)
    ratio = 10.0 ** (1.0 / 8)   # one bucket edge step
    for q in (50.0, 90.0, 95.0, 99.0):
        est = h.percentile(q)
        true = float(np.percentile(vals, q))
        assert true / ratio <= est <= true * ratio, (q, est, true)
    s = h.summary()
    assert s["n"] == len(vals)
    assert s["mean"] == pytest.approx(float(vals.mean()))
    assert s["max"] == pytest.approx(float(vals.max()))
    assert s["p50"] <= s["p95"] <= s["p99"]


def test_log_histogram_edges_and_wire():
    from skellysim_tpu.obs.hist import (LogHistogram,
                                        render_prometheus_histogram)

    h = LogHistogram(lo=1e-3, hi=10.0, per_decade=4)
    assert h.summary() == {"n": 0, "mean": 0.0, "max": 0.0, "p50": 0.0,
                           "p95": 0.0, "p99": 0.0}
    for v in (0.0, 1e-5, 0.02, 0.02, 5.0, 1e9, float("nan")):
        h.observe(v)
    assert h.n == 7
    wire = h.to_wire()
    # cumulative buckets are monotone and terminate at +Inf == n
    counts = [c for _, c in wire["buckets"]]
    assert counts == sorted(counts)
    assert wire["buckets"][-1] == ["+Inf", 7] or \
        wire["buckets"][-1] == ("+Inf", 7)
    lines = render_prometheus_histogram("x_seconds", wire, help_text="t")
    assert lines[0] == "# HELP x_seconds t"
    assert lines[1] == "# TYPE x_seconds histogram"
    assert lines[-2] .startswith("x_seconds_sum ")
    assert lines[-1] == "x_seconds_count 7"
    assert any('le="+Inf"} 7' in ln for ln in lines)
    with pytest.raises(ValueError):
        LogHistogram(lo=1.0, hi=0.5)


# ------------------------------- skelly-pulse: provenance + summarize extras

def test_tracer_header_carries_provenance():
    """The telemetry header self-describes runtime + hardware (jax is
    imported in this process, so real values, not placeholders)."""
    from skellysim_tpu.obs.tracer import provenance

    tr = Tracer()
    header = tr.events[0]
    assert header["ev"] == "telemetry"
    assert header["jax_version"] == jax.__version__
    assert header["device_kind"]  # "cpu" on the test platform
    assert provenance()["device_kind"] == header["device_kind"]


def test_summarize_multifile_source_columns(tmp_path):
    """Several --trace-files summarize with per-file provenance on the
    span and lane-occupancy tables; a single file keeps the old layout."""
    from skellysim_tpu.obs.summarize import summarize_files

    def write(name, rounds):
        p = str(tmp_path / name)
        tr = Tracer(p)
        for i in range(rounds):
            with tr.span("ensemble_step", round=i, live=2, lanes=4):
                pass
        tr.close()
        return p

    a = write("serve_a.jsonl", 2)
    b = write("serve_b.jsonl", 3)
    single = summarize_files([a])
    assert "source" not in single.split("== spans ==")[1].splitlines()[1]
    assert "rounds: 2  lanes: 4" in single

    multi = summarize_files([a, b])
    span_header = multi.split("== spans ==")[1].splitlines()[1]
    assert span_header.startswith("source")
    assert "serve_a.jsonl" in multi and "serve_b.jsonl" in multi
    assert "[serve_a.jsonl] rounds: 2" in multi
    assert "[serve_b.jsonl] rounds: 3" in multi


# ------------------------------------------------------- the step record

def test_collecting_span_gathers_descendants_without_a_tracer():
    """A span told to `collect` is handed every span that closes under it
    (path below it, seconds, leaf or not) with NO tracer active; when
    nothing collects, nothing is handed anywhere."""
    assert obs_tracer.active() is None
    got = []
    with obs_tracer.span("run") as run:
        run.collect(lambda path, dur, leaf: got.append((path, dur, leaf)))
        with obs_tracer.span("clock_read"):
            pass
        with obs_tracer.span("step", step=0):
            with obs_tracer.span("write_frame"):
                with obs_tracer.span("io"):
                    pass
    # after the collector closed: nobody is told
    with obs_tracer.span("step"):
        with obs_tracer.span("wait"):
            pass
    assert [(p, leaf) for p, _, leaf in got] == [
        ("clock_read", True), ("step/write_frame/io", True),
        ("step/write_frame", False), ("step", False)]
    assert all(dur >= 0.0 for _, dur, _ in got)
    assert obs_tracer._COLLECTORS == [] and obs_tracer._STACK == []


def test_step_records_tile_the_run_and_fill_one_ring(tmp_path):
    """Every row of a run carries its step record; the records tile the
    ``run`` span (their ``loop_s`` sum to its duration), and re-entries of
    `run(max_steps=1)` on one `System` fill ONE ring."""
    from skellysim_tpu.audit import fixtures
    from skellysim_tpu.system.system import METRICS_FIELDS

    system = fixtures.make_system()
    state = fixtures.free_state(system)
    m = str(tmp_path / "metrics.jsonl")
    tr = Tracer()
    with obs_tracer.use(tr):    # on, to read the ``run`` span
        state = system.run(state, max_steps=3, metrics_path=m)
    rows = [json.loads(ln) for ln in open(m)]
    assert len(rows) == 3
    for row in rows:
        assert set(row) == set(METRICS_FIELDS)
        assert {"dispatch", "wait", "fetch_info", "clock_read",
                "other"} <= set(row["host_ms"])
        # spans that did not run are absent, not zero
        assert "collision_gate" not in row["host_ms"]
        assert "write_frame/io" not in row["host_ms"]
        assert sum(row["host_ms"].values()) == pytest.approx(
            row["loop_s"] * 1e3, abs=0.05)
        assert row["slow"] is None
    # the row is written after its record closed: its time is the next's
    assert "metrics_row" not in rows[0]["host_ms"]
    assert "metrics_row" in rows[1]["host_ms"]
    (run_span,) = [e for e in tr.events if e["ev"] == "span"
                   and e["path"] == "run"]
    assert sum(r["loop_s"] for r in rows) == pytest.approx(
        run_span["dur_s"], rel=0.02)

    # no tracer, no metrics file: the ring fills all the same, one ring
    assert len(system.step_records.ring) == 3
    for _ in range(3):
        state = system.run(state, max_steps=1)
    ring = list(system.step_records.ring)
    assert [r["n"] for r in ring] == list(range(6))
    assert [r["step"] for r in ring] == [0, 1, 2, 0, 0, 0]
    assert all(b["start"] > a["start"] for a, b in zip(ring, ring[1:]))
    # the third row's write followed the first call's last record: carried
    # into the first record of the next call
    assert "metrics_row" in ring[3]["host_ms"]
    assert "metrics_row" not in ring[4]["host_ms"]
    from skellysim_tpu.obs.step_record import COUNTERS

    assert all(set(r["counters"]) == set(COUNTERS) for r in ring)


class _TickingClock:
    """`time` as the tracer and the step record see it in the planted-stall
    test: every `perf_counter()` call is one millisecond later than the
    last, so each step of the test costs the same whatever else the machine
    does (under six xdist workers a CPU step's real time swings by 4x, and
    a rule that compares steps would flag the noise), and a stall is
    planted by moving the clock."""

    def __init__(self):
        self.now = 1000.0

    def perf_counter(self):
        self.now += 1e-3
        return self.now


class _StallingFile:
    """A trajectory's file whose ``write`` stalls ``stall_s`` on the clock
    above, on its ``on_call``-th call."""

    def __init__(self, fh, clock, on_call, stall_s):
        self.fh, self.clock = fh, clock
        self.on_call, self.stall_s = on_call, stall_s
        self.calls = 0

    def write(self, data):
        self.calls += 1
        if self.calls == self.on_call:
            self.clock.now += self.stall_s
        return self.fh.write(data)

    def flush(self):
        self.fh.flush()

    def close(self):
        self.fh.close()


def test_planted_stall_is_one_slow_step_named_three_ways(tmp_path, caplog,
                                                         monkeypatch):
    """A frame write that stalls 3 s on the ninth of ten steps gives
    exactly one row with ``slow`` set, naming the span that stalled, ONE
    ``fault`` event ``slow_step`` and ONE WARNING line; the eight steps
    before it (fewer than eight records never flag) and the one after give
    none."""
    import logging

    from skellysim_tpu.audit import fixtures
    from skellysim_tpu.io.trajectory import TrajectoryWriter
    from skellysim_tpu.obs import step_record
    from skellysim_tpu.obs.summarize import summarize_files

    clock = _TickingClock()
    monkeypatch.setattr(obs_tracer, "time", clock)
    monkeypatch.setattr(step_record, "time", clock)
    system = fixtures.make_system(dt_write=1e-3)     # a frame every step
    state = fixtures.free_state(system)
    m = str(tmp_path / "metrics.jsonl")
    writer = TrajectoryWriter(str(tmp_path / "traj.out"))
    writer._fh = _StallingFile(writer._fh, clock, on_call=9, stall_s=3.0)
    tr = Tracer()
    with caplog.at_level(logging.WARNING, logger="skellysim_tpu"):
        with obs_tracer.use(tr):
            system.run(state, writer=writer.write_frame, metrics_path=m)
    writer.close()

    rows = [json.loads(ln) for ln in open(m)]
    assert len(rows) == 10
    slow = [r for r in rows if r["slow"]]
    assert [r["step"] for r in slow] == [8]
    verdict = slow[0]["slow"]
    assert verdict["in"] == "write_frame/io"
    assert verdict["over_p50"] > 50.0
    assert verdict["excess_ms"] == pytest.approx(3000.0, abs=5.0)
    assert set(verdict["counters"]) == set(step_record.COUNTERS)
    assert slow[0]["host_ms"]["write_frame/io"] > 3000.0
    assert slow[0]["loop_s"] == pytest.approx(
        3.0 + statistics.median(r["loop_s"] for r in rows), abs=0.01)

    faults = [e for e in tr.events if e["ev"] == "fault"]
    assert [f["kind"] for f in faults] == ["slow_step"]
    assert faults[0]["in"] == "write_frame/io" and faults[0]["step"] == 8
    assert faults[0]["over_p50"] == verdict["over_p50"]
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("slow step")]
    assert len(lines) == 1
    assert "in=write_frame/io +3.00" in lines[0] and "n=8" in lines[0]
    for field in ("majflt=", "nivcsw=", "gc_s=", "psi_mem_us=",
                  "psi_io_us="):
        assert field in lines[0]

    # `obs summarize` on the metrics file ALONE lists it
    report = summarize_files([m])
    section = report.split("== run loop ==")[1]
    assert "slow steps: 1" in section
    (listed,) = [ln for ln in section.splitlines()
                 if ln.startswith("SLOW step 8")]
    assert "in=write_frame/io" in listed and "majflt=" in listed
    assert "write_frame/io" in section and "loop - dispatch - wait" in section


def test_slow_rule_needs_eight_records_and_twice_the_median():
    """The rule alone, on records made by hand: nothing flags before eight
    records exist, a step 1.5x the median (the walkthrough's alternation
    reads 1.38x) never does, one 3x does and names the leaf that grew."""
    from skellysim_tpu.obs import step_record

    rec = step_record.StepRecorder()

    def close(wait_ms, log_ms=1.0):
        record = {"n": rec.count, "step": rec.count, "start": 0.0,
                  "loop_s": (wait_ms + log_ms) / 1e3,
                  "host_ms": {"wait": wait_ms, "log": log_ms},
                  "counters": dict.fromkeys(step_record.COUNTERS, 0),
                  "slow": None}
        rec._judge(record)
        rec.ring.append(record)
        rec.count += 1
        return record["slow"]

    assert close(5000.0) is None        # the compiling first step
    for _ in range(6):
        assert close(100.0) is None
    assert close(1000.0) is None        # the eighth: seven before it
    for i in range(20):                 # 2- and 3-sweep steps in turn
        assert close(100.0 if i % 2 else 150.0) is None
    verdict = close(105.0, log_ms=300.0)
    assert verdict["in"] == "log"
    assert verdict["excess_ms"] == pytest.approx(299.0)
    # the median of the 28 before it is 101 ms (sixteen of them read that)
    assert verdict["over_p50"] == pytest.approx(405.0 / 101.0, abs=2e-3)
    # a toy's millisecond steps: 3x the median, yet under the floor in
    # seconds, and the flag's own cost can never flag the next
    toy = step_record.StepRecorder()
    rec = toy
    for _ in range(10):
        assert close(1.0, log_ms=0.1) is None
    assert close(4.0, log_ms=0.1) is None


def test_unreadable_pressure_files_give_nulls(monkeypatch):
    """Where /proc/pressure cannot be opened the three counters are None —
    never a zero for "not seen" — and no read raises."""
    import os

    from skellysim_tpu.obs import step_record

    real_open = os.open

    def refusing(path, *a, **kw):
        if str(path).startswith("/proc/pressure"):
            raise PermissionError(path)
        return real_open(path, *a, **kw)

    monkeypatch.setattr(step_record, "_PSI_FDS", None)
    monkeypatch.setattr(os, "open", refusing)
    before = step_record.read_counters()
    assert [before[k] for k in ("psi_mem_us", "psi_io_us",
                                "psi_cpu_us")] == [None] * 3
    rec = step_record.StepRecorder()
    rec.enter()
    rec.span_closed("step/wait", 0.001, True)
    record = rec.close(0)
    rec.leave()
    assert record["counters"]["psi_mem_us"] is None
    assert record["counters"]["psi_io_us"] is None
    assert record["counters"]["psi_cpu_us"] is None
    assert record["counters"]["majflt"] >= 0
    assert record["host_ms"]["wait"] == pytest.approx(1.0)
    # dropped with the patch: the next reader opens the real files again
    monkeypatch.setattr(step_record, "_PSI_FDS", None)


def test_step_record_cost_guard():
    """2,000 record cycles with the loop's span set average under 0.5 ms
    (the chip's host reads 0.12 ms a cycle: PERF.md section 6); generous,
    so that a crowded CI host does not flake."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "step_record_cost.py")
    spec = importlib.util.spec_from_file_location("step_record_cost", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = mod.measure(2000)
    assert out["record_ms"] < 0.5
    assert out["cost_ms_per_step"] < 0.5
