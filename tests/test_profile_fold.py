"""The device-time fold (`obs.profile.load_device_trace`) on real TPU dumps,
the cache-key rule, and the run loop's spans on the profiler's clock.

Two recordings from a TPU v5e ("TPU v5 lite", JAX 0.9.0), read here with no
chip:

* ``data/toy_step_v5e.xplane.pb.gz`` — ONE `System.run(profile_dir=)` step
  of a toy coupled scene (2 fibers x 8 nodes, a 64-node shell, a 40-node
  body, tol 1e-7, `gmres_restart` 10, mixed precision: 5 iterations, 2
  sweeps) with its frame write, recorded with the operator scopes and the
  run-loop spans of PR 26 and cut to what the fold reads
  (`scripts/record_profile_fixture.py`: events without their stats,
  executed instructions with their name and ``op_name`` only; 59,697 op
  events, 564 KiB);
* ``chipbench/tests/data/pair_tile_probes_v5e.xplane.pb.gz`` — PR 25's
  probe trace: four modules, two of them with one NAME
  (``jit_convert_element_type``) and instructions named alike across
  modules (``copy.1``, ``custom-call.1``).
"""

import gzip
import json
import os

import pytest

import jax
import jax.numpy as jnp

from skellysim_tpu.obs import profile as profile_mod
from skellysim_tpu.obs import tracer as obs_tracer

HERE = os.path.dirname(os.path.abspath(__file__))
STEP = os.path.join(HERE, "data", "toy_step_v5e.xplane.pb.gz")
PROBES = os.path.join(os.path.dirname(HERE), "chipbench", "tests", "data",
                      "pair_tile_probes_v5e.xplane.pb.gz")
OPERATORS = set(profile_mod.OPERATOR_SCOPES)


@pytest.fixture(scope="module")
def step():
    return profile_mod.load_device_trace(STEP)


# ------------------------------------------------------- the fold, TPU dumps

def test_step_paths_keep_phase_and_operator(step):
    """The whole recognised path stays: the triangular solves, the f64
    residual's pair sums and the Krylov bookkeeping are different keys."""
    phases = {g["key"] for g in step.by_phase()}
    assert "gmres/arnoldi/precond/fiber" in phases
    assert any(p.startswith("gmres/refine") and p.endswith("pair")
               for p in phases), sorted(phases)
    assert {"gmres/gram", "gmres/givens", "advance"} <= phases
    seen = {c for p in phases for c in p.split("/")}
    assert OPERATORS <= seen and not step.stale
    assert step.attributed_frac >= 0.9
    # JAX's own `while/body` is no `body` scope: nothing is under a body
    # operator that is not also in a body's module function
    assert profile_mod.phase_of("jit(f)/gmres/while/body/arnoldi/mul") \
        == "gmres/arnoldi"
    assert profile_mod.phase_of("jit(f)/gmres/while/body/body/mul") \
        == "gmres/body"


def test_self_time_partitions_the_busy_time(step):
    """Self time is duration less same-line children, containers keep
    none: the rows sum to the union of the op intervals."""
    containers = [e for e in step.events
                  if e["name"].split(".")[0] in profile_mod.CONTAINERS]
    assert containers and all(e["self_us"] == 0 for e in containers)
    assert max(e["dur"] for e in containers) > 100 * max(
        e["self_us"] for e in step.events)
    leaves = sum(e["self_us"] for e in step.events)
    assert leaves == pytest.approx(step.total_us, rel=1e-9)
    assert step.total_us <= step.busy_us * (1 + 1e-9)
    # what the containers' own spans add is loop control between trips
    assert step.total_us >= 0.85 * step.busy_us
    parts = (step.attributed_us - step.inferred_us, step.inferred_us,
             step.total_us - step.attributed_us)
    assert all(p >= 0 for p in parts)
    assert sum(parts) == pytest.approx(step.total_us)
    assert sum(g["share"] for g in step.by_phase()) == pytest.approx(1.0)


def test_cross_table_and_seconds(step):
    table = step.cross_table()
    assert {"prep", "gmres", "refine", "advance"} <= set(table)
    assert sum(sum(row.values()) for row in table.values()) \
        == pytest.approx(step.total_us * 1e-6)
    assert "fiber" in table["gmres"] and "pair" in table["refine"]
    # the readers' cuts: a phase, a phase without another, an operator
    assert step.seconds(("refine",)) == pytest.approx(
        sum(table["refine"].values()))
    assert step.seconds(("gmres",), ("refine",)) == pytest.approx(
        sum(table["gmres"].values()))
    assert 0 < step.seconds(("precond", "fiber")) < step.seconds(("fiber",))
    assert step.seconds(("ring-step",)) is None      # not seen is not zero
    assert profile_mod.operator_of("prep/shell/pair") == "shell/pair"
    assert profile_mod.step_phase_of("gmres/refine/pair") == "refine"
    assert profile_mod.step_phase_of(None) == "(unattributed)"
    doc = profile_mod.profile_json(step)
    assert doc["phase_by_operator"] == table and not doc["stale_metadata"]
    text = profile_mod.render_table(step, by="cross")
    assert "phase \\ operator (ms)" in text and "refine" in text


def test_window_clips_on_the_dumps_own_clock(step):
    """The window is in the nanoseconds `jax.profiler.ProfileData` reports;
    an op that straddles an edge counts for the part inside."""
    with gzip.open(STEP, "rb") as fh:
        pd = jax.profiler.ProfileData.from_serialized_xspace(fh.read())
    (dev,) = [p for p in pd.planes if p.name.startswith("/device:TPU")]
    (ops,) = [ln for ln in dev.lines if ln.name == "XLA Ops"]
    starts = sorted(ev.start_ns for ev in ops.events)
    assert step.window_us[0] == pytest.approx(starts[0] * 1e-3, abs=1e-3)
    lo, hi = step.window_us
    mid = (lo + hi) / 2
    first = profile_mod.load_device_trace(STEP, window=(lo * 1e3, mid * 1e3))
    second = profile_mod.load_device_trace(STEP, window=(mid * 1e3, hi * 1e3))
    assert first.busy_us + second.busy_us == pytest.approx(step.busy_us,
                                                           rel=1e-9)
    assert first.total_us + second.total_us == pytest.approx(step.total_us,
                                                             rel=1e-6)
    assert 0 < first.total_us < step.total_us
    assert all(lo <= e["ts"] and e["ts"] + e["dur"] <= mid + 1e-6
               for e in first.events)


def test_idle_gaps_are_cut_and_put_down_to_the_run_loops_spans(step):
    paths = {s[2] for s in step.spans}
    assert {"skelly/run", "skelly/run/step", "skelly/run/step/dispatch",
            "skelly/run/step/wait", "skelly/run/step/fetch_info",
            "skelly/run/step/advance_clock", "skelly/run/step/write_frame",
            "skelly/run/step/write_frame/encode",
            "skelly/run/step/write_frame/io"} <= paths
    assert all(s[3].get("step") == 0 for s in step.spans
               if s[2].startswith("skelly/run/step"))
    gaps = step.idle_gaps()
    lo, hi = step.window_us
    assert sum(e - s for s, e, _ in gaps) == pytest.approx(
        (hi - lo) - step.busy_us, rel=1e-9)
    assert gaps == sorted(gaps, key=lambda g: g[0] - g[1])
    # a gap is cut at the spans' edges: every piece lies inside the span
    # it is put down to, and in none of that span's children
    whole = {p[len(profile_mod.SPAN_PREFIX):]: (a, b)
             for a, b, p, _ in step.spans}
    for s, e, label in gaps[:50]:
        a, b = whole[label]
        assert a <= s and e <= b, label
        assert not any(p.startswith(label + "/") and a2 < e and b2 > s
                       for p, (a2, b2) in whole.items()), label
    table = step.gap_table(min_us=100.0)
    assert table and all(r["ms"] >= 0.1 for r in table)
    # the long gap after the step's last op is split among the loop's
    # spans; what is left to `step` itself is the JSONL tracer writing
    # each child's record after the child closed (this run had one on)
    by_label = {r["label"]: r["ms"] for r in table}
    assert {"run/step/wait", "run/step/fetch_info",
            "run/step/write_frame/encode"} <= set(by_label), by_label
    assert "run" not in by_label and "-" not in by_label
    assert by_label.get("run/step", 0.0) < 0.05 * sum(by_label.values())
    # this capture lies wholly inside `System.run`: every gap is the loop's
    assert step.idle_us_inside("skelly/run") == pytest.approx(
        (hi - lo) - step.busy_us)
    assert step.idle_us_inside("skelly/run/step/wait") > 0
    assert "device idle time by run-loop span" in profile_mod.render_table(
        step)


def test_modules_named_alike_stay_apart():
    """An op's module is the `XLA Modules` event around it, program id and
    all: two programs of one name, and instructions of one name in several
    programs, each get their own module's scope path."""
    trace = profile_mod.load_device_trace(PROBES)
    modules = {r["module"] for r in trace.rows}
    assert len(modules) == 4
    assert len({m for m in modules
                if m.startswith("jit_convert_element_type(")}) == 2
    homes = {r["module"] for r in trace.rows if r["op"] == "copy.1"}
    assert len(homes) == 2
    tile = [r for r in trace.rows if r["op"] == "stokeslet_pallas.1"]
    assert len(tile) == 1 and tile[0]["count"] == 6
    assert tile[0]["scope"].endswith("jit(stokeslet_pallas)/pallas_call")
    assert tile[0]["dur_us"] == pytest.approx(6 * 3173.8, rel=2e-3)
    # recorded before the scopes: all of it unattributed, none of it hidden
    assert trace.attributed_us == 0 and not trace.stale
    assert trace.by_phase()[0]["key"] == "(unattributed)"


def test_op_name_map_reads_both_dumps():
    names = profile_mod.load_op_name_map(PROBES)
    assert len(names) == 70
    assert names[("jit_stokeslet_direct", "stokeslet_pallas.1")] == \
        "jit(stokeslet_direct)/jit(stokeslet_pallas)/pallas_call"
    names = profile_mod.load_op_name_map(STEP)
    solve = {k[1]: v for k, v in names.items() if "solve" in k[0]}
    assert len(solve) > 1000
    assert any("/precond/fiber/" in v for v in solve.values())
    assert any("/refine/" in v and "/pair/" in v for v in solve.values())


def test_obs_profile_cli_on_a_tpu_dump(capsys, tmp_path):
    from skellysim_tpu.obs.cli import main

    assert main(["profile", STEP]) == 0
    out = capsys.readouterr().out
    assert "gmres/arnoldi/precond/fiber" in out
    assert "attributed to named phases" in out and "stale" not in out
    assert main(["profile", STEP, "--by", "cross"]) == 0
    assert "phase \\ operator" in capsys.readouterr().out
    assert main(["profile", STEP, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["attributed_frac"] >= 0.9 and doc["idle_gaps"]
    # a directory with no dump says what it looked for
    assert main(["profile", str(tmp_path)]) == 2
    assert "*.xplane.pb" in capsys.readouterr().err


def test_emit_device_phases_gives_a_tpu_dump_its_phases():
    tr = obs_tracer.Tracer()
    n = profile_mod.emit_device_phases(STEP, tr)
    evs = [e for e in tr.events if e["ev"] == "device_phase"]
    assert n == len(evs) > 10
    assert not [e for e in tr.events if e["ev"] == "device_phase_error"]
    assert any(e["phase"] == "gmres/arnoldi/precond/fiber" for e in evs)
    assert not any(e["stale_metadata"] for e in evs)


# ------------------------------------------- the cache and the scopes (CPU)

@pytest.fixture()
def compile_cache(tmp_path):
    """A persistent compile cache of this test's own that keeps every
    program, and the settings put back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_compilation_cache_include_metadata_in_key")
    was = {k: getattr(jax.config, k) for k in keys}
    jax.config.update(keys[0], str(tmp_path / "cache"))
    jax.config.update(keys[1], 0.0)
    jax.config.update(keys[2], 0)
    cc.reset_cache()
    yield
    for k, v in was.items():
        jax.config.update(k, v)
    cc.reset_cache()
    jax.clear_caches()


@pytest.mark.parametrize("scopes_in_key", [False, True])
def test_cached_executable_serves_the_first_compilers_scopes(
        compile_cache, tmp_path, scopes_in_key):
    """Compile a solve whose paths name no operator, then add the ``fiber``
    scope and capture: the cache hands back the first executable and the
    fold says ``stale metadata`` — unless the scope paths are in the key,
    as `profile_session` puts them."""
    def program(scope):
        @jax.jit
        def solve(x):
            with jax.named_scope("gmres"), jax.named_scope(scope):
                return jnp.sin(x) @ x
        return solve

    x = jnp.ones((32, 32))
    program("before-the-scopes")(x).block_until_ready()
    jax.clear_caches()
    with profile_mod.profile_session(str(tmp_path / "prof")):
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        profile_mod.include_scopes_in_cache_key(scopes_in_key)
        program("fiber")(x).block_until_ready()
    assert not jax.config.jax_compilation_cache_include_metadata_in_key
    trace = profile_mod.load_device_trace(str(tmp_path / "prof"))
    phases = {g["key"] for g in trace.by_phase()}
    if scopes_in_key:
        assert "gmres/fiber" in phases and not trace.stale
    else:
        assert "gmres" in phases and "gmres/fiber" not in phases
        assert trace.stale
        assert "stale metadata" in profile_mod.render_table(trace)


# ----------------------------------------- the run loop on the trace's clock

def test_run_loop_spans_land_in_the_capture(tmp_path):
    """`System.run(profile_dir=)` on the CPU: the dump holds the loop's
    ``skelly/`` annotations with their step ids, on the clock of the ops,
    every one inside its parent; the JSONL stream holds the same spans."""
    from skellysim_tpu.audit import fixtures
    from skellysim_tpu.io.trajectory import TrajectoryWriter

    system = fixtures.make_system()
    state = fixtures.free_state(system)
    state = system.run(state, max_steps=1)           # compile outside
    prof, stream = str(tmp_path / "prof"), str(tmp_path / "t.jsonl")
    with TrajectoryWriter(str(tmp_path / "traj.out")) as writer:
        system.run(state, max_steps=2, writer=writer.write_frame,
                   profile_dir=prof, trace_path=stream)
    trace = profile_mod.load_device_trace(prof)
    spans = {}
    for a, b, path, stats in trace.spans:
        spans.setdefault(path, []).append((a, b, stats))
    children = ("dispatch", "wait", "fetch_info", "log", "advance_clock",
                "clock_read")
    for name in children:
        assert len(spans[f"skelly/run/step/{name}"]) == 2, name
    assert [s[2]["step"] for s in spans["skelly/run/step"]] == [0, 1]
    assert [s[2]["step"] for s in spans["skelly/run/step/wait"]] == [0, 1]
    (run,) = spans["skelly/run"]
    for path, found in spans.items():
        for a, b, _ in found:
            assert run[0] <= a <= b <= run[1], path
    # the device worked while the host waited, not while it dispatched
    lo, hi = trace.window_us
    assert run[0] <= lo and hi <= run[1]
    waits = spans["skelly/run/step/wait"]
    assert any(a <= e["ts"] <= b for e in trace.events for a, b, _ in waits)
    # one frame at most crossed a write boundary: its halves are spans too
    frames = spans.get("skelly/run/step/write_frame", [])
    assert len(spans.get("skelly/run/step/write_frame/io", [])) == len(frames)

    recs = [json.loads(ln) for ln in open(stream)]
    by_path = {}
    for r in recs:
        if r["ev"] == "span":
            by_path.setdefault(r["path"], []).append(r)
    assert {f"run/step/{c}" for c in children} <= set(by_path)
    assert all(r["parent"] == "run/step" and r["step"] in (0, 1)
               for c in children for r in by_path[f"run/step/{c}"])
    for r in by_path.get("run/step/write_frame", []):
        assert r["bytes"] > 0
    assert any(r["ev"] == "device_phase" for r in recs)
    assert not any(r["ev"] == "device_phase_error" for r in recs)


# ------------------------------------------------------ several device planes

def _two_chip_trace():
    """A hand-made fold of a two-chip dump: the same three instructions a
    chip, the second chip a little slower in its ring."""
    def row(pid, op, phase, us, collective=None):
        return {"pid": pid, "op": op, "module": "jit_step", "phase": phase,
                "inferred": False, "collective": collective, "scope": "",
                "dur_us": us, "count": 1}

    rows, events = [], []
    for pid, ring_us in (("/device:TPU:0", 700.0), ("/device:TPU:1", 900.0)):
        rows += [row(pid, "tile.1", "gmres/pair/ring-step", ring_us),
                 row(pid, "dot.2", "gmres/gram/psum-dots", 100.0),
                 row(pid, "all-reduce-start.3", "gmres/gram/psum-dots", 10.0,
                     collective="all_reduce"),
                 row(pid, "collective-permute-done.4",
                     "gmres/pair/ring-step", 5.0,
                     collective="collective_permute")]
        events.append({"pid": pid, "ts": 0.0, "dur": ring_us + 115.0})
    return profile_mod.DeviceTrace(rows, events, window_us=(0.0, 1100.0))


def test_a_dump_of_several_planes_reads_per_chip():
    trace = _two_chip_trace()
    assert trace.planes == ["/device:TPU:0", "/device:TPU:1"]
    # the totals still sum over the planes ...
    assert trace.total_us == pytest.approx(1830.0)
    assert trace.seconds(has=("ring-step",)) == pytest.approx(1610e-6)
    # ... and the chips are kept apart
    assert trace.plane_seconds(has=("ring-step",)) == pytest.approx(
        {"/device:TPU:0": 705e-6, "/device:TPU:1": 905e-6})
    assert trace.plane_seconds(collective=True) == pytest.approx(
        {"/device:TPU:0": 15e-6, "/device:TPU:1": 15e-6})
    table = trace.plane_table()
    assert [r["plane"] for r in table] == trace.planes
    assert table[1]["busy_s"] == pytest.approx(1015e-6)
    assert table[0]["psum_dots_s"] == pytest.approx(110e-6)
    text = profile_mod.render_table(trace)
    assert "per chip (2 device planes" in text
    assert "/device:TPU:1" in text and "spread (max-min)/mean" in text
    assert profile_mod.profile_json(trace)["per_plane"] == table


def test_a_dump_of_one_plane_prints_no_per_chip_table(step):
    assert len(step.planes) == 1
    assert "per chip" not in profile_mod.render_table(step)
    assert step.plane_seconds() == pytest.approx(
        {step.planes[0]: step.total_us * 1e-6})
