"""The plain reference of `oocyte_1024` (`chipbench/references/
revolution_shell_step.py`) against the program, at a test's size: the
cell's configuration cut to 12 clamped 32-node fibers in the same envelope
at ``n_nodes_target`` 300 (287 nodes in 17 rings), built through the
harness's own `run.build` and stepped by `System.run`, judged by
`check.check_window` as a benchmark run is.

* the sound answer is under every limit on the full and the mixed tier;
* planted faults (float32 answers, the state unchanged, one coordinate;
  `max_refine = 1`, a zeroed fiber -> shell and fiber -> fiber block, the
  shell's flow dropped) each read far over a limit in at least one number;
* the reference imports nothing of the program; its quadrature facts take
  the toy's shell and refuse an ellipsoid's, nodes off the envelope,
  normals of the envelope's own level function at the inflated nodes
  (what the program computed before this reference) and scaled weights;
* `fiber_block_counts` against a hand count, its reader on a folded
  trace; the ``periphery``, ``fiber_ops`` and ``block_precond`` events.

**The limits, and why** (the cell's own are set from chip readings in
`chipbench/configs/oocyte_1024.json`; these are the toy's):
``ref_residual`` 1e-8 is the configuration's ``gmres_tol``. The other two
are that tolerance times how much larger the whole right-hand side is than
their rows' scale, because a solve that stops under ``gmres_tol`` of the
WHOLE norm may leave that much in them: ``ref_residual_fiber_bc`` 9e-8
(|b| / |b_bc| = 11,098 / 1,265 here), ``ref_residual_shell`` 4e-4 (the
shell's right-hand side is zero without a wall force, so its rows are
measured against the flow they cancel: |b| / |A_sf x| = 11,098 / 0.286).
"""

import copy
import importlib.util
import json
import logging
import os
import sys
import types

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "chipbench")
if BENCH not in sys.path:       # the benchmark's modules name each other bare
    sys.path.insert(0, BENCH)

import check  # noqa: E402
import controls  # noqa: E402
import run as harness  # noqa: E402
import scene  # noqa: E402


def _by_path(name, *parts):
    spec = importlib.util.spec_from_file_location(name,
                                                  os.path.join(ROOT, *parts))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


pair_control = _by_path("ellipsoid_pair_control", "scripts",
                        "ellipsoid_pair_control.py")
reference = check.load_reference("revolution_shell_step")

LIMITS = {"ref_residual": 1e-8, "ref_residual_shell": 4e-4,
          "ref_residual_fiber_bc": 9e-8}
CELL = scene.load_json(os.path.join(BENCH, "configs", "oocyte_1024.json"))
ENVELOPE = CELL["periphery"]["envelope"]


def _configuration(tier):
    cfg = copy.deepcopy(CELL)
    cfg["fibers"]["n_fibers"] = 12
    cfg["periphery"]["envelope"]["n_nodes_target"] = 300
    cfg["params"]["solver_precision"] = tier
    return dict(cfg, limits=LIMITS)


def _stepped(tmp, tier, control=None):
    """(cfg, snaps, rows, tol) of two `System.run(max_steps=1)` calls."""
    cfg = _configuration(tier)
    system, state, rng, writer, _, _ = harness.build(
        cfg, 2**31 + 9, str(tmp / "scene"), control=control)
    metrics_path = str(tmp / "metrics.jsonl")
    snaps = [harness.snapshot(state, geometry=True)]
    for _ in range(2):
        state = system.run(state, writer=writer.write_frame, rng=rng,
                           metrics_path=metrics_path, max_steps=1)
        snaps.append(harness.snapshot(state))
    writer.close()
    with open(metrics_path) as fh:
        rows = [json.loads(line) for line in fh]
    return cfg, snaps, rows, float(system.params.gmres_tol)


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    old, scene.CACHE_DIR = scene.CACHE_DIR, str(
        tmp_path_factory.mktemp("cache"))
    old_log, harness.log = harness.log, lambda *_: None
    jax.config.update("jax_enable_x64", True)       # as `run.Cell` does
    yield
    scene.CACHE_DIR, harness.log = old, old_log


@pytest.fixture(scope="module")
def sound(cache, tmp_path_factory):
    return {tier: _stepped(tmp_path_factory.mktemp(tier), tier)
            for tier in ("full", "mixed")}


def _numbers(cfg, snaps, rows, tol, pre=None):
    got = check.check_window(cfg, {"checked_steps": 2}, rows, snaps, {},
                             seed=1, tol=tol, eta=1.0, log=lambda *_: None,
                             pre_snaps=pre)
    return {c["name"]: c for c in got}


# ------------------------------------------------------------------ agreement

@pytest.mark.parametrize("tier", ["full", "mixed"])
def test_sound_answer_is_under_every_limit(sound, tier):
    cfg, snaps, rows, tol = sound[tier]
    assert all(r["accepted"] and r["health"] == 0 for r in rows)
    assert all(r["fiber_error"] > 0 for r in rows)  # the fibers carry force
    got = _numbers(cfg, snaps, rows, tol)
    assert set(LIMITS) <= set(got)
    assert all(c["ok"] for c in got.values()), got
    # the reference and the program's own explicit residual are one number
    theirs = max(r["residual_true"] for r in rows)
    assert abs(got["ref_residual"]["value"] - theirs) <= 0.01 * theirs


def test_the_shells_right_hand_side_is_zero(sound):
    """No wall force, no body, no external flow: nothing drives the shell's
    rows but the fibers' implicit flow, which `scales` measures."""
    cfg, snaps, rows, tol = sound["full"]
    got = reference.scales(cfg, dict(snaps[0]), snaps[1],
                           dt=snaps[0]["dt"], eta=1.0)
    assert got["b_shell"] == 0.0
    assert got["fiber_flow_on_shell"] > 0.0
    # the toy's limits are the tolerance scaled by these norms
    assert LIMITS["ref_residual_shell"] >= (
        tol * got["b"] / got["fiber_flow_on_shell"])
    assert LIMITS["ref_residual_fiber_bc"] >= tol * got["b"] / got["b_bc"]


# ------------------------------------------------------- faults in the answer

def _one_coordinate(post, pre):
    post["fibers"][0]["x"][0, 1, 1] += 1e-6
    return post


ANSWER_FAULTS = {
    "one_coordinate_1e-6": _one_coordinate,
    "answer_in_float32": controls.f32_answer,
    "state_unchanged": controls.unchanged,
}


@pytest.mark.parametrize("tier", ["full", "mixed"])
@pytest.mark.parametrize("fault", sorted(ANSWER_FAULTS))
def test_fault_in_the_answer_reads_100x_a_limit(sound, tier, fault):
    cfg, snaps, rows, tol = sound[tier]
    bad = controls.map_answers(snaps, ANSWER_FAULTS[fault])
    got = _numbers(cfg, bad, rows, tol, pre=snaps)
    assert any(got[k]["value"] >= 100 * LIMITS[k] for k in LIMITS), got
    assert not all(c["ok"] for c in got.values())


# ------------------------------------------------------ faults in the program

#: fault -> at least how many times a limit one number has to read (at this
#: size: the zeroed blocks 69 x to 166 x, the shell's flow dropped far more,
#: the program's own float32 path 171 x and 276 x: one float32 sweep on
#: 32-node blocks; on the chip at the cell's size 457 x, PERF.md section 2)
PROGRAM_FAULTS = {"fiber_to_shell_block": 50, "fiber_to_fiber_block": 50,
                  "shell_to_fiber_flow": 50, "max_refine_1": 100}


@pytest.mark.parametrize("fault", sorted(PROGRAM_FAULTS))
def test_fault_in_the_program_reads_far_over_a_limit(cache, tmp_path, fault):
    """One block of a pair sum zeroed, the shell's flow dropped (planted by
    `scripts/ellipsoid_pair_control.py`, never a switch in the program), or
    the program's own float32 path (`max_refine = 1`, mixed tier). A zeroed
    block is zeroed in the program's own residual too, so the program
    itself reports a sound step."""
    if fault == "max_refine_1":
        cfg, snaps, rows, tol = _stepped(
            tmp_path, "mixed", control={"params": {"max_refine": 1}})
    else:
        restore = []
        pair_control.plant(fault, lambda mod, attr, new: (
            restore.append((mod, attr, getattr(mod, attr))),
            setattr(mod, attr, new)))
        try:
            cfg, snaps, rows, tol = _stepped(tmp_path, "full")
        finally:
            for mod, attr, old in restore:
                setattr(mod, attr, old)
        assert not check.failed_steps(rows, tol)
    got = _numbers(cfg, snaps, rows, tol)
    assert any(got[k]["value"] >= PROGRAM_FAULTS[fault] * LIMITS[k]
               for k in LIMITS), got
    assert not all(c["ok"] for c in got.values())


# ------------------------------------------------------------- the reference

def test_reference_imports_nothing_of_the_program():
    src = open(os.path.join(BENCH, "references",
                            "revolution_shell_step.py")).read()
    assert "skellysim" not in src.replace("SkellySim", "")
    assert "import jax" not in src.split("def _step")[0]


def _old_rule_normals(nodes):
    """Inward normals from the gradient of the ENVELOPE's level function
    h(x)^2 - y^2 - z^2 at the inflated nodes, the caps at the envelope's
    own bounds: what the program stored before this reference held it."""
    env = reference.Envelope(ENVELOPE)
    x = nodes[:, 0]
    g = np.stack([-env(x) * env.slope(x), nodes[:, 1], nodes[:, 2]], axis=1)
    with np.errstate(invalid="ignore"):     # the tips, set below
        g /= np.linalg.norm(g, axis=1, keepdims=True)
    g[x <= env.lower] = [-1.0, 0.0, 0.0]
    g[x >= env.upper] = [1.0, 0.0, 0.0]
    return -g


@pytest.mark.parametrize("what", ["own", "ellipsoid", "off_the_envelope",
                                  "old_rule_normals", "scaled_weights",
                                  "scaled_normals"])
def test_quadrature_is_held_to_a_surface_of_revolutions_facts(sound, what):
    shell = sound["full"][1][0]["geometry"]["shell"]
    nodes, normals, weights = (shell["nodes"].copy(), shell["normals"],
                               shell["weights"])
    said = {"own": None, "ellipsoid": "not on the",
            "off_the_envelope": "not on the", "old_rule_normals": "normals",
            "scaled_weights": "weights sum", "scaled_normals": "unit"}[what]
    if what == "ellipsoid":
        from skellysim_tpu.periphery.shapes import ellipsoid_shape

        spec = ellipsoid_shape(300, 3.9, 2.8, 2.8)
        nodes, normals = spec.nodes, -spec.node_normals
    elif what == "off_the_envelope":
        i = int(np.argmax(np.hypot(nodes[:, 1], nodes[:, 2])))
        nodes[i, 1:] *= 1.0 + 1e-6
    elif what == "old_rule_normals":
        normals = _old_rule_normals(nodes)
    elif what == "scaled_weights":
        weights = 1.01 * weights
    elif what == "scaled_normals":
        normals = 1.001 * normals
    if said is None:
        reference.check_quadrature(nodes, normals, weights, ENVELOPE)
        return
    with pytest.raises(ValueError, match=said):
        reference.check_quadrature(nodes, normals, weights, ENVELOPE)


def test_envelope_area_and_slope_against_a_sphere():
    """A sphere of radius R as an envelope: the area of the inflated surface
    is 4 pi (1.04 R)^2, the slope -x / h."""
    env = reference.Envelope({"height": "np.sqrt(R**2 - x**2)", "R": 2.0,
                              "lower_bound": -2.0, "upper_bound": 2.0})
    assert env.area() == pytest.approx(4 * np.pi * (1.04 * 2.0) ** 2,
                                       rel=1e-9)
    x = np.array([-1.5, 0.3, 1.9])
    np.testing.assert_allclose(env.slope(x), -x / np.sqrt(4.0 - x * x),
                               rtol=1e-12)


def test_reference_refuses_what_it_does_not_write(sound):
    cfg, snaps, rows, tol = sound["full"]
    pre = dict(snaps[0])
    for key, value in (("plus_pinned", True), ("binding_body", 0),
                       ("active", False)):
        bad = copy.deepcopy(pre)
        bad["fibers"][0][key][0] = value
        with pytest.raises(ValueError, match="free plus end"):
            reference.step_residual(cfg, bad, snaps[1], dt=pre["dt"], eta=1.0)


def test_no_pair_is_near_the_regularisation(sound):
    snaps = sound["full"][1]
    x = np.concatenate([g["x"] for g in snaps[0]["fibers"]])
    gaps = reference.closest_pairs(x, snaps[0]["geometry"]["shell"]["nodes"])
    assert gaps["fiber_to_shell"] > 0.03 and gaps["fiber_to_fiber"] > 0.03


# ------------------------------------------------------ counts and the events

def test_fiber_block_counts_against_a_hand_count():
    import fiber_block_counts as fbc

    # one fiber of 32 nodes: A_bc 128 x 128, P_down 114 x 128, D1 32 x 32
    entries = 128 * 128 + 114 * 128 + 32 * 32
    assert fbc.matvec_flops(1, 32) == 30 * entries
    vectors = (128 + 128) + (114 + 128) + (32 + 32)
    assert fbc.matvec_bytes(1, 32) == 8 * (entries + vectors)
    # 1,024 fibers: the shared matrices' words once, the blocks' each
    assert fbc.matvec_bytes(1024, 32) == 8 * (
        1024 * 128 * 128 + 114 * 128 + 32 * 32 + 1024 * vectors)
    assert fbc.matvec_flops(1024, 32) == 1024 * 30 * entries


def test_fiber_block_df_roofline_reads_the_tiles_calls_under_matvec():
    """The reader on a folded trace: the tile's calls under ``fiber`` and
    ``matvec`` only (not ``fiber/force``, not other ops), three calls an
    application, the least time over their self time."""
    import counts
    import fiber_block_counts as fbc

    reader = harness.load_module("metrics", "fiber_block_df_roofline")
    rows = [
        {"op": "block_matvec_df.1", "phase": "gmres/arnoldi/fiber/matvec",
         "dur_us": 4000.0, "count": 30},
        {"op": "block_matvec_df.4", "phase": "gmres/arnoldi/fiber/force",
         "dur_us": 9999.0, "count": 10},
        {"op": "fusion.7", "phase": "gmres/arnoldi/fiber/matvec",
         "dur_us": 9999.0, "count": 10}]
    run = types.SimpleNamespace(
        phase_fold=types.SimpleNamespace(rows=rows, stale=False),
        snaps=[{"fibers": [{"x": np.zeros((1024, 32, 3))}]}],
        peaks={"flops_per_s": 197e12, "bytes_per_s": 819e9})
    least, side = counts.least_seconds(10 * fbc.matvec_flops(1024, 32),
                                       10 * fbc.matvec_bytes(1024, 32),
                                       run.peaks)
    assert side == "memory"
    assert reader.read(run) == pytest.approx(100 * least / 4000e-6)
    run.phase_fold.rows = rows[1:]
    assert reader.read(run) is None            # no tile under the matvec


def test_events_name_the_bucket_and_the_revolution_shell(cache, tmp_path,
                                                         caplog):
    from skellysim_tpu.obs import tracer as obs_tracer
    from skellysim_tpu.obs.summarize import Summary

    system, state, *_ = harness.build(_configuration("mixed"), 3,
                                      str(tmp_path / "scene"))
    system._fiber_ops = "df_tile"       # what a TPU takes: 12 blocks a step
    tr = obs_tracer.Tracer()
    with obs_tracer.use(tr), caplog.at_level(logging.INFO, "skellysim_tpu"):
        jax.eval_shape(system._solve_impl, state)
    ev = {e["ev"]: e for e in tr.events
          if e["ev"] in ("periphery", "fiber_ops", "block_precond")}
    assert (ev["periphery"]["shape"], ev["periphery"]["nodes"],
            ev["periphery"]["bounds"]) == ("revolution", 287, "-3.75:3.75")
    assert (ev["fiber_ops"]["nodes"], ev["fiber_ops"]["block"],
            ev["fiber_ops"]["blocks_per_step"]) == ("32", "128x128", "12")
    assert (ev["block_precond"]["fiber_nodes"],
            ev["block_precond"]["fiber_block"]) == ("32", "128x128")
    for line in ("periphery shape=revolution nodes=287 ",
                 "bounds=-3.75:3.75",
                 "nodes=32 block=128x128 blocks_per_step=12",
                 "fiber_nodes=32 fiber_block=128x128"):
        assert line in caplog.text
    report = Summary()
    for e in ev.values():
        report.add_record(e)
    text = report.render()
    assert "bounds=-3.75:3.75" in text
    assert "nodes=32 block=128x128 blocks_per_step=12" in text
    assert "fiber_nodes=32 fiber_block=128x128" in text
