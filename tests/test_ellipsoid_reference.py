"""The plain reference of `ellipsoid_256` (`chipbench/references/
clamped_shell_step.py`) against the program, at a test's size: the toy cut
of `examples/ellipsoid` (`chipbench/tests/toy/ellipsoid_toy.json`: 8
clamped 16-node fibers on a 300-node ellipsoid) built through the
harness's own `run.build` and stepped by `System.run`, judged by
`check.check_window` as a benchmark run is.

* the sound answer is under every limit on the full and the mixed tier;
* eight planted faults each read far over a limit in at least one number;
* the reference imports nothing of the program; its quadrature facts take
  the toy's shell and refuse a sphere's nodes and scaled weights;
* `shell_counts`' numbers against a hand count; the ``periphery`` event.

**The limits, and why** (the cell's own are set from chip readings in
`chipbench/configs/ellipsoid_256.json`; these are the toy's):
``ref_residual`` 1e-8 is the configuration's ``gmres_tol``, the guarantee
itself. The other two are that tolerance times how much smaller their
rows' right-hand side is than the whole, because a solve that stops under
``gmres_tol`` of the WHOLE norm may leave that much in them:
``ref_residual_fiber_bc`` 5e-8 (the 14 boundary rows hold 1 / 4.4 of |b|:
x_0 / dt and xs_0 / dt), ``ref_residual_shell`` 2e-2 (|b| / |b_shell| is
1.2e6 here: the shell's right-hand side is the wall forces' flow alone;
the full tier, which stops at 3.7e-9, reads 3.4e-3, and the mixed tier,
which refines to 7e-12, reads 1.9e-6).
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
reference = check.load_reference("clamped_shell_step")

LIMITS = {"ref_residual": 1e-8, "ref_residual_shell": 2e-2,
          "ref_residual_fiber_bc": 5e-8}
TOY = scene.load_json(os.path.join(BENCH, "tests", "toy",
                                   "ellipsoid_toy.json"))


def _configuration(tier):
    cfg = copy.deepcopy(TOY)
    cfg["params"]["solver_precision"] = tier
    return dict(cfg, reference="clamped_shell_step", limits=LIMITS)


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
    harness.log = lambda *_: None
    yield
    scene.CACHE_DIR = old


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
    got = _numbers(cfg, snaps, rows, tol)
    assert set(LIMITS) <= set(got)
    assert all(c["ok"] for c in got.values()), got
    # the reference and the program's own explicit residual are one number
    # to 1 % (four digits on the full tier; the mixed tier's 7e-12 is near
    # both sides' float64 rounding)
    theirs = max(r["residual_true"] for r in rows)
    assert abs(got["ref_residual"]["value"] - theirs) <= 0.01 * theirs


# ------------------------------------------------------- faults in the answer

def _flags_flipped(snaps):
    """Clamped read as free: the reference's INPUT, not the answer."""
    out = copy.deepcopy(snaps)
    for snap in out:
        for g in snap["fibers"]:
            g["minus_clamped"] = ~g["minus_clamped"]
    return out


def _one_coordinate(post, pre):
    # node 1 is the one the clamp's angular-velocity rows and X'''' both
    # read: y of node 1 reads 176 x the limit, x of node 0 reads 55 x (a
    # 16-node fiber's D4 has entries ~2e5; a 64-node fiber's 256 x that)
    post["fibers"][0]["x"][0, 1, 1] += 1e-6
    return post


ANSWER_FAULTS = {
    "flags_flipped": lambda snaps: (_flags_flipped(snaps), None),
    "one_coordinate_1e-6": lambda snaps: (
        controls.map_answers(snaps, _one_coordinate), snaps),
    "answer_in_float32": lambda snaps: (
        controls.map_answers(snaps, controls.f32_answer), snaps),
    "state_unchanged": lambda snaps: (
        controls.map_answers(snaps, controls.unchanged), snaps),
}


@pytest.mark.parametrize("tier", ["full", "mixed"])
@pytest.mark.parametrize("fault", sorted(ANSWER_FAULTS))
def test_fault_in_the_answer_reads_100x_a_limit(sound, tier, fault):
    cfg, snaps, rows, tol = sound[tier]
    bad, pre = ANSWER_FAULTS[fault](snaps)
    got = _numbers(cfg, bad, rows, tol, pre=pre)
    assert any(got[k]["value"] >= 100 * LIMITS[k] for k in LIMITS), got
    assert not all(c["ok"] for c in got.values())


# ------------------------------------------------------ faults in the program

#: fault -> at least how many times a limit one number has to read. The
#: program's own lower-precision path reads 20 x at this size (2.05e-7:
#: one float32 sweep gets that far on 16-node blocks of condition ~1e4;
#: the cell's 64-node blocks have 2e7, and there, on the chip, the same
#: control reads 271 x to 308 x: PERF.md section 2), the rest 128 x to
#: 1,677 x.
PROGRAM_FAULTS = {"fiber_to_shell_block": 100, "fiber_to_fiber_block": 100,
                  "shell_to_fiber_flow": 100, "max_refine_1": 10}


@pytest.mark.parametrize("fault", sorted(PROGRAM_FAULTS))
def test_fault_in_the_program_reads_far_over_a_limit(cache, tmp_path,
                                                     monkeypatch, fault):
    """One block of a pair sum zeroed, the shell's flow dropped (planted by
    `scripts/ellipsoid_pair_control.py`, never a switch in the program), or
    the program's own float32 path (`max_refine = 1`, mixed tier). A zeroed
    block is zeroed in the program's own residual too, so the program
    itself reports a sound step."""
    if fault == "max_refine_1":
        cfg, snaps, rows, tol = _stepped(
            tmp_path, "mixed", control={"params": {"max_refine": 1}})
    else:
        pair_control.plant(fault, monkeypatch.setattr)
        cfg, snaps, rows, tol = _stepped(tmp_path, "full")
        assert not check.failed_steps(rows, tol)
    got = _numbers(cfg, snaps, rows, tol)
    assert any(got[k]["value"] >= PROGRAM_FAULTS[fault] * LIMITS[k]
               for k in LIMITS), got
    assert not all(c["ok"] for c in got.values())


# ------------------------------------------------------------- the reference

def test_reference_imports_nothing_of_the_program():
    src = open(os.path.join(BENCH, "references",
                            "clamped_shell_step.py")).read()
    assert "skellysim" not in src.replace("SkellySim", "")
    assert "import jax" not in src.split("def step_residual")[0]


def test_quadrature_is_held_to_an_ellipsoids_facts(sound):
    shell = sound["full"][1][0]["geometry"]["shell"]
    abc = [TOY["periphery"][k] for k in "abc"]
    reference.check_quadrature(shell["nodes"], shell["normals"],
                               shell["weights"], abc)       # the toy's: fine
    on_sphere = (shell["nodes"] * 6.0
                 / np.linalg.norm(shell["nodes"], axis=1, keepdims=True))
    for nodes, normals, weights, said in [
            (on_sphere, shell["normals"], shell["weights"], "not on the"),
            (shell["nodes"], shell["normals"], 1.01 * shell["weights"],
             "weights sum"),
            (shell["nodes"], np.roll(shell["normals"], 1, axis=0),
             shell["weights"], "gradient"),
            (shell["nodes"], 1.001 * shell["normals"], shell["weights"],
             "unit")]:
        with pytest.raises(ValueError, match=said):
            reference.check_quadrature(nodes, normals, weights, abc)
    with pytest.raises(ValueError, match="b = c"):
        reference.check_quadrature(shell["nodes"], shell["normals"],
                                   shell["weights"], (7.8, 4.16, 4.0))


def test_spheroid_area_closed_form():
    # a sphere; the prolate and the oblate form against a midpoint rule of
    # the surface of revolution 2 pi a b int sqrt(1 - (1 - b^2/a^2) t^2) dt
    assert reference.spheroid_area(2.0, 2.0) == pytest.approx(16 * np.pi)
    t = (np.arange(200000) + 0.5) / 100000 - 1.0
    for a, b in ((7.8 * 1.04, 4.16 * 1.04), (1.0, 2.0)):
        integral = 2 * np.pi * a * b * np.sqrt(
            1 - (1 - (b / a) ** 2) * t ** 2).sum() / 100000
        assert reference.spheroid_area(a, b) == pytest.approx(integral,
                                                              rel=1e-8)


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
    assert gaps["fiber_to_shell"] > 0.3 and gaps["fiber_to_fiber"] > 0.3


# ------------------------------------------------------ counts and the event

def test_shell_counts_against_a_hand_count():
    import shell_counts

    # one pair: 3 + 5 + 4 + 3 + 5 + 5 + 2 + 6
    assert shell_counts.stresslet_flops(1, 1) == 33
    assert shell_counts.stresslet_flops(8000, 16384) == 33 * 8000 * 16384
    assert shell_counts.stresslet_bytes(8000, 16384) == 4 * (72000 + 98304)
    assert shell_counts.shell_product_flops(8000) == 2 * 24000 ** 2
    # the cell's step at 15 iterations and 2 sweeps: 17 operators of
    # 16,384 x 24,384 Stokeslet pairs, 8,000 x 16,384 stresslet pairs and
    # the 24,000^2 product
    once = 30 * 16384 * 24384 + 33 * 8000 * 16384 + 2 * 24000 ** 2
    assert once == 17_462_599_680
    assert shell_counts.shell_step_flops(16384, 8000, 15, 2) == 17 * once


def test_periphery_is_announced_once_a_build(cache, tmp_path, caplog):
    from skellysim_tpu.obs import tracer as obs_tracer
    from skellysim_tpu.obs.summarize import Summary

    system, state, *_ = harness.build(_configuration("mixed"), 3,
                                      str(tmp_path / "scene"))
    tr = obs_tracer.Tracer()
    with obs_tracer.use(tr), caplog.at_level(logging.INFO, "skellysim_tpu"):
        jax.eval_shape(system._solve_impl, state)
    (ev,) = [e for e in tr.events if e["ev"] == "periphery"]
    assert (ev["shape"], ev["nodes"], ev["operator"], ev["operator_dtype"],
            ev["operator_bytes"]) == ("ellipsoid", 300, "900x900", "float64",
                                      900 * 900 * 8)
    assert (ev["m_inv_dtype"], ev["m_inv_bytes"]) == ("float32",
                                                      900 * 900 * 4)
    assert (ev["f64_product"], ev["row_block"]) == ("whole", 0)
    assert ev["precompute"] == "periphery_precompute.npz"
    assert ev["load_s"] > 0
    line = ("periphery shape=ellipsoid nodes=300 operator=900x900 float64 "
            "6480000B m_inv=900x900 float32 3240000B f64_product=whole")
    assert line in caplog.text
    report = Summary()
    report.add_record(ev)
    assert "== periphery ==" in report.render()
    assert "f64_product=whole row_block=0" in report.render()


def test_a_large_float64_operator_is_said_to_go_in_row_blocks():
    from skellysim_tpu.periphery import periphery as peri

    def shell(rows, dtype):
        mat = jax.ShapeDtypeStruct((rows, rows), dtype)
        vec = jax.ShapeDtypeStruct((rows // 3, 3), dtype)
        return peri.PeripheryState(vec, vec, vec, mat, mat, vec)

    big = peri.describe(shell(24000, np.float64))
    assert (big["f64_product"], big["row_block"]) == ("row_blocks", 2048)
    assert big["operator_bytes"] == 4_608_000_000 and big["nodes"] == 8000
    assert peri.describe(shell(24000, np.float32))["f64_product"] == "whole"
    assert peri.describe(shell(4096, np.float64))["f64_product"] == "whole"
    # what is said is what `_apply_operator` does
    assert peri._row_blocked(shell(24000, np.float64).M_inv)


def test_a_system_without_a_shell_announces_none():
    from skellysim_tpu.obs import tracer as obs_tracer
    from skellysim_tpu.params import Params
    from skellysim_tpu.system import System

    tr = obs_tracer.Tracer()
    with obs_tracer.use(tr):
        System(Params())._announce_periphery(types.SimpleNamespace(shell=None))
    assert not [e for e in tr.events if e["ev"] == "periphery"]
