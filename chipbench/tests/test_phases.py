"""The readers of the `*_device_s` metrics (`phases.py`) on the recorded
TPU v5e step kept with the program's tests
(`tests/data/toy_step_v5e.xplane.pb.gz`: one `System.run` step of a toy
coupled scene with the operator scopes and run-loop spans), and through a
whole traced toy run."""

import argparse
import importlib.util
import os

import pytest

import phases

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
STEP = os.path.join(os.path.dirname(BENCH), "tests", "data",
                    "toy_step_v5e.xplane.pb.gz")
NEW = ("prep_device_s", "advance_device_s", "gmres_device_s",
       "refine_device_s", "krylov_device_s", "pair_device_s",
       "fiber_solve_device_s", "shell_device_s", "phase_attributed_pct",
       "host_gap_ms_per_step")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Trace:
    """What a reader needs of `xplane.TraceSummary`."""

    def __init__(self, window_ns, steps=1):
        self.window_ns = window_ns
        self._steps = steps

    def span_seconds(self, name):
        return [1.0] * self._steps if name == "chipbench_step" else []


class Run:
    def __init__(self, window_ns=(0.0, 1e18)):
        self.probes = {}
        self.trace = Trace(window_ns)
        self.trace_path = STEP


@pytest.fixture(scope="module")
def run():
    r = Run()
    for name in NEW:            # every reader's probe is the one fold
        reader(name).probe(r)
    return r


def test_every_new_metric_reads_the_recorded_step(run):
    values = {name: reader(name).read(run) for name in NEW}
    assert all(v is not None and v > 0 for v in values.values()), values
    fold = run.phase_fold
    # the four phases and the unattributed make up the op time
    phases_s = sum(values[k] for k in ("prep_device_s", "gmres_device_s",
                                       "refine_device_s", "advance_device_s"))
    rest = sum(s for ph, row in fold.cross_table().items()
               if ph not in ("prep", "gmres", "refine", "advance")
               for s in row.values())
    assert phases_s + rest == pytest.approx(fold.total_us * 1e-6, rel=1e-9)
    assert values["krylov_device_s"] < values["gmres_device_s"]
    assert values["fiber_solve_device_s"] < values["gmres_device_s"]
    assert 85.0 <= values["phase_attributed_pct"] <= 100.0
    assert values["host_gap_ms_per_step"] == pytest.approx(
        fold.idle_us_inside("skelly/run") * 1e-3)
    # what `report` prints under `run`
    assert set(run.probes["phases"]) >= {"prep", "gmres", "refine", "advance"}
    assert all(len(g) == 3 and g[2] >= 0.1 for g in run.probes["host_gaps"])
    assert run.probes["phase_fold"]["stale_metadata"] is False


def test_seconds_are_per_traced_step_and_clipped_to_the_window(run):
    lo = min(e["ts"] for e in run.phase_fold.events)
    hi = max(e["ts"] + e["dur"] for e in run.phase_fold.events)
    half = Run(window_ns=(lo * 1e3, (lo + hi) / 2 * 1e3))
    phases.probe(half)
    whole = reader("gmres_device_s").read(run)
    assert 0 < reader("gmres_device_s").read(half) < whole
    two = Run()
    two.trace = Trace((0.0, 1e18), steps=2)
    phases.probe(two)
    assert reader("gmres_device_s").read(two) == pytest.approx(whole / 2)


def test_not_seen_reads_nothing_and_stale_reads_nothing(run, monkeypatch):
    assert phases.seconds(run, has=("ring-step",)) is None
    monkeypatch.setattr(run.phase_fold, "stale", True)
    assert all(reader(name).read(run) is None for name in NEW)


def test_a_program_without_the_fold_reads_nothing(monkeypatch):
    """The parent of the PR that added these metrics: the helper finds no
    fold, the probe does nothing, every reader returns None."""
    monkeypatch.setattr(phases, "_profile", None)
    r = Run()
    phases.probe(r)
    assert r.probes == {} and not hasattr(r, "phase_fold")
    assert all(reader(name).read(r) is None for name in NEW)


def test_traced_toy_run_reports_the_new_metrics(cpu_as_chip, toy_root):
    """`run.py --trace 1` end to end on the CPU: the helper finds the
    window's dump by itself and the line holds the new metrics. (Held to
    the CPU the solve is full precision: no `refine`, and the toy cell
    list leaves `shell_device_s` to the walkthrough's.)"""
    root, _ = toy_root
    args = argparse.Namespace(workload="free_fibers_toy.run", seed=7,
                              seconds=1.0, trace=1)
    res = cpu_as_chip.run_cell(args, root=root)
    assert res["correct"] is True
    got = set(res["metrics"])
    assert {"prep_device_s", "gmres_device_s", "advance_device_s",
            "krylov_device_s", "pair_device_s", "fiber_solve_device_s",
            "phase_attributed_pct", "host_gap_ms_per_step"} <= got
    assert "refine_device_s" not in got and "shell_device_s" not in got
    assert res["run"]["probes"]["phases"]["gmres"]
    assert res["run"]["probes"]["host_gaps"]
