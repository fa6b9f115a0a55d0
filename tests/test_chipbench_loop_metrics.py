"""The benchmark's five readers of the run loop's step record
(`chipbench/metrics/{loop_host_ms_per_step, fetch_info_ms_per_step,
write_frame_ms_per_frame, step_max_over_p50, slow_steps_in_window}.py`,
PR 38), loaded by path as `chipbench/run.py` loads them, on rows made by
hand: each reads EVERY row of the window, and each returns None (the result
line then leaves the metric out) on the rows of a program that keeps no
record. Beside them: every `per_layer` entry of `BENCHMARK.json` finds its
reader under `chipbench/metrics/`.
"""

import importlib.util
import json
import os
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
METRICS = os.path.join(ROOT, "chipbench", "metrics")
READERS = ("loop_host_ms_per_step", "fetch_info_ms_per_step",
           "write_frame_ms_per_frame", "step_max_over_p50",
           "slow_steps_in_window")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metrics_{name}", os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def row(loop_ms, wait, dispatch=2.0, fetch=5.0, frame=None, slow=None):
    """A metrics row as `System.run` writes it, the fields the readers
    take; ``frame`` = (encode, io) milliseconds where the step wrote one."""
    host_ms = {"dispatch": dispatch, "wait": wait, "fetch_info": fetch,
               "clock_read": 0.5}
    if frame is not None:
        host_ms["write_frame/encode"], host_ms["write_frame/io"] = frame
    host_ms["other"] = loop_ms - sum(host_ms.values())
    return {"step": 0, "iters": 12, "wall_s": (dispatch + wait) / 1e3,
            "loop_s": loop_ms / 1e3, "host_ms": host_ms, "slow": slow}


def window(rows):
    return types.SimpleNamespace(rows=rows)


#: five steps; the serial host part (loop - dispatch - wait) reads
#: 8, 20, 9, 10, 7 ms: median 9
STEADY = [row(400.0, 390.0), row(412.0, 390.0, frame=(11.0, 1.0)),
          row(401.0, 390.0), row(402.0, 390.0), row(399.0, 390.0)]

#: the same window with a stall in its fourth step's wait
STALL = {"over_p50": 10.25, "in": "wait", "excess_ms": 3700.0,
         "counters": {"majflt": 0}}
STALLED = STEADY[:3] + [row(4100.0, 4090.0, slow=STALL)] + STEADY[4:]

#: what the parent's program writes: no record in the row
PARENT = [{"step": 0, "iters": 12, "wall_s": 0.39, "wall_ms": 390.0}] * 4


@pytest.mark.parametrize("name, rows, expected", [
    ("loop_host_ms_per_step", STEADY, 9.0),
    # a stall inside `wait` is no host time: 8, 20, 9, 8, 7
    ("loop_host_ms_per_step", STALLED, 8.0),
    ("fetch_info_ms_per_step", STEADY, 5.0),
    ("fetch_info_ms_per_step",
     STEADY[:2] + [row(400.0, 390.0, fetch=7.0)] * 3, 7.0),
    ("write_frame_ms_per_frame", STEADY, 12.0),
    ("write_frame_ms_per_frame",
     STEADY + [row(420.0, 390.0, frame=(15.0, 3.0))], 15.0),
    ("write_frame_ms_per_frame", [STEADY[0], STEADY[2]], None),  # no frame
    ("step_max_over_p50", STEADY, 412.0 / 401.0),
    ("step_max_over_p50", STALLED, 4100.0 / 401.0),
    ("slow_steps_in_window", STEADY, 0.0),
    ("slow_steps_in_window", STALLED, 1.0),
])
def test_reader_on_rows_made_by_hand(name, rows, expected):
    value = reader(name)(window(rows))
    if expected is None:
        assert value is None
    else:
        assert value == pytest.approx(expected)


@pytest.mark.parametrize("name", READERS)
def test_reader_is_silent_without_the_record(name):
    """Against the parent's program, and in an empty window: None, which
    `run.report` leaves out of the line; never a zero."""
    assert reader(name)(window(PARENT)) is None
    assert reader(name)(window([])) is None


def test_every_per_layer_entry_has_its_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, m in by_name.items():
        assert os.path.exists(os.path.join(METRICS, name + ".py")), name
    # the record's five: the loop's layer, every cell, no bound of their own
    for name in READERS:
        m = by_name[name]
        assert m["layer"] == "host run loop" and m["moves"] == "step_wall_s"
        assert m["better"] == "lower" and "workloads" not in m
    assert [m["name"] for m in bench["per_layer"][-5:]] == list(READERS)
