"""The trace reduction on a small recorded TPU v5e trace kept beside it:
`data/pair_tile_probes_v5e.xplane.pb.gz` is the probe trace of PR 25's
first chip run of `free_fibers_256.run` (62 KB): a warm call and five
`kernels.stokeslet_direct(..., impl="pallas")` calls on 16,384 nodes, each
under a `chipbench_pair_tile_call` span, inside one `chipbench_probes`
span."""

import os

import pytest

import counts
import peaks
import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, "data", "pair_tile_probes_v5e.xplane.pb.gz")


@pytest.fixture(scope="module")
def tr():
    return xplane.summarize(TRACE, window_span="chipbench_probes")


def test_window_and_busy(tr):
    assert list(tr.device_ops) == ["/device:TPU:0"]
    assert tr.window_s == pytest.approx(0.832234844, rel=1e-9)
    ops = tr.device_ops["/device:TPU:0"]
    lo, hi = tr.window_ns
    inside = [(s, e) for s, e, _ in ops if s >= lo and e <= hi]
    # no two ops overlap in this trace: the union is the sum
    assert tr.busy_s() == pytest.approx(sum(e - s for s, e in inside) * 1e-9,
                                        rel=1e-12)
    # six pair-tile calls of 3.17 ms and a few microseconds of casts
    assert tr.busy_s() == pytest.approx(6 * 3.1738e-3, rel=2e-3)
    idle = 100.0 * (1 - tr.busy_s() / tr.window_s)
    assert idle == pytest.approx(97.71, abs=0.01)


def test_idle_gaps_are_the_complement_and_labelled(tr):
    gaps = tr.idle_gaps(100)
    assert sum(s for _, s in gaps) == pytest.approx(
        tr.window_s - tr.busy_s(), rel=1e-9)
    assert gaps[0][0] == "probes" and gaps[0][1] == pytest.approx(0.6121,
                                                                  abs=1e-3)
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    # a gap between two pair-tile calls lies under no inner span but the
    # probes'; one INSIDE a call's span is labelled by that call
    labels = {g[0] for g in gaps}
    assert "probes" in labels and "pair_tile_call" in labels


def test_ops_by_family_and_short_names(tr):
    top = tr.top_ops(3)
    assert top[0][0] == "%stokeslet_pallas custom-call:tpu_custom_call x6"
    assert top[0][1] == pytest.approx(6 * 3.1738e-3, rel=1e-3)
    assert xplane.short_name(
        '%while.2 = (f32[4]{0}, s32[]) while((f32[4]{0}, s32[]) %t), '
        'condition=%c, body=%b') == "%while.2 while"
    assert xplane.family('%fusion.5341 = f32[8]{0} fusion(f32[8]{0} %p), '
                         'kind=kLoop, calls=%f') == "%fusion fusion"


def test_pair_tile_roofline_from_the_recorded_trace(tr):
    """The reader's arithmetic, end to end, on the recording."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "pair_tile_roofline", os.path.join(os.path.dirname(HERE), "metrics",
                                           "pair_tile_roofline.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)

    class Run:
        probes = {"pair_tile": {"n": 16384, "impl": "pallas"}}
        probe_trace = tr
        peaks = peaks.peaks_for("TPU v5 lite")

    share = reader.read(Run)
    times = Run.probes["pair_tile"]["seconds"]
    assert len(times) == 5 and all(3.17e-3 < t < 3.18e-3 for t in times)
    least = counts.stokeslet_flops(16384, 16384) / 197e12     # 40.9 us
    assert share == pytest.approx(100 * least / 3.17554e-3, rel=1e-3)
    assert 1.2 < share < 1.4                                   # per cent


def test_no_device_plane_reads_nothing():
    empty = xplane.TraceSummary(window_ns=(0.0, 1e9))
    assert empty.busy_s() == 0.0 and empty.idle_gaps() == []
    assert empty.top_ops() == []
