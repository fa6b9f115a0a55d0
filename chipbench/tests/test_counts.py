"""`counts.py` against hand-worked numbers, and the table of peaks."""

import pytest

import counts
import peaks


def test_pairs_flops_bytes_at_the_cell_size():
    n = 256 * 64                      # 16,384 nodes
    assert counts.stokeslet_pairs(n, n) == 268_435_456
    assert counts.stokeslet_flops(n, n) == 8_053_063_680      # x 30
    assert counts.stokeslet_bytes(n, n) == 4 * 12 * 16_384 == 786_432


def test_least_time_is_compute_bound_at_the_cell_size():
    pk = peaks.peaks_for("TPU v5 lite")
    n = 16_384
    t, side = counts.least_seconds(counts.stokeslet_flops(n, n),
                                   counts.stokeslet_bytes(n, n), pk)
    assert side == "compute"
    assert t == pytest.approx(8_053_063_680 / 197e12)         # 40.9 us
    # a thin problem is memory bound: 1 source, 1e6 targets
    t, side = counts.least_seconds(counts.stokeslet_flops(1, 10**6),
                                   counts.stokeslet_bytes(1, 10**6), pk)
    assert side == "memory"
    assert t == pytest.approx(4 * 6 * (10**6 + 1) / 819e9)


def test_step_pair_flops():
    # 27 iterations and 2 refinement residuals: 29 all-pairs sums
    assert counts.step_pair_flops(16_384, 27, 2) == 29 * 8_053_063_680


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v99")
    with pytest.raises(KeyError):
        peaks.peaks_for("_source")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
