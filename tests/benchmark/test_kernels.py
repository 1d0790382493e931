"""The lane block's byte count from its shapes, and the peaks table."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))        # the repo root holds `benchmark`

import pytest

from benchmark import kernels, peaks


def test_lane_block_bytes_at_the_north_star_shape():
    # 1024 lanes x 448 cells: three 4-byte input grids, two i32 per lane,
    # seven 4-byte result rows of the same capacity
    cells = 1024 * 448
    assert kernels.lane_block_bytes(1024, 448) == \
        4 * (3 * cells + 2 * 1024 + 7 * cells)
    assert kernels.lane_block_bytes(8, 16, in_cols=1, out_rows=1) == \
        4 * (128 + 16 + 128)


def test_lane_block_bytes_refuses_empty_shapes():
    with pytest.raises(ValueError):
        kernels.lane_block_bytes(0, 448)


def test_peaks_are_keyed_by_device_kind_and_unknown_is_an_error():
    v5e = peaks.peaks_of("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flop_per_s"] == 197e12 and v5e["hbm_bytes"] == 16e9
    assert "source" in v5e
    with pytest.raises(KeyError):
        peaks.peaks_of("cpu")
