"""The byte count of a round and the table of peaks."""
import pytest

import roofline


def test_round_bytes_matches_a_hand_count():
    # n = 10 vertices, m = 30 edges, 2 rows of float64:
    #   edge structure 4 * (30 + 10)   = 160
    #   h and pi_bar r/w 4 * 8 * 10 * 2 = 640
    #   1/out_degree     8 * 10         =  80
    assert roofline.round_bytes(10, 30, 2, 8) == 880
    # one row of float32: 160 + 4 * 4 * 10 + 4 * 10
    assert roofline.round_bytes(10, 30, 1, 4) == 360


def test_round_bytes_grows_with_rows_by_the_state_alone():
    one = roofline.round_bytes(875713, 5105039, 1, 8)
    sixteen = roofline.round_bytes(875713, 5105039, 16, 8)
    assert sixteen - one == 15 * 4 * 8 * 875713


def test_peaks_are_keyed_by_device_kind():
    v5e = roofline.peaks_for("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks_for("cpu")
