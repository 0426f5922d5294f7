"""The benchmark's arithmetic: operations and bytes from shapes, and the
end-to-end metrics from host-clock records."""
import pytest

import arith
import harness


def yi():
    return harness.load_config("yi-6b")


def test_packed_groups_and_int8_bytes_of_the_yi_layout():
    # the f32 state a device trigger packs: params, Adam m and v
    assert arith.packed_groups(yi()) == 703_524
    assert arith.int8_encode_bytes(703_524) == 703_524 * (1024 * 9 + 4)
    assert abs(arith.int8_encode_bytes(703_524) / 1e9 - 6.48) < 0.01


def test_train_flops_of_the_yi_cut():
    cfg = yi()
    # 6 x (one layer's projections + the 8000-row head) + attention
    layer = 4096 * 4096 * 2 + 2 * 4096 * 512 + 3 * 4096 * 11008
    per_token = 6 * (layer + 4096 * 8000) + 12 * 4096 * 2048
    assert arith.train_flops_per_token(cfg, 2048) == per_token
    assert arith.train_flops_per_step(cfg) == per_token * 4 * 2048
    assert abs(arith.train_flops_per_step(cfg) / 1.10e13 - 1) < 0.01


def test_goodput_counts_rolled_back_steps_once():
    # cursor after each step: 4, 8, 12, then a restore to 4 and two steps
    boundaries = [(0.0, 4), (1.0, 8), (2.0, 12), (5.0, 8), (6.0, 12)]
    assert arith.goodput(boundaries, 10) == pytest.approx((12 - 4) * 10 / 6)
    assert arith.goodput(boundaries[:1], 10) is None


def test_resume_over_several_kills():
    assert arith.resume_s([(0.0, 2.0), (10.0, 14.0), (20.0, 23.0)]) == 3.0
    assert arith.resume_s([]) is None


def test_kept_offsets_after_restores():
    log = [("step", 0, 4), ("step", 4, 4), ("step", 8, 4), ("restore", 4),
           ("step", 4, 4), ("step", 8, 4), ("step", 12, 4)]
    assert arith.kept_offsets(log) == [(0, 4), (4, 4), (8, 4), (12, 4)]


def test_peaks_refuse_an_unknown_device():
    assert arith.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        arith.peaks("cpu")

