import pytest

from chipbench import roofline

V5E = roofline.load_peaks("TPU v5 lite")


def test_peaks_are_the_published_ones_and_an_unknown_device_is_an_error():
    assert V5E["bf16_flops_per_s"] == 197e12 and V5E["int8_ops_per_s"] == 393e12
    assert V5E["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.load_peaks("TPU v9 imaginary")


def test_pass_work_on_hand_computed_shapes():
    # 1000 rows x 10 features, 255 bins, 255 leaves
    # int8: 10,000 bin bytes + 1000 x (2 gradient bytes + 1 leaf byte) = 13,000
    assert roofline.pass_bytes(1000, 10, 255, 255, "int8") == 13_000
    # bf16 hi/lo reads the f32 pair: 10,000 + 1000 x (8 + 1) = 19,000
    assert roofline.pass_bytes(1000, 10, 255, 255, "bf16_hi_lo") == 19_000
    # 3 channels per (row, feature)
    assert roofline.pass_ops(1000, 10) == 30_000
    # wider codes double the bin bytes; more leaves widen the id
    assert roofline.pass_bytes(1000, 10, 1023, 255, "int8") == 23_000
    assert roofline.pass_bytes(1000, 10, 255, 1023, "int8") == 14_000


def test_the_floor_is_bound_by_bytes_at_these_shapes():
    f = roofline.pass_floor(21_250_000, 67, 255, 255, "int8", V5E)
    want = (21_250_000 * 67 + 21_250_000 * 3) / 819e9
    assert f["bound_by"] == "hbm_bytes" and f["seconds"] == pytest.approx(want)
    ops = 21_250_000 * 67 * 3 / 393e12
    assert ops < want
    # a chip with slow arithmetic would be bound by it
    slow = dict(V5E, int8_ops_per_s=1e9)
    assert roofline.pass_floor(1000, 10, 255, 255, "int8", slow)["bound_by"] == "int8_ops_per_s"
