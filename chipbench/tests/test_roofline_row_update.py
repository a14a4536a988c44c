"""``chipbench/roofline_row_update.py``: the least bytes of one wave's routing."""

import pytest

from chipbench import roofline, roofline_row_update as ru

PEAKS = roofline.load_peaks("TPU v5 lite")


def test_four_bytes_a_row_at_the_new_cells_shape():
    rows = 45_840_617
    assert ru.row_update_bytes(rows, 255, 255) == 4 * rows
    assert ru.row_update_floor(rows, 255, 255, PEAKS) == pytest.approx(4 * rows / 819e9)
    assert ru.row_update_floor(rows, 255, 255, PEAKS) == pytest.approx(0.22389e-3, rel=1e-4)


@pytest.mark.parametrize("max_bin, leaves, per_row", [
    (255, 255, 4), (256, 256, 4), (1023, 255, 5), (255, 1000, 6), (1023, 1000, 7)])
def test_wider_codes_and_leaf_ids_cost_their_bytes(max_bin, leaves, per_row):
    assert ru.row_update_bytes(1000, max_bin, leaves) == per_row * 1000


def test_the_reader_divides_by_the_calls_of_one_device():
    from chipbench import manifest as mf
    from chipbench.facts import Facts
    from chipbench.tests import helpers

    class Trace:
        events = [(0, "lgbm_wave_row_update_dma_cat_w42", 0, 10_000_000),
                  (0, "lgbm_wave_row_update_dma_cat_w42", 20_000_000, 30_000_000),
                  (0, "lgbm_hist_leaves_q8", 30_000_000, 40_000_000),
                  (1, "lgbm_wave_row_update_dma_cat_w42", 0, 10_000_000),
                  (1, "lgbm_wave_row_update_dma_cat_w42", 20_000_000, 30_000_000)]

        def matching_s(self, needle):
            return 0.020

    reader = mf.load_module(f"{helpers.REPO}/chipbench/layer_metrics/row_update_kernel_roofline.py")
    cfg = {"params": {"max_bin": 255, "num_leaves": 255}, "data": {"rows": 45_840_617}}
    facts = Facts(cfg, helpers.CPU_DEVICE, PEAKS, {}, Trace())
    assert reader.read(facts) == pytest.approx(100 * 0.22389e-3 / 0.010, rel=1e-4)
    assert reader.read(Facts(cfg, helpers.CPU_DEVICE, PEAKS, {}, None)) is None
