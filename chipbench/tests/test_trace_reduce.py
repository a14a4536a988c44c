import os

import pytest

from chipbench import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000


def test_merged_intervals_and_busy_union():
    assert tr.merged_intervals([(5, 7), (0, 2), (1, 3), (7, 9), (20, 21)]) == \
        [(0, 3), (5, 9), (20, 21)]
    ev = [(0, "while", 0, 10 * MS), (0, "fusion.1", 1 * MS, 3 * MS),
          (0, "fusion.2", 12 * MS, 14 * MS), (1, "fusion.1", 0, 4 * MS)]
    # device 0: [0, 10] and [12, 14] = 12 ms; device 1: 4 ms; the mean over devices
    assert tr.busy_ns(ev) == 8 * MS
    assert tr.busy_ns([]) == 0.0


def test_kernel_time_is_a_union_by_label():
    ev = [(0, "%lgbm_hist_leaves_q8_dma_f72.1 = s32[8,128] custom-call(u8[72,4096] %pad.1)", 0, 5 * MS),
          (0, "%lgbm_hist_single_dma_f8.2 = f32[8,8] custom-call(u8[8,4096] %pad.2)", 6 * MS, 8 * MS),
          (0, "%lgbm_wave_row_update_dma.3 = s32[4096] custom-call(u8[42,4096] %x)", 8 * MS, 9 * MS),
          (0, "%while.4 = (s32[255]) while(s32[255] %tuple.1), body=%region_1", 0, 9 * MS),
          (0, "%lgbm_hist_leaves_q8_dma_f72.25 = s32[8,128] custom-call(u8[72,4096] %pad.1)", 9 * MS, 12 * MS),
          (0, "%lgbm_hist_leaves_q8_dma_f72.26 = s32[8,128] custom-call(u8[72,4096] %pad.1)", 12 * MS, 15 * MS)]
    assert tr.matching_ns(ev, "lgbm_hist_") == 13 * MS
    assert tr.matching_ns(ev, "lgbm_wave_row_update") == 1 * MS
    assert tr.matching_ns(ev, "no_such_kernel") == 0
    # the loop is a container and is left out; one kernel's numbered calls add up
    assert tr.top_ops(ev, 2) == [["lgbm_hist_leaves_q8_dma_f72", 0.011],
                                 ["lgbm_hist_single_dma_f8", 0.002]]
    assert tr.short_name("%fusion = f32[8] fusion(f32[8] %x)") == "fusion"


def test_window_clip_and_idle_gaps_labelled_by_the_harness_spans():
    spans = [("chipbench.window", 0, 100 * MS), ("chipbench.update", 0, 30 * MS),
             ("chipbench.update", 30 * MS, 60 * MS), ("chipbench.force", 60 * MS, 100 * MS)]
    ev = [(0, "a", -5 * MS, 10 * MS),          # starts before the window: clipped
          (0, "b", 40 * MS, 55 * MS),
          (0, "c", 70 * MS, 120 * MS)]         # ends after it: clipped
    red = tr.Reduced(ev, spans)
    assert red.window_s == pytest.approx(0.1)
    assert red.busy_s == pytest.approx(0.055)                 # 10 + 15 + 30 ms
    gaps = red.breakdown()["idle_gaps"]
    assert gaps[0] == ["update", pytest.approx(0.030)]        # 10..40 ms, middle at 25 ms
    assert gaps[1] == ["force", pytest.approx(0.015)]         # 55..70 ms, middle in the force
    assert red.breakdown()["device_ops"][0][0] == "c"
    with pytest.raises(ValueError):
        tr.Reduced(ev, [("chipbench.update", 0, 1)])


RECORDED = os.path.join(HERE, "data", "recorded_q8.textproto")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_the_recorded_chip_trace_reduces_to_the_numbers_worked_out_by_hand():
    from jax.profiler import ProfileData
    with open(RECORDED) as fh:
        prof = ProfileData.from_text_proto(fh.read())
    red = tr.Reduced(*tr.read_events(prof))
    with open(os.path.join(HERE, "data", "recorded_q8.expected.json")) as fh:
        want = __import__("json").load(fh)
    assert red.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert red.busy_s == pytest.approx(want["busy_s"], rel=1e-6)
    assert red.matching_s("lgbm_hist_") == pytest.approx(want["hist_kernel_s"], rel=1e-6)
    assert 0 < red.matching_s("lgbm_hist_") < red.busy_s <= red.window_s
    assert len(red.breakdown()["device_ops"]) == 10
