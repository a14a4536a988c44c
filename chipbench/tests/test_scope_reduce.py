import json
import os

import pytest

from chipbench import scope_reduce as sr
from chipbench.facts import Facts

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000


def test_innermost_scope_wins():
    assert sr.innermost_scope("jit(grow)/jit(main)/lgbm.endgame/while/body/"
                              "lgbm.endgame.select/while/body/add") == "lgbm.endgame.select"
    assert sr.innermost_scope("jit(grow)/lgbm.wave.scan/vmap(jit(cumsum))/cumsum") == \
        "lgbm.wave.scan"
    assert sr.innermost_scope("jit(exp)/exp") is None
    assert sr.innermost_scope("") is None and sr.innermost_scope(None) is None
    # every scope the parts read belongs to exactly one part
    assert len(sr.PART_OF_SCOPE) == sum(len(v) for v in sr.PARTS.values())


EVENTS = [
    # one wave inside a loop on device 0: the loop is a container and counts for nothing itself
    (0, "%while.4 = (s32[255]) while(s32[255] %t), body=%b", "lgbm.wave.commit", 0, 100 * MS),
    (0, "%fusion.1 = s8[8] fusion(f32[8] %g)", "lgbm.quantize", 0, 5 * MS),
    (0, "%concatenate.2 = u8[42,8] concatenate(u8[1,8] %a)", "lgbm.wave.row_update", 5 * MS, 25 * MS),
    (0, "%lgbm_wave_row_update_dma_w42.3 = s32[8] custom-call(u8[42,8] %x)",
     "lgbm.wave.row_update", 25 * MS, 30 * MS),
    (0, "%pad.5 = u8[96,8] pad(u8[67,8] %x)", "lgbm.wave.hist", 30 * MS, 34 * MS),
    # the histogram kernel is in a scope, and in no part
    (0, "%lgbm_hist_leaves_q8_dma_f96.6 = s32[8,128] custom-call(u8[96,8] %pad.5)",
     "lgbm.wave.hist", 34 * MS, 74 * MS),
    (0, "%fusion.7 = f32[84,67,256,3] fusion(s32[8,128] %h)", "lgbm.wave.scan", 74 * MS, 84 * MS),
    (0, "%scatter.8 = f32[255,3] scatter(f32[255,3] %s)", "lgbm.wave.commit", 84 * MS, 86 * MS),
    # 86..100 ms: inside the loop, covered by no operation: busy, and unscoped
    (0, "%lgbm_hist_single_dma_f8.9 = f32[8,8] custom-call(u8[8,8] %r)", "lgbm.renew",
     100 * MS, 104 * MS),
    (0, "%fusion.10 = f32[255] fusion(f32[8,8] %g)", "lgbm.renew", 104 * MS, 105 * MS),
    (0, "%gather_add_fusion = f32[8] fusion(f32[8] %score)", "lgbm.score_update", 105 * MS, 106 * MS),
    # an eager operation (its own module: no scope), and a scope no part knows
    (0, "%exp.1 = f32[8] exponential(f32[8] %x)", None, 110 * MS, 113 * MS),
    (0, "%fusion.11 = f32[8] fusion(f32[8] %x)", "lgbm.not_in_the_table", 113 * MS, 114 * MS),
]


def test_the_six_parts_partition_busy_minus_the_histogram_kernels():
    parts = sr.parts_ns(EVENTS)
    assert parts == {"grad_quant": 5 * MS, "row_update": 25 * MS, "hist_glue": 4 * MS,
                     "split_scan": 12 * MS, "score_renew": 2 * MS, "unscoped": 18 * MS}
    busy = 106 * MS + 4 * MS                       # [0, 106] and [110, 114]
    kernels = 40 * MS + 4 * MS
    assert sum(parts.values()) == busy - kernels
    # a second device is averaged in, as trace_reduce.busy_ns does
    two = EVENTS + [(1, "%fusion.1 = s8[8] fusion(f32[8] %g)", "lgbm.quantize", 0, 3 * MS)]
    assert sr.parts_ns(two)["grad_quant"] == 4 * MS
    assert sr.parts_ns(two)["unscoped"] == 9 * MS


def test_an_unnamed_operation_takes_the_scope_its_neighbours_share():
    """The compiler's own copies carry no op_name.  Between two operations of
    one scope they are that scope's; between two trees (one's last phase,
    the next one's first) the eager operations stay unnamed."""
    ev = [
        (0, "%while.1 = (s32[]) while(s32[] %t), body=%b", None, 0, 60 * MS),       # a loop: never named
        (0, "%fusion.1 = u8[42,8] fusion(u8[67,8] %x)", "lgbm.wave.row_update", 0, 10 * MS),
        (0, "%copy.2 = u8[6,8,8,8] copy(u8[6,8,8,8] %bitcast)", None, 10 * MS, 15 * MS),
        (0, "%dynamic-update-slice.3 = s32[1,8,8] dynamic-update-slice(s32[1,8,8] %g)", None,
         15 * MS, 16 * MS),
        (0, "%lgbm_wave_row_update_dma_w42.4 = s32[8] custom-call(u8[42,8] %x)",
         "lgbm.wave.row_update", 16 * MS, 20 * MS),
        (0, "%copy.5 = s32[1,1,8,8] copy(s32[1,1,8,8] %c)", None, 20 * MS, 21 * MS),  # row_update | hist
        (0, "%pad.6 = u8[96,8] pad(u8[67,8] %x)", "lgbm.wave.hist", 21 * MS, 25 * MS),
        (0, "%gather_fusion = f32[8] fusion(f32[255] %lv, s32[8] %rl)", "lgbm.score_update",
         60 * MS, 62 * MS),
        (0, "%exp.1 = f32[8] exponential(f32[8] %x)", None, 63 * MS, 64 * MS),       # score | quantize
        (0, "%fusion.7 = s8[8] fusion(f32[8] %g)", "lgbm.quantize", 65 * MS, 66 * MS),
        (0, "%copy.8 = u8[8] copy(u8[8] %x)", None, 66 * MS, 67 * MS),               # nothing after it
        (1, "%copy.9 = u8[8] copy(u8[8] %x)", None, 5 * MS, 6 * MS),                 # another device
    ]
    got = {name.split(" = ")[0]: scope for _, name, scope, _, _ in sr.fill_between(ev)}
    assert got == {"%while.1": None, "%fusion.1": "lgbm.wave.row_update",
                   "%copy.2": "lgbm.wave.row_update", "%dynamic-update-slice.3": "lgbm.wave.row_update",
                   "%lgbm_wave_row_update_dma_w42.4": "lgbm.wave.row_update", "%copy.5": None,
                   "%pad.6": "lgbm.wave.hist", "%gather_fusion": "lgbm.score_update",
                   "%exp.1": None, "%fusion.7": "lgbm.quantize", "%copy.8": None, "%copy.9": None}
    assert [e[:2] + e[3:] for e in sr.fill_between(ev)] == [e[:2] + e[3:] for e in ev]
    assert sr.fill_between([]) == []


def test_the_wire_reader_keeps_the_event_metadata_s_stat():
    """``tf_op`` sits in an operation's event METADATA, which ProfileData does
    not show: the file is read as protobuf wire format."""
    from jax.profiler import ProfileData
    text = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 5000000 duration_ps: 2000999 }
    events { metadata_id: 2 offset_ps: 8000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 9000000 duration_ps: 500000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 0 duration_ps: 20000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.7 = f32[8] fusion(f32[8] %x)"
    stats { metadata_id: 1 str_value: "loop fusion" }
    stats { metadata_id: 2 str_value: "jit(grow)/lgbm.endgame/while/body/lgbm.endgame.select/add:" } } }
  event_metadata { key: 2 value { id: 2 name: "%copy.9 = u8[8] copy(u8[8] %x)"
    stats { metadata_id: 1 str_value: "data formatting" } } }
  event_metadata { key: 3 value { id: 3 name: "%mul.1 = f32[8] multiply(f32[8] %x, f32[8] %y)"
    stats { metadata_id: 2 str_value: "jit(multiply)/mul:" } } }
  event_metadata { key: 4 value { id: 4 name: "jit_grow(123)" } }
  stat_metadata { key: 1 value { id: 1 name: "hlo_category" } }
  stat_metadata { key: 2 value { id: 2 name: "tf_op" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 7 name: "python3" timestamp_ns: 900
    events { metadata_id: 1 offset_ps: 50000 duration_ps: 30000000 } }
  event_metadata { key: 1 value { id: 1 name: "chipbench.window" } } }
"""
    events, window = sr.read_scoped_events(ProfileData.text_proto_to_serialized_xspace(text))
    assert window == (950, 30950)
    assert events == [(0, "%fusion.7 = f32[8] fusion(f32[8] %x)", "lgbm.endgame.select", 6000, 8000),
                      (0, "%copy.9 = u8[8] copy(u8[8] %x)", None, 9000, 10000),
                      (0, "%mul.1 = f32[8] multiply(f32[8] %x, f32[8] %y)", None, 10000, 10500)]


def test_a_program_without_scopes_gives_no_parts():
    bare = [(dev, name, None, s, e) for dev, name, _, s, e in EVENTS]
    assert sr.parts_ns(bare) == {}
    assert sr.parts_ns([]) == {}
    facts = Facts({}, {}, {}, {"traced_trees": 3}, trace=None)
    assert sr.part_ms_per_tree(facts, "row_update", __file__) is None


def test_clip_cuts_events_to_the_window():
    cut = sr.clip(EVENTS, 20 * MS, 40 * MS)
    assert [(s, e) for _, _, _, s, e in cut] == [(20 * MS, 40 * MS), (20 * MS, 25 * MS),
                                                 (25 * MS, 30 * MS), (30 * MS, 34 * MS),
                                                 (34 * MS, 40 * MS)]


def test_readers_find_the_run_s_trace_from_their_own_file(tmp_path, monkeypatch):
    """``<root>/<path>/layer_metrics/x.py`` -> ``<root>/.chipbench_trace``: the
    newest trace there, and only if its window is the run's."""
    reader = tmp_path / "chipbench" / "layer_metrics" / "x.py"
    reader.parent.mkdir(parents=True)
    assert sr.newest_xplane(str(tmp_path)) is None
    old = tmp_path / ".chipbench_trace" / "a.train" / "plugins" / "profile" / "1" / "h.xplane.pb"
    new = tmp_path / ".chipbench_trace" / "b.train" / "plugins" / "profile" / "2" / "h.xplane.pb"
    for i, p in enumerate((old, new)):
        p.parent.mkdir(parents=True)
        p.write_bytes(b"")
        os.utime(p, (1000 + i, 1000 + i))
    assert sr.newest_xplane(str(tmp_path)) == str(new)

    class Trace:
        window = (0, 120 * MS)
    monkeypatch.setattr(sr, "read_scoped_events", lambda xspace: (EVENTS, (0, 120 * MS)))
    facts = Facts({}, {}, {}, {"traced_trees": 2}, trace=Trace())
    assert sr.part_ms_per_tree(facts, "row_update", str(reader)) == pytest.approx(12.5)
    assert sr.part_ms_per_tree(facts, sr.UNSCOPED, str(reader)) == pytest.approx(9.0)
    Trace.window = (5000, 120 * MS)       # another run's trace: nothing to read
    facts = Facts({}, {}, {}, {"traced_trees": 2}, trace=Trace())
    assert sr.part_ms_per_tree(facts, "row_update", str(reader)) is None


RECORDED = os.path.join(HERE, "data", "scoped_q8.json")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded scoped trace")
def test_the_recorded_scoped_chip_trace_reduces_to_its_recorded_parts():
    with open(RECORDED) as fh:
        doc = json.load(fh)
    assert doc["stat"] == sr.SCOPE_STAT           # the stat that carries op_name on the chip
    events = sr.fill_between([tuple(ev) for ev in doc["events"]])
    parts = sr.parts_ns(events)
    assert parts == pytest.approx(doc["parts_ns"])
    assert set(parts) == set(sr.PARTS) | {sr.UNSCOPED}
    assert parts["row_update"] > 0 and parts["hist_glue"] > 0
    assert 0 <= parts[sr.UNSCOPED] < 0.05 * sum(parts.values())
    scopes = {scope for _, _, scope, _, _ in events if scope}
    assert scopes <= set(sr.PART_OF_SCOPE), scopes - set(sr.PART_OF_SCOPE)
