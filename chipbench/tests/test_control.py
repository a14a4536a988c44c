"""The control of each precision, at a size a test run can hold: the nearest
precision below the configuration's has to read worse than the program as the
configuration states it, on the number that is there to catch it, and the
planted fault (half of the batch left out) has to show in the leaf counts.
The limits themselves are set from readings on the chip (PERF.md)."""

import json

import pytest

from chipbench import run
from chipbench.tests import helpers
from chipbench.tools import readings


@pytest.mark.parametrize("quantized, number", [(True, "split_gain_median_gap"), (False, "leaf_value_gap")],
                         ids=["int4_for_int8", "bf16_for_bf16_hi_lo"])
def test_the_control_reads_worse_than_the_program(tmp_path, capsys, monkeypatch, quantized, number):
    monkeypatch.setattr(run, "find_device", lambda chips: dict(helpers.CPU_DEVICE))
    root = helpers.make_root(str(tmp_path), quantized=quantized)
    seeds = [2**31 + 21, 22, 23]
    assert readings.main(["--seeds", ",".join(map(str, seeds)), "--configs", "tiny",
                          "--control-seeds", "3", "--trees", "4"], root=root) == 0
    recs = [json.loads(ln) for ln in capsys.readouterr().out.strip().splitlines()]
    by = lambda kind: [r["numbers"] for r in recs if r["kind"] == kind]
    assert len(by("program")) == 3 and len(by("control")) == 3 and len(by("fault_half_batch")) == 3
    lower = max(n[number] for n in by("program"))
    upper = min(n[number] for n in by("control"))
    assert upper >= 3 * lower, (lower, upper)
    assert all(n["leaf_count_diff"] == 0 for n in by("program"))
    assert all(n["leaf_count_diff"] > 1000 for n in by("fault_half_batch"))
