from chipbench import program_record as pr
from chipbench.facts import Facts


def facts_with(snapshot, passes):
    facts = Facts({}, {}, {}, {"hist_passes": passes})
    facts.program_snapshot = snapshot
    return facts


ROWS = [{"hist_passes": 7, "endgame_passes": 2, "ramp_committed": 41},     # two warm-up trees
        {"hist_passes": 7, "endgame_passes": 2, "ramp_committed": 41},
        {"hist_passes": 7, "endgame_passes": 2, "ramp_committed": 40},     # the window's three
        {"hist_passes": 9, "endgame_passes": 4, "ramp_committed": 40},
        {"hist_passes": 8, "endgame_passes": 3, "ramp_committed": 37}]


def test_window_mean_reads_the_window_s_rows_and_checks_them_against_the_driver_s():
    snap = {"trees": ROWS, "setup_seconds": {"bin_find": 14.2}}
    assert pr.window_mean(facts_with(snap, [7, 9, 8]), "endgame_passes") == 3.0
    assert pr.window_mean(facts_with(snap, [7, 9, 8]), "ramp_committed") == 39.0
    assert pr.window_mean(facts_with(snap, [7, 9, 9]), "endgame_passes") is None   # not these rows
    assert pr.window_mean(facts_with(snap, []), "endgame_passes") is None
    assert pr.setup_seconds(facts_with(snap, []), "bin_find") == 14.2
    assert pr.setup_seconds(facts_with(snap, []), "layout") is None


def test_a_program_without_the_keys_gives_nothing():
    """The parent of the PR that added them: rows without pass kinds, a
    snapshot without ``setup_seconds``, or no record at all."""
    old = {"trees": [{"hist_passes": 7}, {"hist_passes": 8}]}
    assert pr.window_mean(facts_with(old, [7, 8]), "endgame_passes") is None
    assert pr.setup_seconds(facts_with(old, [7, 8]), "bin_find") is None
    assert pr.window_mean(facts_with(None, [7, 8]), "endgame_passes") is None
    assert pr.setup_seconds(facts_with(None, []), "bin_find") is None
