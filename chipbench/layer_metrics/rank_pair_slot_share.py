"""The pairs the reference's lambdarank loop visits (over all queries, the
sorted pairs (i, j), i < j, i below the truncation level) over the pair
slots the program's objective evaluates an iteration, as its ``TrainRecord``
states its query layout (``snapshot()["objective"]``: ``pairs_counted``,
``pair_slots``).  1 would be no padded or untruncated slot at all.  None
where the program states no layout (the parent of the PR that added it)."""


def read(facts):
    layout = facts.counters.get("rank_layout") or {}
    if not layout.get("pair_slots") or "pairs_counted" not in layout:
        return None
    return layout["pairs_counted"] / layout["pair_slots"]
