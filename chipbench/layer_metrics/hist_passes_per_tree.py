"""Full-data histogram passes per tree, mean over the window's trees: the
program's own count (``TrainRecord`` ``hist_passes``), which repeats exactly.
0 means the grower does not count passes: nothing to read."""


def read(facts):
    passes = [p for p in facts.counters.get("hist_passes", []) if p > 0]
    if not passes:
        return None
    return sum(passes) / len(passes)
