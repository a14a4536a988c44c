"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip after the window."""


def read(facts):
    peak = facts.counters.get("memory_peak_bytes", 0)
    return peak / 1e9 if peak > 0 else None
