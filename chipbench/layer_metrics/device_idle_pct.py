"""Share of the traced window in which no operation ran on the device."""


def read(facts):
    if facts.trace is None or facts.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - facts.trace.busy_s / facts.trace.window_s)
