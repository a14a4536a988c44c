"""What a per-layer metric's reader is given.

``counters``: numbers the driver counted or clocked itself, or read from the
program's counters.  ``trace``: a :class:`chipbench.trace_reduce.Reduced`, or
None in an untraced run.  ``config``: the configuration's file.  ``peaks``:
this device's row of ``peaks.json``.  A reader returns None where it finds
nothing to read.
"""

from __future__ import annotations


class Facts:
    def __init__(self, config: dict, device: dict, peaks: dict, counters: dict, trace=None):
        self.config, self.device, self.peaks = config, device, peaks
        self.counters, self.trace = counters, trace

    def traced_kernel_s(self, needle: str):
        """Device seconds, in the traced window, of the kernels whose event
        names contain ``needle``; None where there is no trace or no such
        event."""
        if self.trace is None:
            return None
        s = self.trace.matching_s(needle)
        return s if s > 0 else None

    def traced_passes(self):
        """Full-data passes the program counted in the traced trees, or None."""
        k = self.counters.get("traced_trees", 0)
        passes = self.counters.get("hist_passes", [])[:k]
        if not passes or min(passes) <= 0:
            return None
        return sum(passes)
