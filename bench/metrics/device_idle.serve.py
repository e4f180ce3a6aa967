"""Share of the traced window in which no operation ran on the chip (%).

Layer: device.  From the profiler trace: 1 - (union of the intervals of
the ``XLA Ops`` events) / the traced window, averaged over the chips.
Moves ``tpot_p95_ms``.
"""

from benchlib import xplane


def read(rec):
    w = rec["window_s"]
    if not w or not rec["trace"].devices:
        return None
    return 100.0 * (1.0 - xplane.busy_s(rec["trace"]) / w)
