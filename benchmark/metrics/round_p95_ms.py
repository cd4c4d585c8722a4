"""95th percentile of round start to commit returned, over ALL rounds of
the window (nearest rank; the sample count is printed beside it)."""

import math

UNIT, SOURCE = "ms", "host_clock"


def read(ev):
    took = sorted(r[3] - r[0] for r in ev["rounds"])
    if len(took) < 20:
        return None
    return 1e3 * took[math.ceil(0.95 * len(took)) - 1]
