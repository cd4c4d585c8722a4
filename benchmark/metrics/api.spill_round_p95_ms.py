"""95th percentile (nearest rank) of round start to commit returned over
all rounds of a window whose commits are spilled to disk and fsynced.  With
some fifty rounds it is the third longest, and it spreads by 2 to 4 % from
run to run (``PERF.md``): too far for a bound, so it stands here and the
median is the cell's end-to-end metric."""

import math

UNIT, SOURCE, LAYER, MOVES = "ms", "host_clock", "API", "round_p50_ms"


def read(ev):
    took = sorted(r[3] - r[0] for r in ev["rounds"])
    if len(took) < 20 or not ev["traffic"].get("spill"):
        return None
    return 1e3 * took[math.ceil(0.95 * len(took)) - 1]
