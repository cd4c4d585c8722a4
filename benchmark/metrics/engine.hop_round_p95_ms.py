"""95th percentile (nearest rank) of round start to commit returned over
all rounds of a window whose round waits for the host once a level (the
engine hop).  With some 77 rounds it is the fourth longest; a host that
stalls twice in a window moves it by 10 % in two runs of twelve
(``PERF.md``): too far for a bound, so it stands here and the median and
the rate are the cell's end-to-end metrics."""

import math

UNIT, SOURCE, LAYER, MOVES = "ms", "host_clock", "engine", "round_p50_ms"


def read(ev):
    took = sorted(r[3] - r[0] for r in ev["rounds"])
    if len(took) < 20 or not sum(life["hops"][1] for life in ev["lives"]):
        return None
    return 1e3 * took[math.ceil(0.95 * len(took)) - 1]
