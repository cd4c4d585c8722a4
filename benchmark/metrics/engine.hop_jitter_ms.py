"""The largest, over a round's levels, of the range across the traced
rounds of that level's hop, first device operation's start to last one's
end (T3 - T0, ``harness/hops.py``): whether one hop of one level runs in
two modes while the kernels repeat.  ``None`` with fewer than two traced
rounds or no hop paired with a device operation."""

from harness import hops

UNIT, SOURCE, LAYER, MOVES = "ms", "device_trace", "engine", "round_p50_ms"


def read(ev):
    return hops.jitter_ms(hops.table(ev))
