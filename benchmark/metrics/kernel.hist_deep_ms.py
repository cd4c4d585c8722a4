"""Device time a traced round of the deep histogram levels: those whose
stacked gradient matrix (hi and lo plane, g and h, a node: ``4 * 2**d``
rows) passes one 128-row MXU tile, level 6 on.  The operations that do
them are found in the trace by the name patterns below; ``None`` where
none ran (a configuration of depth 6 or less, a program without them)."""

import re

UNIT, SOURCE, LAYER, MOVES = "ms", "device_trace", "kernels", "round_p50_ms"

#: the first level whose ``4 * 2**d`` rows pass the MXU's 128
FIRST_DEEP = 6
#: ``ops/boost.py`` names a level kernel for its level ("hist_level_d6.1",
#: seen on the v5e, PR 27); a step that derives part of a level's histogram
#: on the device gets a name and a pattern of its own here
OPS = [r"^hist_level_d(\d+)"]


def read(ev):
    t = ev.get("trace")
    if not t or not t["rounds"]:
        return None
    found = ((re.search(p, name), s) for name, (_, s) in t["ops"].items()
             for p in OPS)
    took = sum(s for m, s in found if m and int(m.group(1)) >= FIRST_DEEP)
    return 1e3 * took / t["rounds"] if took > 0 else None
