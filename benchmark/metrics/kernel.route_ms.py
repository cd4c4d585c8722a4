"""Device time a traced round of the routing passes: the operations that
move rows one level down without building a histogram — the leaves' pass,
and a pass a level where the matrix is wider than one tile of codes and
routing has left the histogram's sweep.  Found in the trace by the name
pattern below; ``None`` where none ran."""

import re

UNIT, SOURCE, LAYER, MOVES = "ms", "device_trace", "kernels", "round_p50_ms"

#: ``ops/boost.py`` names them for the level they route to
#: ("route_level_d6.1", seen on the v5e, PR 24; "route_margin_d<d>")
OPS = [r"^route_"]


def read(ev):
    t = ev.get("trace")
    if not t or not t["rounds"]:
        return None
    took = sum(s for name, (_, s) in t["ops"].items()
               if any(re.search(p, name) for p in OPS))
    return 1e3 * took / t["rounds"] if took > 0 else None
