"""The routing passes' share of their roofline: the least time for the
passes' algorithmic work (``harness/route_work.py``: a row's codes and its
node id in, the node id out; bound by HBM bandwidth) over the summed device
time of the operations that do them.  A pass is a level some routing
operation of the trace is named for; ``None`` where none ran."""

import re

from harness import route_work, work

UNIT, SOURCE, LAYER, MOVES = "%", "device_trace", "kernels", "round_p50_ms"

#: the routing operations with the level they route to
#: ("route_level_d6.1", "route_margin_d8.1")
OPS = [r"^route_[a-z]+_d(\d+)"]


def read(ev):
    t, c = ev.get("trace"), ev["config"]
    if not t or not t["rounds"]:
        return None
    found = [(m.group(1), s) for name, (_, s) in t["ops"].items()
             for m in (re.search(p, name) for p in OPS) if m and s > 0]
    took = sum(s for _, s in found)
    if took <= 0:
        return None
    need = work.least_seconds(
        route_work.route_passes(c["rows"], c["features"], c["max_bin"],
                                len({level for level, _ in found})),
        ev["device"]["kind"], ev["device"]["count"])
    return 100.0 * need / (took / t["rounds"])
