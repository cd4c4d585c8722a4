"""The deep histogram levels' share of their roofline: the least time for
the passes at the levels whose stacked gradient matrix passes one 128-row
MXU tile (level 6 on; ``harness/work.py:hist_pass``, bound by HBM
bandwidth) over the summed device time of the operations that do them,
found in the trace by the name patterns below; ``None`` where none ran."""

import re

from harness import work

UNIT, SOURCE, LAYER, MOVES = "%", "device_trace", "kernels", "round_p50_ms"

#: the first level whose ``4 * 2**d`` rows pass the MXU's 128
FIRST_DEEP = 6
#: ``ops/boost.py`` names a level kernel for its level ("hist_level_d6.1",
#: seen on the v5e, PR 27); a step that derives part of a level's histogram
#: on the device gets a name and a pattern of its own here
OPS = [r"^hist_level_d(\d+)"]


def read(ev):
    t, c = ev.get("trace"), ev["config"]
    if not t or not t["rounds"]:
        return None
    found = ((re.search(p, name), s) for name, (_, s) in t["ops"].items()
             for p in OPS)
    took = sum(s for m, s in found if m and int(m.group(1)) >= FIRST_DEEP)
    if took <= 0:
        return None
    passes = [work.hist_pass(c["rows"], c["features"], c["max_bin"], d)
              for d in range(FIRST_DEEP, c["max_depth"])]
    need = work.least_seconds(
        {k: sum(p[k] for p in passes) for k in ("adds", "bytes")},
        ev["device"]["kind"], ev["device"]["count"])
    return 100.0 * need / (took / t["rounds"])
