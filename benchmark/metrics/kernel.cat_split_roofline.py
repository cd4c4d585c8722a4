"""The categorical split searches' share of their roofline: the least time
for the searches' algorithmic work (``harness/cat_split_work.py``: each
level's combined histogram of the categorical features read once, a node's
set, feature, code count and gain written; bound by HBM bandwidth) over the
summed device time of the operations that do them.  A level is counted
where some search of the trace is named for it; ``None`` where none ran."""

import re

from harness import cat_split_work, work

UNIT, SOURCE, LAYER, MOVES = "%", "device_trace", "kernels", "round_p50_ms"

#: the search kernels with their level ("cat_split_d7.1")
OPS = [r"^cat_split_d(\d+)"]


def read(ev):
    t, c = ev.get("trace"), ev["config"]
    if not t or not t["rounds"]:
        return None
    found = [(int(m.group(1)), s) for name, (_, s) in t["ops"].items()
             for m in (re.search(p, name) for p in OPS) if m and s > 0]
    took = sum(s for _, s in found)
    if took <= 0:
        return None
    need = work.least_seconds(
        cat_split_work.cat_split_levels(
            len(c["program"].get("categorical", ())), c["max_bin"],
            sorted({level for level, _ in found})),
        ev["device"]["kind"], ev["device"]["count"])
    return 100.0 * need / (took / t["rounds"])
