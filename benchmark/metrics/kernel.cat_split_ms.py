"""Device time a traced round of the categorical split searches: the kernels
that find each node's best set of codes on the categorical features, one a
level (``ops/boost.py`` ``cat_split``).  Found in the trace by the name
pattern below; ``None`` where none ran (a configuration with no categorical
feature, or a program without the kernel)."""

import re

UNIT, SOURCE, LAYER, MOVES = "ms", "device_trace", "kernels", "round_p50_ms"

#: ``ops/boost.py`` names the kernel for its level ("cat_split_d<d>")
OPS = [r"^cat_split"]


def read(ev):
    t = ev.get("trace")
    if not t or not t["rounds"]:
        return None
    took = sum(s for name, (_, s) in t["ops"].items()
               if any(re.search(p, name) for p in OPS))
    return 1e3 * took / t["rounds"] if took > 0 else None
