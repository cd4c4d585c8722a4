"""Device time a traced round outside the round's kernels: ``step.device_ms``
less the operations that build histograms and the routing passes, found by
the name patterns below.  What is left is the XLA side of a round: the
blocked codes re-laid, the kernels' output reshaped and stacked, the scan
over every (feature, bin) candidate of every node, the leaves' gather."""

import re

UNIT, SOURCE, LAYER, MOVES = "ms", "device_trace", "kernels", "round_p50_ms"

#: ``kernel.hist_roofline``'s patterns and ``kernel.route_ms``'s
OPS = [r"^hist_level", r"^node_histograms", r"hist_kernel", r"^route_"]


def read(ev):
    t = ev.get("trace")
    if not t or not t["rounds"]:
        return None
    kernels = sum(s for name, (_, s) in t["ops"].items()
                  if any(re.search(p, name) for p in OPS))
    if kernels <= 0:
        return None
    return 1e3 * (t["busy_s"] - kernels) / t["rounds"]
