"""The histogram passes' share of their roofline: the least time for the
passes' algorithmic work (``harness/work.py``, bound by HBM bandwidth) over
the summed device time of the operations that do them, found in the trace
by the name patterns below."""

import re

from harness import work

UNIT, SOURCE, LAYER, MOVES = "%", "device_trace", "kernels", "round_p50_ms"

#: device operations that build histograms, by the short names the trace
#: shows ("hist_level0.1", "hist_level.9"): the fused level kernels of
#: ``ops/boost.py`` (seen on the v5e, PR 24) and the standalone kernel of
#: ``ops/hist.py`` ("node_histograms_pallas.6", seen in engine-hop, PR 24)
OPS = [r"^hist_level", r"^node_histograms", r"hist_kernel"]


def read(ev):
    t, c = ev.get("trace"), ev["config"]
    if not t or not t["rounds"]:
        return None
    took = sum(s for name, (_, s) in t["ops"].items()
               if any(re.search(p, name) for p in OPS))
    if took <= 0:
        return None
    need = work.least_seconds(
        work.hist_passes(c["rows"], c["features"], c["max_bin"], c["max_depth"]),
        ev["device"]["kind"], ev["device"]["count"])
    return 100.0 * need / (took / t["rounds"])
