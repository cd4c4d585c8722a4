"""The whole round's share of the chip's peak: the least time the chips
could take for the round's algorithmic work (``harness/work.py``: the
histogram passes and the leaf pass, bound by HBM bandwidth) over the wall
time of a traced round, commit included."""

from harness import work

UNIT, SOURCE, LAYER, MOVES = "%", "device_trace", "model step", "round_p50_ms"


def read(ev):
    t, c = ev.get("trace"), ev["config"]
    if not t or not t["rounds"]:
        return None
    need = work.least_seconds(
        work.round_work(c["rows"], c["features"], c["max_bin"], c["max_depth"]),
        ev["device"]["kind"], ev["device"]["count"])
    return 100.0 * need / (t["window_s"] / t["rounds"])
