"""1 - busy/window from the profiler's trace, mean over the devices."""

UNIT, SOURCE, LAYER, MOVES = "%", "device_trace", "device", "round_p50_ms"


def read(ev):
    t = ev.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
