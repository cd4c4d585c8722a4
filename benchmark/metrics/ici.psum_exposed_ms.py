"""All-reduce device time a round during which no other operation runs on
that device, mean over the devices."""

UNIT, SOURCE, LAYER, MOVES = "ms", "device_trace", "collectives", "round_p50_ms"


def read(ev):
    t = ev.get("trace")
    if not t or not t["rounds"] or t["collective_s"] <= 0:
        return None
    return 1e3 * t["collective_exposed_s"] / t["rounds"]
