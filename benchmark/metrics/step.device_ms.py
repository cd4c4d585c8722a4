"""Device time a round: the union of the device operations' intervals in
the traced rounds, averaged over the devices, over the rounds traced."""

UNIT, SOURCE, LAYER, MOVES = "ms", "device_trace", "model step", "round_p50_ms"


def read(ev):
    t = ev.get("trace")
    if not t or not t["rounds"]:
        return None
    return 1e3 * t["busy_s"] / t["rounds"]
