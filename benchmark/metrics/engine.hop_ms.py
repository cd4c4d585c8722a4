"""Mean host time inside the worker's ``rabit_tpu.allreduce`` callback, a
hop (the hops are counted and printed beside it)."""

UNIT, SOURCE, LAYER, MOVES = "ms", "host_clock", "engine", "round_p50_ms"


def read(ev):
    seconds = sum(life["hops"][0] for life in ev["lives"])
    count = sum(life["hops"][1] for life in ev["lives"])
    return 1e3 * seconds / count if count else None
