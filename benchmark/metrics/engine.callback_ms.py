"""Mean time a hop inside ``gbdt.cross``, the host function of
``train_round_hybrid``'s ``pure_callback``: the histogram's copy to the
host, the engine's allreduce and the result's copy back — the host side of
a hop, from the program's own span in the profiler's trace
(``harness/spans.py``)."""

from harness import spans

UNIT, SOURCE, LAYER, MOVES = "ms", "device_trace", "engine", "round_p50_ms"


def read(ev):
    return spans.per_call_ms(spans.table(ev), "gbdt.cross")
