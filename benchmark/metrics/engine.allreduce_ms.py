"""Mean time a hop inside ``rabit.allreduce``: ``obs.collective`` around
the engine's allreduce, from the program's own span in the profiler's
trace (``harness/spans.py``) — ``engine.hop_ms`` from inside."""

from harness import spans

UNIT, SOURCE, LAYER, MOVES = "ms", "device_trace", "engine", "round_p50_ms"


def read(ev):
    return spans.per_call_ms(spans.table(ev), "rabit.allreduce")
