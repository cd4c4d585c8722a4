"""Mean time a traced round inside ``rabit.checkpoint.pickle``: both
``pickle.dumps`` of ``rabit_tpu.checkpoint`` (and, with the spill on, the
wrapper's copy of the global blob), from the program's own span in the
profiler's trace (``harness/spans.py``)."""

from harness import spans

UNIT, SOURCE, LAYER, MOVES = "ms", "device_trace", "API", "round_p50_ms"


def read(ev):
    return spans.per_round_ms(spans.table(ev), "rabit.checkpoint.pickle")
