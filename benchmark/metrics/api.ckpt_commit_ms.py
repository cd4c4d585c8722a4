"""Mean time a traced round inside ``rabit.checkpoint.commit``: the
engine's ``checkpoint`` (its copy of the blobs; at world > 1 the ring
replication of the local model) and the commit's bookkeeping, from the
program's own span in the profiler's trace (``harness/spans.py``)."""

from harness import spans

UNIT, SOURCE, LAYER, MOVES = "ms", "device_trace", "API", "round_p50_ms"


def read(ev):
    return spans.per_round_ms(spans.table(ev), "rabit.checkpoint.commit")
