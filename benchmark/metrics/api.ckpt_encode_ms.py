"""Mean time a traced round inside ``rabit.spill.encode``, both files of a
commit: the store's codec (``rabit_checkpoint_compress``) and the crc over
what it made, from the program's own span in the profiler's trace
(``harness/spans.py``).  The span's ``raw`` and ``encoded`` stats say what
the codec saved."""

from harness import spans

UNIT, SOURCE, LAYER, MOVES = "ms", "device_trace", "API", "round_p50_ms"


def read(ev):
    return spans.per_round_ms(spans.table(ev), "rabit.spill.encode")
