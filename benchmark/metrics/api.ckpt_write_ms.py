"""Mean time a traced round inside ``rabit.spill.write`` (open, write,
flush, fsync, rename) and ``rabit.spill.dirsync`` (the directory's fsync),
both files of a commit: what the durable spill costs on the disk, from the
program's own spans in the profiler's trace (``harness/spans.py``)."""

from harness import spans

UNIT, SOURCE, LAYER, MOVES = "ms", "device_trace", "API", "round_p50_ms"


def read(ev):
    return spans.per_round_ms(spans.table(ev), "rabit.spill.write",
                              "rabit.spill.dirsync")
