"""Mean time a hop inside the four copies of the host function together:
``gbdt.cross.in`` and ``.out`` (the operand made a numpy array, the answer
made the operand's type) and ``rabit.allreduce.copy_in`` and ``.copy_out``
(``data.flatten()`` and what else ``rabit_tpu.allreduce`` does before the
engine's call, the result's reshape after it), from the program's own spans
in the profiler's trace (``harness/hops.py``).  ``None`` where the program
has none of the four (the parent of PR 36)."""

from harness import hops

UNIT, SOURCE, LAYER, MOVES = "ms", "device_trace", "engine", "round_p50_ms"


def read(ev):
    return hops.mean_ms(hops.rows(hops.table(ev)), "copy_s")
