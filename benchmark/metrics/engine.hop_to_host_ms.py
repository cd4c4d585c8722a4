"""Mean time a hop from the start of its first device operation (the
``pure_callback``'s send) to the moment ``gbdt.cross`` opens on the host,
T1 - T0 on the profiler's clock (``harness/hops.py``): the histogram's way
down and JAX's dispatch into Python.  ``None`` where the trace pairs no
``gbdt.cross`` with a device operation."""

from harness import hops

UNIT, SOURCE, LAYER, MOVES = "ms", "device_trace", "engine", "round_p50_ms"


def read(ev):
    return hops.mean_ms(hops.rows(hops.table(ev), paired=True), "to_host_s")
