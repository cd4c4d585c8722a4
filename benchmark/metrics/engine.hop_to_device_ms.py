"""Mean time a hop from the moment ``gbdt.cross`` closes on the host to the
end of the hop's last device operation (the ``pure_callback``'s receive),
T3 - T2 on the profiler's clock (``harness/hops.py``): the answer's way up.
``None`` where the trace pairs no ``gbdt.cross`` with a device operation."""

from harness import hops

UNIT, SOURCE, LAYER, MOVES = "ms", "device_trace", "engine", "round_p50_ms"


def read(ev):
    return hops.mean_ms(hops.rows(hops.table(ev), paired=True), "to_device_s")
