"""The share of the traced window in which a device ran nothing and no
span says why: idle time goes to the deepest span open at that moment
(``harness/spans.py``), and what falls to no span, or to the self time of
the spans below (which only hold other spans), is not accounted for.  Read
only where the program wrote its own spans into the trace."""

from harness import spans

UNIT, SOURCE, LAYER, MOVES = "%", "device_trace", "device", "round_p50_ms"

#: idle under these is idle nobody has named: the worker's two outer spans
#: and the program's two that only hold their children
HOLDERS = (spans.NO_SPAN, "round", "checkpoint", "rabit.checkpoint",
           "rabit.checkpoint.spill")


def read(ev):
    t = spans.table(ev)
    if not spans.program_spans(t) or t["window_s"] <= 0:
        return None
    lost = sum(t["idle_by_span"].get(name, 0.0) for name in HOLDERS)
    return 100.0 * lost / t["window_s"]
