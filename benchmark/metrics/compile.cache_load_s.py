"""First life's ``lower().compile()`` of the round, tracing included: a
cache load on a warm cache, the compile itself on a cold one (which of the
two is printed beside it)."""

UNIT, SOURCE, LAYER, MOVES = "s", "host_clock", "compile", "setup_s"


def read(ev):
    return ev["lives"][0]["compile"]["seconds"]
