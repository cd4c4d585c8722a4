"""Mean host time a round from the round's fence to ``checkpoint``
returning: the forest's and the margin's copy to the host, the pickle, the
engine's commit and, where the spill is on, the fsynced frame."""

UNIT, SOURCE, LAYER, MOVES = "ms", "host_clock", "API", "round_p50_ms"


def read(ev):
    rounds = ev["rounds"]
    if not rounds:
        return None
    return 1e3 * sum(r[3] - r[1] for r in rounds) / len(rounds)
