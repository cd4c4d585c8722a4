"""Rounds committed in the window over the whole window's wall time, an
outage included: from the window's opening to the return of the last
commit (a round that straddles the close is counted with its time)."""

UNIT, SOURCE = "rounds/s", "host_clock"


def read(ev):
    rounds = ev["rounds"]
    if not rounds:
        return None
    return len(rounds) / (rounds[-1][3] - ev["window"]["start"])
