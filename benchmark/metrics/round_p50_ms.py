"""Median of round start to commit returned, over ALL rounds of the window
(the upper median; the sample count is printed beside it): the round a user
waits for, steady where a rate with an outage inside it is not."""

UNIT, SOURCE = "ms", "host_clock"


def read(ev):
    took = sorted(r[3] - r[0] for r in ev["rounds"])
    return 1e3 * took[len(took) // 2] if took else None
