"""Second life's ``lower().compile()`` of the round: a persistent-cache
load (the run fails outright where it is a miss)."""

UNIT, SOURCE, LAYER, MOVES = "s", "host_clock", "compile", "setup_s"


def read(ev):
    lives = ev["lives"]
    return lives[1]["compile"]["seconds"] if len(lives) > 1 else None
