"""Kill to the first line of the second life's ``main``."""

UNIT, SOURCE, LAYER, MOVES = "s", "host_clock", "launch", "setup_s"


def read(ev):
    lives = ev["lives"]
    if len(lives) < 2 or "killed" not in lives[0]:
        return None
    return lives[1]["stamps"]["main"] - lives[0]["killed"]["at"]
