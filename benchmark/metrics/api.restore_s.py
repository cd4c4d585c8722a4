"""Second life: ``init`` + ``load_checkpoint(with_local=True)`` + placing
the restored state on the device."""

UNIT, SOURCE, LAYER, MOVES = "s", "host_clock", "API", "setup_s"


def read(ev):
    lives = ev["lives"]
    if len(lives) < 2 or not lives[1].get("restored"):
        return None
    s = lives[1]["stamps"]
    return s["restored"] - s["placed"]
