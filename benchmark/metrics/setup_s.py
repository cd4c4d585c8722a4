"""Start of the command to the first timed round: native build, reaching
the chip, data, placement, compile or cache load, the first rounds."""

UNIT, SOURCE = "s", "host_clock"


def read(ev):
    return ev["window"]["start"] - ev["t_cmd"]
