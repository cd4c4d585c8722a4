"""SIGKILL of the worker to ``block_until_ready`` of the first resumed
round, both on ``time.time()`` of the one machine: what a preemption costs.
A per-layer metric and not an end-to-end one, because half of it is the TPU
runtime's start in the second life, which takes 8 to 16 s as it pleases
(``PERF.md``): no bound the contract allows would hold it."""

UNIT, SOURCE, LAYER, MOVES = "s", "host_clock", "recovery", "setup_s"


def read(ev):
    lives = ev["lives"]
    if len(lives) < 2 or "killed" not in lives[0]:
        return None
    fenced = lives[1]["stamps"].get("first_fenced")
    return None if fenced is None else fenced - lives[0]["killed"]["at"]
