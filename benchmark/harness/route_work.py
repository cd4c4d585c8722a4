"""What one routing pass has to do, whatever does it: the count that
``work.py`` has for the histogram passes, for the passes that only move
rows one level down (the leaves' pass of every program, and a pass a level
where routing no longer rides the histogram's sweep).

A routing pass over ``n`` rows reads every row's bin codes (``work.py``'s
byte or two a code) and its node id, compares one code with one threshold,
and writes the node id of the next level.  Nothing here knows of feature
tiles, lane masks or kernels.
"""

from __future__ import annotations

from harness import work


def route_pass(n: int, features: int, bins: int) -> dict:
    return {"adds": n, "bytes": n * (features * work.code_bytes(bins) + 4 + 4)}


def route_passes(n: int, features: int, bins: int, passes: int) -> dict:
    one = route_pass(n, features, bins)
    return {k: passes * v for k, v in one.items()}
