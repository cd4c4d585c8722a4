"""The comparison that decides ``correct``.

What the timed path produced in its first rounds — the trees, and after
each round the log-loss and the norm of the margin — is held against the
plain reference following those trees (``reference.boost_rounds`` with
``follow``), and the checkpoint's round trip against what was committed.
Each number has a limit of its own, set from readings (``PERF.md`` has
them) and kept as data beside the configuration; exact comparisons have
the limit 0 and are not in that file.
"""

from __future__ import annotations

import math

#: what a number that cannot be read, or is not finite, is written as: the
#: result's line has to stay JSON, and no limit is this wide
UNREADABLE = 1e308


def numbers(ev: dict, followed, limits: dict) -> list:
    """``[(name, value, limit)]``; a value that cannot be read is ``nan``
    and fails."""
    first = ev["lives"][0].get("first") or {}
    traffic = ev["traffic"]

    def worst(pairs):
        gaps = [abs(a - b) / abs(b) for a, b in pairs]
        return max(gaps) if gaps and len(gaps) == traffic["check_rounds"] else math.nan

    out = [
        ("gain_gap", max(followed.gain_gap, default=math.nan), limits["gain_gap"]),
        ("leaf_gap", max(followed.leaf_gap, default=math.nan), limits["leaf_gap"]),
        ("logloss_gap", worst(zip(first.get("logloss", []), followed.logloss)),
         limits["logloss_gap"]),
        ("margin_norm_gap",
         worst(zip(first.get("margin_norm", []), followed.margin_norm)),
         limits["margin_norm_gap"]),
    ]
    last = ev["lives"][-1]
    done = traffic["check_rounds"] + len(ev["rounds"])
    out.append(("commit_mismatch", abs(last.get("version", -1) - done), 0))
    kill = traffic.get("kill_after_commit")
    if kill:
        lives = ev["lives"]
        killed = lives[0].get("killed") or {}
        back = (lives[1].get("restored") or {}) if len(lives) > 1 else {}
        wrong = [
            len(lives) != 2,
            killed.get("after_commit") != kill,
            back.get("version") != kill,
            back.get("state_digest") is None
            or back.get("state_digest") != killed.get("state_digest"),
            len(lives) < 2 or lives[1].get("trees_digest_at_restore") is None
            or lives[1]["trees_digest_at_restore"] != killed.get("trees_digest"),
        ]
        out.append(("resume_mismatch", sum(wrong), 0))
    return [(n, v if math.isfinite(v) else UNREADABLE, lim) for n, v, lim in out]


def correct(compared: list) -> bool:
    return all(v <= lim for _, v, lim in compared)
