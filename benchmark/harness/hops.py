"""One hop of the engine, phase by phase, on the profiler's clock.

A hop is one ``rabit_tpu.allreduce`` that ``train_round_hybrid`` makes from
inside its jitted round through a ``pure_callback``.  The device shows it as
host-transfer operations (``engine.hop_device_ms``'s own pattern,
``^pure_callback``: the sends, then a receive in which the device waits for
the host's answer), the program as ``gbdt.cross`` with its children
(``gbdt.cross.in``, ``rabit.allreduce.copy_in``, ``rabit.allreduce``,
``rabit.allreduce.copy_out``, ``gbdt.cross.out``: ``PERF.md`` §3)::

    device:  [send(s)] ...................................... [receive]
              T0                                                     T3
    host:            T1 [gbdt.cross ............................] T2
    phase:   to_host |  copy  |  engine  |  copy  |  to_device

``read`` takes the device's operations from ``xplane.read`` and the
program's spans, with ALL their stats, from the same ``.xplane.pb``;
``reduce`` is arithmetic on plain lists (checked on hand-made intervals and
on a trace recorded on the v5e) and gives one row a hop inside
``xplane.reduce``'s window.  A device operation belongs to the first
``gbdt.cross`` that closes after it starts: level d + 1's histogram needs
level d's answer, so a hop's operations all start between the last hop's
close and its own.  The two clocks of one trace agree only to a millisecond
or so, anew in every session, so the device's times are first moved later
by the least shift that lets no receive end before its ``gbdt.cross``
closes (``clock_shift_s``; it leaves T3 - T0 and the sum of the phases as
they are, and makes ``to_host`` an upper and ``to_device`` a lower reading
by what the quickest answer really took).  A ``gbdt.cross`` with no
operation that starts before it opens gives no row and is counted
(``dropped``); in a trace with no such operation at all every row keeps its
spans' times and has nothing of what needs the device's.

``table(ev)`` is what the readers call: the run's raw trace, read once a
process in a child (the benchmark's parent stays free of jax).
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
if str(HERE.parent) not in sys.path:
    sys.path.insert(0, str(HERE.parent))

from harness import spans, xplane  # noqa: E402

#: the hops' device operations: ``metrics/engine.hop_device_ms.py``'s pattern
OPS = [r"^pure_callback"]
#: the host function of a hop, and the program's spans read beside it
CROSS = "gbdt.cross"
PROGRAM = ("gbdt.cross", "rabit.allreduce")
ENGINE = "rabit.allreduce"
#: the four copies of a hop, in the order they run
COPIES = ("gbdt.cross.in", "rabit.allreduce.copy_in",
          "rabit.allreduce.copy_out", "gbdt.cross.out")
#: what a row holds of each phase, in the order of the picture
PHASES = ("to_host_s", "copy_s", "engine_s", "other_s", "to_device_s")


def read(path: str, rules: dict = xplane.RULES) -> dict:
    """``xplane.read``'s lists and ``"program"``: ``[(name, start_ns,
    end_ns, stats)]`` for every ``gbdt.cross*`` and ``rabit.allreduce*``
    event of the host's threads."""
    from jax.profiler import ProfileData

    raw = xplane.read(path, rules)
    raw["program"] = [
        (e.name, e.start_ns, e.start_ns + e.duration_ns,
         {k: v for k, v in e.stats if isinstance(v, (int, float, str))})
        for plane in ProfileData.from_file(path).planes
        if re.search(rules["host_plane"], plane.name)
        for line in plane.lines for e in line.events
        if e.name.startswith(PROGRAM)]
    return raw


def reduce(raw: dict, rules: dict = xplane.RULES) -> dict | None:
    """``{"rounds", "window_s", "devices", "device_ops", "device_s",
    "dropped", "clock_shift_s", "hops": [row]}``; ``None`` where the trace has no ``round``
    and ``checkpoint`` to bound the window or no ``gbdt.cross`` in it."""
    host = sorted(raw["host"], key=lambda e: e[1])
    opens = [e for e in host if e[0] == rules["window_opens_with"]]
    closes = [e for e in host if e[0] == rules["window_closes_with"]]
    if not opens or not closes:
        return None
    lo, hi = opens[0][1], max(e[2] for e in closes)
    rounds = [e for e in opens if e[1] >= lo and e[2] <= hi]
    program = sorted((s for s in raw["program"] if s[1] >= lo and s[2] <= hi),
                     key=lambda s: s[1])
    crosses = [s for s in program if s[0] == CROSS]
    if not crosses:
        return None
    n_dev = max(len(raw["devices"]), 1)
    everything = sorted((e for evs in raw["devices"].values()
                         for e in xplane.clip(evs, lo, hi)),
                        key=lambda e: e[1])
    mine, others = [], []
    for e in everything:
        (mine if any(re.search(p, e[0]) for p in OPS) else others).append(e)
    s = 1e9
    # a device operation belongs to the first gbdt.cross that closes after
    # it starts
    groups, i = [], 0
    for _, _, t2, _ in crosses:
        first = i
        while i < len(mine) and mine[i][1] < t2:
            i += 1
        groups.append(mine[first:i])
    # The profiler lays the device's clock beside the host's anew in every
    # session, to a millisecond or so (on the v5e, PR 36: a receive that
    # ends 0.07 ms and, in another run, 0.85 ms BEFORE the host function
    # that feeds it returns).  No answer arrives before it was returned:
    # the device's times are moved later by the least shift that holds
    # every hop to that.
    shift = max([0.0] + [t2 - max(e[2] for e in ops)
                         for (_, _, t2, _), ops in zip(crosses, groups) if ops])
    rows, dropped = [], 0
    for cross, ops in zip(crosses, groups):
        _, t1, t2, stats = cross
        inside = [c for c in program if c[1] >= t1 and c[2] <= t2
                  and c is not cross]
        took: dict = {}
        for n, a, b, _ in inside:
            if n in COPIES or n == ENGINE:
                took[n] = took.get(n, 0.0) + (b - a) / s
        copies = [took[n] for n in COPIES if n in took]
        row = {
            "round": next((k for k, r in enumerate(rounds)
                           if r[1] <= t1 and t2 <= r[2]), None),
            "level": stats.get("level"),
            "version": stats.get("version"),
            # the parent of PR 36 has it on ``rabit.allreduce`` alone
            "nbytes": next((c[3]["nbytes"] for c in [cross] + inside
                            if "nbytes" in c[3]), None),
            "t1": (t1 - lo) / s, "t2": (t2 - lo) / s,
            "callback_s": (t2 - t1) / s,
            "spans": took,
            "copy_s": sum(copies) if copies else None,
            "engine_s": took.get(ENGINE),
            "other_s": (t2 - t1) / s - sum(took.values()),
        }
        if mine:
            t0 = min((e[1] for e in ops), default=t1) + shift
            if t0 >= t1:
                dropped += 1     # nothing on the device opens this hop
                continue
            t3 = max(e[2] for e in ops) + shift
            receive = max(ops, key=lambda e: e[2])   # it ends the hop
            sends = [e for e in ops if e is not receive]
            row.update({
                "t0": (t0 - lo) / s, "t3": (t3 - lo) / s,
                "to_host_s": (t1 - t0) / s, "to_device_s": (t3 - t2) / s,
                "hop_s": (t3 - t0) / s,
                "device_ops": len(ops),
                "device_s": sum(b - a for _, a, b in ops) / s / n_dev,
                "beside_s": beside_ns(sends, receive, others) / s / n_dev,
            })
        rows.append(row)
    return {
        "devices": len(raw["devices"]),
        "window_s": (hi - lo) / s,
        "rounds": len(rounds),
        "device_ops": len(mine),
        "device_s": sum(b - a for _, a, b in mine) / s / n_dev,
        "dropped": dropped,
        "clock_shift_s": shift / s,
        "hops": rows,
    }


def beside_ns(sends, receive, others) -> float:
    """What the device ran from the sends' end to the receive's start: the
    time in which something else could run beside a transfer."""
    if not sends:
        return 0.0
    a, b = max(e[2] for e in sends), receive[1]
    return xplane.length(xplane.union(
        (max(c, a), min(d, b)) for _, c, d in others if d > a and c < b))


def rows(t: dict | None, paired: bool = False) -> list:
    """A table's rows; ``paired``: those that have the device's side."""
    return [r for r in (t or {}).get("hops", []) if "t0" in r or not paired]


def mean_ms(rows, key) -> float | None:
    """Mean a hop of one of a row's times; ``None`` where no row has it."""
    got = [r[key] for r in rows if r.get(key) is not None]
    return 1e3 * sum(got) / len(got) if got else None


def by_level(rows) -> dict:
    """``{level: [row]}`` in the order of a round's hops."""
    out: dict = {}
    for r in rows:
        out.setdefault(r["level"], []).append(r)
    return out


def jitter_ms(t: dict | None) -> float | None:
    """The largest, over levels, of the range across the traced rounds of
    that level's T3 - T0; ``None`` with fewer than two rounds of a level."""
    ranges = [max(r["hop_s"] for r in group) - min(r["hop_s"] for r in group)
              for group in by_level(rows(t, paired=True)).values()
              if len(group) > 1]
    return 1e3 * max(ranges) if ranges else None


# -- finding and reading a run's trace ----------------------------------------

_TABLES: dict = {}   # path -> table: the readers of one run share one read


def table_of(run_dir: Path) -> dict | None:
    path = spans.find(run_dir)
    if path is None:
        return None
    if path not in _TABLES:
        r = subprocess.run(
            [sys.executable, str(Path(__file__).resolve()), path,
             json.dumps(spans.rules_of(run_dir))],
            capture_output=True, text=True, timeout=900,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        if r.returncode != 0:
            print(f"harness/hops.py: could not read {path}:\n"
                  f"{r.stderr[-2000:]}", file=sys.stderr, flush=True)
            _TABLES[path] = None
        else:
            _TABLES[path] = json.loads(r.stdout.strip().splitlines()[-1])
    return _TABLES[path]


def table(ev: dict) -> dict | None:
    """The hops of the run whose evidence this is."""
    return table_of(spans.ROOT / ".bench_runs" / ev["cell"]["name"])


def main(argv) -> int:
    rules = json.loads(argv[2]) if len(argv) > 2 else xplane.RULES
    print(json.dumps(reduce(read(argv[1], rules), rules)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
