"""From the profiler's trace to a table: the yardstick's reduction.

``read`` turns an ``.xplane.pb`` into plain lists (it needs nothing of JAX
but ``jax.profiler.ProfileData``); ``reduce`` is arithmetic on those lists
and is what the tests check on a recorded trace and on hand-made intervals.
Which planes are devices, which line holds the operations, which host spans
the worker writes and what counts as a collective is data, in
``trace_rules.json`` beside this file.

The window of a trace is from the start of the first traced ``round`` span
to the end of the last ``checkpoint`` span: whole rounds, commit included.
Per device, *busy* is the union of the operations' intervals inside it, an
operation's seconds are its self time (its interval less what nests in it,
so a loop is not counted twice), *exposed* collective time is the part of
the collectives' intervals in which nothing else runs on that device, and
an idle gap goes to the host span that covers most of it.  Devices are
averaged.
"""

from __future__ import annotations

import glob
import json
import re
from pathlib import Path

RULES = json.loads((Path(__file__).with_name("trace_rules.json")).read_text())

#: the opcode of an HLO instruction's text: the first lower-case word that
#: opens a bracket ("f32[8]{0:T(128)S(1)} all-reduce(%x), ..." -> "all-reduce")
OPCODE = re.compile(r"(?:^|[\s)])([a-z][a-z0-9_-]*)\(")


def short_name(text: str, collective) -> str:
    """``"%hist_level.9 = (f32[...]) custom-call(...)"`` -> ``"hist_level.9"``.
    XLA names an instruction after the JAX primitive that made it, so the
    chip's trace shows a ``lax.psum`` as ``psum.42``; where the opcode is a
    collective and the name does not say so, the opcode goes in front:
    ``"all-reduce:psum.42"``."""
    short, _, rest = text.partition(" = ")
    short = short.lstrip("%")
    m = OPCODE.search(rest)
    if m and collective.search(m.group(1)) and not collective.search(short):
        short = f"{m.group(1)}:{short}"
    return short


def read(path: str, rules: dict = RULES) -> dict:
    """``{"devices": {plane: [(name, start_ns, end_ns)]}, "async": {plane:
    [...]}, "host": [...]}``: the operations of each device by their short
    names, the spans of asynchronous operations (start to done), and the
    worker's host spans."""
    from jax.profiler import ProfileData

    raw = {"devices": {}, "async": {}, "host": []}
    spans = set(rules["spans"])

    coll = re.compile(rules["collective"])

    def op(e):
        return (short_name(e.name, coll), e.start_ns, e.start_ns + e.duration_ns)

    for plane in ProfileData.from_file(path).planes:
        if re.search(rules["device_plane"], plane.name):
            events = raw["devices"].setdefault(plane.name, [])
            later = raw["async"].setdefault(plane.name, [])
            for line in plane.lines:
                if any(re.search(p, line.name) for p in rules["op_lines"]):
                    events.extend(op(e) for e in line.events if e.duration_ns > 0)
                elif any(re.search(p, line.name) for p in rules["async_lines"]):
                    later.extend(op(e) for e in line.events if e.duration_ns > 0)
        if re.search(rules["host_plane"], plane.name):
            for line in plane.lines:
                raw["host"].extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                                   for e in line.events if e.name in spans)
    return raw


def union(intervals) -> list:
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        elif b > a:
            out.append([a, b])
    return out


def length(disjoint) -> float:
    return sum(b - a for a, b in disjoint)


def subtract(disjoint, holes) -> list:
    """``disjoint`` less ``holes``; both sorted and disjoint."""
    out = []
    for a, b in disjoint:
        for c, d in holes:
            if d <= a or c >= b:
                continue
            if c > a:
                out.append([a, c])
            a = max(a, d)
            if a >= b:
                break
        if a < b:
            out.append([a, b])
    return out


def self_times(events) -> list:
    """``(name, self_ns)`` for properly nested events of one line."""
    out, stack = [], []   # stack of [name, end, self]
    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= min(b, stack[-1][1]) - a
        stack.append([name, b, b - a])
    out.extend((s[0], s[2]) for s in stack)
    return out


def clip(events, lo, hi):
    return [(n, max(a, lo), min(b, hi)) for n, a, b in events
            if b > lo and a < hi]


def reduce(raw: dict, rules: dict = RULES) -> dict | None:
    """The table the per-layer readers read; ``None`` where no operation ran
    on a device inside the traced rounds."""
    host = sorted(raw["host"], key=lambda e: e[1])
    opens = [e for e in host if e[0] == rules["window_opens_with"]]
    closes = [e for e in host if e[0] == rules["window_closes_with"]]
    all_ops = [e for evs in raw["devices"].values() for e in evs]
    if not all_ops:
        return None
    if opens and closes:
        lo, hi = opens[0][1], max(e[2] for e in closes)
    else:
        lo, hi = min(e[1] for e in all_ops), max(e[2] for e in all_ops)
    if hi <= lo:
        return None
    coll = re.compile(rules["collective"])
    n_dev = len(raw["devices"])
    busy = coll_s = exposed = 0.0
    ops: dict = {}
    gaps: dict = {}
    host_in = clip(host, lo, hi)
    for plane, events in raw["devices"].items():
        events = clip(events, lo, hi)
        later = clip(raw.get("async", {}).get(plane, []), lo, hi)
        covered = union((a, b) for _, a, b in events)
        busy += length(covered)
        for name, ns in self_times(events):
            cell = ops.setdefault(name, [0, 0.0])
            cell[0] += 1
            cell[1] += ns
        c_cov = union((a, b) for n, a, b in events + later if coll.search(n))
        o_cov = union((a, b) for n, a, b in events if not coll.search(n))
        coll_s += length(c_cov)
        exposed += length(subtract(c_cov, o_cov))
        for a, b in subtract([[lo, hi]], covered):
            best, share = "no_span", 0.0
            for name, c, d in host_in:
                over = min(b, d) - max(a, c)
                if over > share:
                    best, share = name, over
            gaps[best] = gaps.get(best, 0.0) + (b - a)
    spans = {}
    for name, a, b in host_in:
        cell = spans.setdefault(name, [0, 0.0])
        cell[0] += 1
        cell[1] += (b - a) / 1e9
    s = 1e9 * n_dev
    return {
        "devices": n_dev,
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / s,
        "rounds": len([e for e in opens if e[1] >= lo and e[2] <= hi]),
        "ops": {k: [v[0] / n_dev, v[1] / s] for k, v in ops.items()},
        "collective_s": coll_s / s,
        "collective_exposed_s": exposed / s,
        "idle_gaps": sorted(([k, v / s] for k, v in gaps.items()),
                            key=lambda kv: -kv[1]),
        "spans": spans,
    }


def reduce_dir(trace_dir, rules: dict | None = None) -> dict | None:
    rules = {**RULES, **(rules or {})}
    found = sorted(glob.glob(str(Path(trace_dir) / "plugins" / "profile" / "*"
                                 / "*.xplane.pb")))
    return reduce(read(found[-1], rules), rules) if found else None
