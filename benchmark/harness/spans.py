"""The program's own spans in the profiler's trace, and the device's idle
time put down to them.

``rabit_tpu.obs.span`` writes every span of the program (``rabit.checkpoint``
and what is under it, ``rabit.allreduce``, ``gbdt.cross``: names and fields in
``PERF.md`` §3) into the profiler's trace as a ``TraceAnnotation``, beside
the four the worker writes (``round``, ``margin_d2h``, ``checkpoint``,
``engine_hop``).  ``read`` takes them from an ``.xplane.pb`` with the thread
each ran on and the stats it carries; ``reduce`` is arithmetic on plain
lists, checked on hand-made intervals and on a trace recorded on the v5e.

Inside the window ``xplane.reduce`` uses (first ``round`` start to last
``checkpoint`` end) a span has a count, a total, a *self time* (its interval
less what its children on the same thread cover) and its stats summed; and
every nanosecond in which a device runs nothing goes to the *deepest* span
open at that moment, on whichever thread (of two equally deep, the one that
opened last), or to ``no_span``: a gap is split over the spans that share
it, not handed whole to the one that covers most of it.  Devices are
averaged.

``table(ev)`` is what the readers call: it finds the run's raw trace under
``.bench_runs/<cell>/trace<k>/``, reads it once a process, in a child (the
benchmark's parent stays free of jax), and returns ``None`` where there is
no trace or the trace holds no span.
"""

from __future__ import annotations

import glob
import json
import os
import re
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent
if str(HERE.parent) not in sys.path:
    sys.path.insert(0, str(HERE.parent))

from harness import xplane  # noqa: E402

#: the program's spans, by prefix; the worker's are ``trace_rules.json``'s
PROGRAM = ("rabit.", "gbdt.")
#: the stats that are byte counts, summed a span
SUMMED = ("raw", "encoded", "bytes", "nbytes")
#: where no span is open
NO_SPAN = "no_span"


def read(path: str, rules: dict = xplane.RULES) -> dict:
    """``{"devices": {plane: [(start_ns, end_ns)]}, "threads": {line:
    [(name, start_ns, end_ns, stats)]}}``: when each device ran an
    operation, and the worker's and the program's spans by host thread."""
    from jax.profiler import ProfileData

    worker = set(rules["spans"])
    raw = {"devices": {}, "threads": {}}
    for plane in ProfileData.from_file(path).planes:
        if re.search(rules["device_plane"], plane.name):
            busy = raw["devices"].setdefault(plane.name, [])
            for line in plane.lines:
                if any(re.search(p, line.name) for p in rules["op_lines"]):
                    busy.extend((e.start_ns, e.start_ns + e.duration_ns)
                                for e in line.events if e.duration_ns > 0)
        if re.search(rules["host_plane"], plane.name):
            for i, line in enumerate(plane.lines):
                kept = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         {k: v for k, v in e.stats if k in SUMMED
                          and isinstance(v, (int, float))})
                        for e in line.events
                        if e.name in worker or e.name.startswith(PROGRAM)]
                if kept:
                    raw["threads"][f"{line.name}#{i}"] = kept
    return raw


def nest(spans) -> list:
    """``[name, start, end, stats, depth, self_ns]`` for the properly
    nested spans of one thread, outermost depth 0."""
    out, stack = [], []
    for name, a, b, stats in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][2] <= a:
            stack.pop()
        if stack:
            stack[-1][5] -= min(b, stack[-1][2]) - a
        row = [name, a, b, stats, len(stack), b - a]
        stack.append(row)
        out.append(row)
    return out


def timeline(rows, lo, hi) -> list:
    """Disjoint ``[start, end, owner]`` covering ``[lo, hi]``: the deepest
    span open in each stretch, of two equally deep the later one."""
    points = sorted({lo, hi} | {p for r in rows for p in (r[1], r[2])
                                if lo < p < hi})
    out = []
    for a, b in zip(points, points[1:]):
        over = [r for r in rows if r[1] <= a and r[2] >= b]
        owner = max(over, key=lambda r: (r[4], r[1]))[0] if over else NO_SPAN
        if out and out[-1][2] == owner:
            out[-1][1] = b
        else:
            out.append([a, b, owner])
    return out


def share(idle, line) -> dict:
    """The length of the sorted disjoint ``idle`` intervals that falls to
    each owner of the timeline ``line``."""
    out, i = {}, 0
    for a, b in idle:
        while i < len(line) and line[i][1] <= a:
            i += 1
        j = i
        while j < len(line) and line[j][0] < b:
            over = min(b, line[j][1]) - max(a, line[j][0])
            if over > 0:
                out[line[j][2]] = out.get(line[j][2], 0) + over
            j += 1
    return out


def reduce(raw: dict, rules: dict = xplane.RULES) -> dict | None:
    """The table the span readers read; ``None`` where the trace holds no
    device, or no ``round`` and ``checkpoint`` to bound the window."""
    everything = [s for spans in raw["threads"].values() for s in spans]
    opens = [s for s in everything if s[0] == rules["window_opens_with"]]
    closes = [s for s in everything if s[0] == rules["window_closes_with"]]
    if not raw["devices"] or not opens or not closes:
        return None
    lo, hi = min(s[1] for s in opens), max(s[2] for s in closes)
    if hi <= lo:
        return None
    rows = [r for spans in raw["threads"].values() for r in nest(
        [(n, max(a, lo), min(b, hi), st) for n, a, b, st in spans
         if b > lo and a < hi])]
    spans: dict = {}
    for name, a, b, stats, _depth, self_ns in rows:
        cell = spans.setdefault(name, {"count": 0, "total_s": 0.0,
                                       "self_s": 0.0})
        cell["count"] += 1
        cell["total_s"] += (b - a) / 1e9
        cell["self_s"] += self_ns / 1e9
        for k, v in stats.items():
            cell[k] = cell.get(k, 0) + v
    line = timeline(rows, lo, hi)
    n_dev = len(raw["devices"])
    idle_by: dict = {}
    idle = 0.0
    for busy in raw["devices"].values():
        covered = xplane.union((max(a, lo), min(b, hi)) for a, b in busy
                               if b > lo and a < hi)
        gaps = xplane.subtract([[lo, hi]], covered)
        idle += xplane.length(gaps)
        for owner, ns in share(gaps, line).items():
            idle_by[owner] = idle_by.get(owner, 0.0) + ns
    s = 1e9 * n_dev
    return {
        "devices": n_dev,
        "window_s": (hi - lo) / 1e9,
        "rounds": len([e for e in opens if e[1] >= lo and e[2] <= hi]),
        "idle_s": idle / s,
        "spans": spans,
        "idle_by_span": dict(sorted(((k, v / s) for k, v in idle_by.items()),
                                    key=lambda kv: -kv[1])),
    }


def program_spans(t: dict | None) -> bool:
    """Whether the program itself wrote into this trace (a program from
    before ``obs.span`` has not: its readers then read nothing)."""
    return bool(t) and any(n.startswith(PROGRAM) for n in t["spans"])


def per_round_ms(t: dict | None, *names: str) -> float | None:
    """Mean time a traced round inside the named spans together; ``None``
    where none of them is in the trace."""
    if not t or not t["rounds"] or not any(n in t["spans"] for n in names):
        return None
    total = sum(t["spans"][n]["total_s"] for n in names if n in t["spans"])
    return 1e3 * total / t["rounds"]


def per_call_ms(t: dict | None, name: str) -> float | None:
    if not t or name not in t["spans"]:
        return None
    cell = t["spans"][name]
    return 1e3 * cell["total_s"] / cell["count"]


# -- finding and reading a run's trace ----------------------------------------

def find(run_dir: Path) -> str | None:
    """The newest life's raw trace under a run's directory."""
    found = glob.glob(str(run_dir / "trace*" / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))

    def life(p):
        m = re.search(r"trace(\d+)", Path(p).parts[len(run_dir.parts)])
        return (int(m.group(1)) if m else -1, p)

    return max(found, key=life) if found else None


def rules_of(run_dir: Path) -> dict:
    """The rules the run's worker reduced its trace with: a rehearsal on
    the CPU names other planes (``spec.json``, written by ``run.py``)."""
    try:
        spec = json.loads((run_dir / "spec.json").read_text())
    except (OSError, ValueError):
        return dict(xplane.RULES)
    return {**xplane.RULES, **((spec.get("rehearse") or {}).get("trace_rules")
                               or {})}


_TABLES: dict = {}   # path -> table: the readers of one run share one read


def table_of(run_dir: Path) -> dict | None:
    path = find(run_dir)
    if path is None:
        return None
    if path not in _TABLES:
        r = subprocess.run(
            [sys.executable, str(Path(__file__).resolve()), path,
             json.dumps(rules_of(run_dir))],
            capture_output=True, text=True, timeout=900,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        if r.returncode != 0:
            print(f"harness/spans.py: could not read {path}:\n"
                  f"{r.stderr[-2000:]}", file=sys.stderr, flush=True)
            _TABLES[path] = None
        else:
            _TABLES[path] = json.loads(r.stdout.strip().splitlines()[-1])
    return _TABLES[path]


def table(ev: dict) -> dict | None:
    """The span table of the run whose evidence this is."""
    return table_of(ROOT / ".bench_runs" / ev["cell"]["name"])


def main(argv) -> int:
    rules = json.loads(argv[2]) if len(argv) > 2 else xplane.RULES
    print(json.dumps(reduce(read(argv[1], rules), rules)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
