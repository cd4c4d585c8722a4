"""The span table of a traced run, by hand: ``python
benchmark/tools/spans_dump.py [<run directory or file.xplane.pb>]`` prints
each span of the window with its count, its time and self time a traced
round, the device's idle time that falls to it, and its byte counts — what
``PERF.md`` §5 is filled from.  With no argument it takes every cell's
directory under ``.bench_runs/``; ``--json`` prints the tables as they are.
Run it with ``JAX_PLATFORMS=cpu`` anywhere but in the process that holds
the chip."""

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from harness import spans, xplane  # noqa: E402


def show(title: str, t: dict | None) -> None:
    print(f"== {title}")
    if t is None:
        print("   no trace, or no round and checkpoint span in it")
        return
    n = max(t["rounds"], 1)
    print(f"   {t['devices']} device(s), {t['rounds']} rounds, window "
          f"{1e3 * t['window_s']:.3f} ms, idle {1e3 * t['idle_s']:.3f} ms "
          f"({100 * t['idle_s'] / t['window_s']:.2f} %)")
    print(f"   {'span':32} {'count':>6} {'ms/round':>10} {'self':>10} "
          f"{'idle under':>11}  bytes")
    names = sorted(set(t["spans"]) | set(t["idle_by_span"]),
                   key=lambda k: -t["spans"].get(k, {}).get("total_s", 0.0))
    for name in names:
        cell = t["spans"].get(name, {"count": 0, "total_s": 0.0, "self_s": 0.0})
        stats = {k: v for k, v in cell.items()
                 if k not in ("count", "total_s", "self_s")}
        print(f"   {name:32} {cell['count']:>6} "
              f"{1e3 * cell['total_s'] / n:>10.3f} "
              f"{1e3 * cell['self_s'] / n:>10.3f} "
              f"{1e3 * t['idle_by_span'].get(name, 0.0) / n:>11.3f}  "
              f"{stats or ''}")
    print(f"   idle by span sums to "
          f"{1e3 * sum(t['idle_by_span'].values()):.3f} ms")


def main(argv) -> int:
    as_json = "--json" in argv
    where = [Path(a) for a in argv[1:] if a != "--json"] or sorted(
        p for p in (spans.ROOT / ".bench_runs").glob("*") if p.is_dir())
    tables = {}
    for p in where:
        if p.is_dir():
            tables[str(p)] = spans.table_of(p)
        else:
            tables[str(p)] = spans.reduce(spans.read(str(p), xplane.RULES))
    if as_json:
        print(json.dumps(tables, indent=1))
    else:
        for title, t in tables.items():
            show(title, t)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
