"""The hops of a traced run, by hand: ``python benchmark/tools/hops_dump.py
[<run directory or file.xplane.pb>]`` prints one row a hop — the traced
round, the level, the payload, the five phases of ``harness/hops.py``'s
picture, the hop as the device sees it (T3 - T0), its device operations'
own time and what the device ran beside the transfer — then, a level, each
phase's mean and its range over the traced rounds, and the four copies'
means: which hop is slow in a slow round, and in which phase
(``ROADMAP.md`` S1).  The first line says how far the device's clock was
moved to lie beside the host's.  With no argument it takes every cell's
directory under ``.bench_runs/``; ``--json`` prints the tables as they are.
Run it with ``JAX_PLATFORMS=cpu`` anywhere but in the process that holds
the chip."""

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from harness import hops, xplane  # noqa: E402

COLUMNS = hops.PHASES + ("hop_s", "device_s", "beside_s")
HEAD = ("to_host", "copy", "engine", "other", "to_device", "T3-T0",
        "dev ops", "beside")


def level_name(tag) -> str:
    """The span's ``level`` is the round's tag: ``2**level``, -1 the leaves."""
    if not isinstance(tag, int) or tag == 0:
        return str(tag)
    return "leaves" if tag < 0 else f"d{tag.bit_length() - 1}"


def ms(v) -> str:
    return f"{'-':>9}" if v is None else f"{1e3 * v:>9.3f}"


def show(title: str, t: dict | None) -> None:
    print(f"== {title}")
    if t is None:
        print("   no trace, or no round, checkpoint and gbdt.cross span in it")
        return
    rows = t["hops"]
    print(f"   {t['devices']} device(s), {t['rounds']} rounds, window "
          f"{1e3 * t['window_s']:.3f} ms, {len(rows)} hops, {t['dropped']} "
          f"dropped, {t['device_ops']} device operations of "
          f"{1e3 * t['device_s']:.3f} ms, the device's clock moved "
          f"{1e3 * t['clock_shift_s']:.3f} ms later; times in ms")
    head = "".join(f"{h:>10}" for h in HEAD)
    print(f"   {'round':>5} {'level':>6} {'KB':>9}{head}")
    for r in rows:
        kb = "-" if r["nbytes"] is None else f"{r['nbytes'] / 1e3:.1f}"
        print(f"   {r['round']!s:>5} {level_name(r['level']):>6} {kb:>9}"
              + "".join(" " + ms(r.get(c)) for c in COLUMNS))
    print("   by level: mean (range over the rounds)")
    print(f"   {'level':>6} {'n':>3}" + "".join(f"{h:>20}" for h in HEAD))
    for level, group in hops.by_level(rows).items():
        cells = []
        for c in COLUMNS:
            got = [r[c] for r in group if r.get(c) is not None]
            cells.append(f"{'-':>20}" if not got else
                         f"{1e3 * sum(got) / len(got):>11.3f}"
                         f" ({1e3 * (max(got) - min(got)):>6.3f})")
        print(f"   {level_name(level):>6} {len(group):>3}" + "".join(cells))
    whole = [f"{1e3 * sum(r.get(c) or 0.0 for r in rows) / max(t['rounds'], 1):>10.3f}"
             for c in COLUMNS]
    print("   a round, all hops:   " + "".join(whole))
    print("   the copies by level, mean:"
          + "".join(f"{n:>26}" for n in hops.COPIES))
    for level, group in hops.by_level(rows).items():
        cells = [[r["spans"][n] for r in group if n in r["spans"]]
                 for n in hops.COPIES]
        print(f"   {level_name(level):>6} {len(group):>3}" + 18 * " " + "".join(
            f"{'-':>26}" if not got else f"{1e3 * sum(got) / len(got):>26.3f}"
            for got in cells))
    jitter = hops.jitter_ms(t)
    print("   jitter (the widest range of T3-T0 at one level): "
          + ("-" if jitter is None else f"{jitter:.3f} ms"))


def main(argv) -> int:
    as_json = "--json" in argv
    where = [Path(a) for a in argv[1:] if a != "--json"] or sorted(
        p for p in (hops.spans.ROOT / ".bench_runs").glob("*") if p.is_dir())
    tables = {}
    for p in where:
        if p.is_dir():
            tables[str(p)] = hops.table_of(p)
        else:
            tables[str(p)] = hops.reduce(hops.read(str(p), xplane.RULES))
    if as_json:
        print(json.dumps(tables, indent=1))
    else:
        for title, t in tables.items():
            show(title, t)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
