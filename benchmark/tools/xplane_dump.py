"""Look at a trace by hand: ``python benchmark/tools/xplane_dump.py
<file.xplane.pb> [events-per-line]`` prints every plane, every line with
its number of events, and the first events of each with their stats — what
to read before changing ``harness/trace_rules.json`` or a reader's name
patterns.  Needs only ``jax.profiler.ProfileData``; run it with
``JAX_PLATFORMS=cpu`` anywhere but in the process that holds the chip."""

import sys


def main() -> int:
    from jax.profiler import ProfileData

    path = sys.argv[1]
    first = int(sys.argv[2]) if len(sys.argv) > 2 else 8
    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", repr(line.name), len(events))
            for e in events[:first]:
                stats = {k: (v if len(str(v)) < 80 else str(v)[:80] + "...")
                         for k, v in e.stats}
                print(f"    {e.name!r} start_ns={e.start_ns:.0f} "
                      f"dur_ns={e.duration_ns:.0f} {stats}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
