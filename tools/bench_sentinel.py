#!/usr/bin/env python
"""Bench regression sentinel — the trajectory's high-water gate.

The repo's perf evidence is a trajectory of driver runs: ``BENCH_rNN.json``
(the gbdt macro-bench, one record per run), ``MULTICHIP_rNN.json`` (the
8-device smoke), and the ``RESULTS/`` snapshots (speed tables, failover
drills).  A gate must read the WHOLE trajectory, not the last record: a
benchmark that silently falls back from the TPU backend to CPU leaves
records that individually "pass" (rc 0, a plausible rounds/s number)
while the TPU capability goes dark with nobody flagging it.  ``bench.py``
no longer has such a fallback; this sentinel still makes that shape a
first-class failure:

* **high-water tracking** — per metric, per platform, the best value
  ever measured and the run that measured it;
* **drop rule** — the latest sample on a platform fell more than
  ``--tolerance`` (default 20%) below that platform's high-water mark;
* **dark rule** — the platform holding a metric's global high-water has
  produced no sample for the last ``--dark-after`` runs while a sibling
  platform still reports the metric (the silent-fallback shape);
* **failing rule** — the newest run exited non-zero or parsed to nothing.

``bench.py`` stamps the verdict into every new driver record
(``RABIT_BENCH_SENTINEL=0`` skips); standalone CLI::

    python tools/bench_sentinel.py [--root DIR] [--json] \
        [--tolerance 0.2] [--dark-after 2] [--strict]

Exit status is 0 unless ``--strict`` is given and a regression is
flagged — the sentinel reports by default, it only gates on request.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

#: Verdict record schema (bump on incompatible change).
SENTINEL_SCHEMA = 1

_RUN_RE = re.compile(r"^BENCH_r(\d+)\.json$")
_MULTI_RE = re.compile(r"^MULTICHIP_r(\d+)\.json$")


def _load(path: str):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def collect_runs(root: str) -> list[dict]:
    """Every BENCH_rNN.json under ``root``, ordered by run number; each
    entry is ``{"n", "rc", "parsed"}`` (missing fields defaulted)."""
    runs = []
    try:
        names = sorted(os.listdir(root))
    except OSError:
        return []
    for name in names:
        m = _RUN_RE.match(name)
        if not m:
            continue
        doc = _load(os.path.join(root, name))
        if not isinstance(doc, dict):
            continue
        runs.append({"n": int(doc.get("n", m.group(1))),
                     "rc": int(doc.get("rc", 0) or 0),
                     "parsed": doc.get("parsed")})
    runs.sort(key=lambda r: r["n"])
    return runs


def collect_results(root: str) -> dict:
    """Informational context from the RESULTS/ snapshots and the
    multichip smoke — carried in the verdict, not rule inputs (they are
    single snapshots, not a trajectory)."""
    out: dict = {}
    speed_path = os.path.join(root, "RESULTS", "speed.jsonl")
    best: dict[str, float] = {}
    try:
        with open(speed_path) as f:
            for line in f:
                try:
                    row = json.loads(line)
                except ValueError:
                    continue
                op, mbs = row.get("op"), row.get("mb_per_s")
                if isinstance(op, str) and isinstance(mbs, (int, float)):
                    best[op] = max(best.get(op, 0.0), float(mbs))
    except OSError:
        pass
    if best:
        out["speed_mb_per_s"] = {op: round(v, 2)
                                 for op, v in sorted(best.items())}
    multi_ok = multi_total = 0
    try:
        names = sorted(os.listdir(root))
    except OSError:
        names = []
    for name in names:
        if not _MULTI_RE.match(name):
            continue
        doc = _load(os.path.join(root, name))
        if isinstance(doc, dict) and not doc.get("skipped"):
            multi_total += 1
            multi_ok += 1 if doc.get("ok") else 0
    if multi_total:
        out["multichip"] = {"ok": multi_ok, "runs": multi_total}
    return out


def _series(runs: list[dict]) -> dict[str, dict[str, list[tuple[int, float]]]]:
    """metric -> platform -> [(run_n, value), ...] in run order."""
    table: dict[str, dict[str, list[tuple[int, float]]]] = {}
    for run in runs:
        parsed = run["parsed"]
        if not isinstance(parsed, dict):
            continue
        metric, value = parsed.get("metric"), parsed.get("value")
        platform = str(parsed.get("platform") or "unknown")
        if isinstance(metric, str) and isinstance(value, (int, float)):
            table.setdefault(metric, {}).setdefault(platform, []).append(
                (run["n"], float(value)))
    return table


def verdict(root: str = ".", tolerance: float = 0.2,
            dark_after: int = 2) -> dict:
    """The sentinel's one-call entry point: collect the trajectory,
    apply the rules, return the verdict record ``bench.py`` embeds."""
    runs = collect_runs(root)
    series = _series(runs)
    regressions: list[dict] = []
    metrics: dict[str, dict] = {}
    last_n = runs[-1]["n"] if runs else 0

    for metric, platforms in sorted(series.items()):
        mdoc: dict = {"platforms": {}}
        hw_global, hw_platform = 0.0, None
        for platform, samples in sorted(platforms.items()):
            hw_n, hw = max(samples, key=lambda s: s[1])
            latest_n, latest = samples[-1]
            mdoc["platforms"][platform] = {
                "high_water": hw, "high_water_run": hw_n,
                "latest": latest, "latest_run": latest_n,
                "samples": len(samples),
            }
            if hw > hw_global:
                hw_global, hw_platform = hw, platform
            if latest < (1.0 - tolerance) * hw:
                regressions.append({
                    "kind": "drop", "metric": metric, "platform": platform,
                    "high_water": hw, "high_water_run": hw_n,
                    "latest": latest, "latest_run": latest_n,
                    "tolerance": tolerance,
                })
        mdoc["high_water"] = hw_global
        mdoc["high_water_platform"] = hw_platform
        metrics[metric] = mdoc
        # dark rule: the high-water platform stopped reporting while a
        # sibling platform kept the metric alive (silent fallback)
        if hw_platform is None or len(platforms) < 2:
            continue
        hw_last_n = platforms[hw_platform][-1][0]
        dark_runs = [r["n"] for r in runs
                     if r["n"] > hw_last_n and isinstance(r["parsed"], dict)
                     and r["parsed"].get("metric") == metric]
        if len(dark_runs) >= max(dark_after, 1):
            reg = {
                "kind": "dark", "metric": metric, "platform": hw_platform,
                "high_water": platforms[hw_platform][-1][1],
                "last_seen_run": hw_last_n, "dark_runs": dark_runs,
                "fallback_platforms": sorted(p for p in platforms
                                             if p != hw_platform),
            }
            regressions.append(reg)

    if runs and (runs[-1]["rc"] != 0
                 or not isinstance(runs[-1]["parsed"], dict)):
        regressions.append({"kind": "failing", "run": last_n,
                            "rc": runs[-1]["rc"],
                            "parsed": runs[-1]["parsed"] is not None})

    return {
        "schema": SENTINEL_SCHEMA,
        "runs": len(runs),
        "latest_run": last_n,
        "tolerance": tolerance,
        "dark_after": dark_after,
        "metrics": metrics,
        "results": collect_results(root),
        "regressions": regressions,
        "ok": not regressions,
    }


def _human(doc: dict) -> str:
    lines = [f"bench sentinel: {doc['runs']} run(s), "
             f"{'OK' if doc['ok'] else str(len(doc['regressions'])) + ' regression(s)'}"]
    for metric, mdoc in doc["metrics"].items():
        lines.append(f"  {metric}: high-water {mdoc['high_water']:g} "
                     f"[{mdoc['high_water_platform']}]")
        for platform, p in mdoc["platforms"].items():
            lines.append(f"    {platform}: best {p['high_water']:g} "
                         f"(run {p['high_water_run']}), latest "
                         f"{p['latest']:g} (run {p['latest_run']})")
    for reg in doc["regressions"]:
        if reg["kind"] == "dark":
            lines.append(f"  REGRESSION dark: {reg['metric']} last seen on "
                         f"{reg['platform']} in run {reg['last_seen_run']} "
                         f"(high-water {reg['high_water']:g}); runs "
                         f"{reg['dark_runs']} fell back to "
                         f"{','.join(reg['fallback_platforms'])}")
        elif reg["kind"] == "drop":
            lines.append(f"  REGRESSION drop: {reg['metric']} on "
                         f"{reg['platform']} fell {reg['latest']:g} < "
                         f"{1 - reg['tolerance']:g}x high-water "
                         f"{reg['high_water']:g} (run {reg['high_water_run']})")
        else:
            lines.append(f"  REGRESSION {reg['kind']}: run {reg['run']} "
                         f"rc={reg['rc']}")
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="flag high-water regressions across the BENCH/RESULTS "
                    "trajectory")
    ap.add_argument("--root", default=".",
                    help="repo root holding BENCH_rNN.json and RESULTS/")
    ap.add_argument("--json", action="store_true",
                    help="print the verdict record as JSON")
    ap.add_argument("--tolerance", type=float, default=0.2,
                    help="allowed fraction below a platform high-water "
                         "(default 0.2)")
    ap.add_argument("--dark-after", type=int, default=2,
                    help="trailing runs without a high-water-platform "
                         "sample that count as gone dark (default 2)")
    ap.add_argument("--strict", action="store_true",
                    help="exit 1 when any regression is flagged")
    args = ap.parse_args(argv)
    doc = verdict(args.root, tolerance=args.tolerance,
                  dark_after=args.dark_after)
    print(json.dumps(doc, indent=1, sort_keys=True) if args.json
          else _human(doc))
    return 1 if (args.strict and not doc["ok"]) else 0


if __name__ == "__main__":
    sys.exit(main())
