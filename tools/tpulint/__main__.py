"""CLI: ``python -m tools.tpulint [--root DIR] [--only FAMILY]
[--timings] [--json [PATH]] [--write-baseline] [--prune]``.

Exit status: 0 — clean (every finding baselined with a justification);
1 — new findings; 2 — malformed baseline or internal error.  Stale
baseline entries (suppressing nothing) are reported but do not fail the
run — ``--prune`` rewrites the baseline without them (justifications of
live entries preserved).

``--only FAMILY`` runs one family (see FAMILIES for the names) — the
debugging loop for a single rule.  Stale-entry reporting is skipped
under ``--only`` (the other families' baseline entries would all read
as stale), and ``--prune``/``--write-baseline`` refuse to combine with
it for the same reason.

``--timings`` prints per-family wall time after the summary; lint.sh
passes it so the 15s budget failure names the family that blew it.

``--json`` alone prints the machine-readable findings document on
stdout; ``--json out.json`` writes it to a file alongside the normal
human output, so CI can diff finding sets across commits.  The
document's ``new`` entries carry rule/path/line/message/fingerprint;
``suppressed``/``stale_baseline`` carry fingerprints; ``families``
carries per-family finding/new counts and seconds.

``--root`` points at an alternate tree with the repo's layout (used by
the fixture tests in tests/test_tpulint.py); the default is this repo.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

from tools.tpulint import (
    callgraph,
    configkeys,
    determinism,
    journalcov,
    lockorder,
    locks,
    ownership,
    reactor,
    registry,
    resources,
    servingparity,
    streammetrics,
    wire,
)
from tools.tpulint.core import (
    BaselineError,
    Finding,
    iter_python_files,
    load_baseline,
    rel,
    save_baseline,
    write_baseline,
)

#: trees that are lint *inputs* but not part of the product surface
_EXCLUDE_PARTS = ("data",)  # tests/data: fixture trees with seeded bugs


class _Ctx:
    """Shared per-run inputs: the graph families split one whole-repo
    call-graph build (the single most expensive step), built on first
    use so ``--only locks`` never pays for it."""

    def __init__(self, root: Path):
        self.root = root
        self._graph: callgraph.CallGraph | None = None

    @property
    def graph(self) -> callgraph.CallGraph:
        if self._graph is None:
            files = iter_python_files(self.root, ["rabit_tpu/**/*.py"],
                                      exclude_parts=_EXCLUDE_PARTS)
            self._graph = callgraph.CallGraph.build(files, self.root)
        return self._graph


def _fam_locks(ctx: _Ctx) -> list[Finding]:
    files = iter_python_files(ctx.root, ["rabit_tpu/**/*.py"])
    return locks.check_locks(files, ctx.root)


def _fam_events(ctx: _Ctx) -> list[Finding]:
    events_py = ctx.root / "rabit_tpu" / "obs" / "events.py"
    kinds = registry.load_kinds(events_py)
    emit_files = iter_python_files(ctx.root, ["rabit_tpu/**/*.py"])
    consume_files = iter_python_files(
        ctx.root,
        ["rabit_tpu/obs/**/*.py", "rabit_tpu/tracker/*.py",
         "tools/*.py", "tests/**/*.py"],
        exclude_parts=_EXCLUDE_PARTS)
    emitted = registry.collect_emitted(emit_files, ctx.root)
    consumed = registry.collect_consumed(consume_files, ctx.root)
    local = registry.collect_emitted(
        [p for p in consume_files if p not in set(emit_files)], ctx.root)
    return registry.check_event_kinds(
        kinds, emitted, consumed, local_emitted=local,
        events_py_rel=rel(events_py, ctx.root))


def _fam_config(ctx: _Ctx) -> list[Finding]:
    config_py = ctx.root / "rabit_tpu" / "config.py"
    defaults_keys, env_values, dmlc = configkeys.declared_keys(config_py)
    py_read_files = iter_python_files(
        ctx.root,
        ["rabit_tpu/**/*.py", "tools/*.py", "tests/**/*.py",
         "guide/**/*.py"],
        exclude_parts=_EXCLUDE_PARTS)
    native_files = [p for p in
                    sorted((ctx.root / "native").glob("**/*"))
                    if p.suffix in (".cc", ".h") and p.is_file()]
    return configkeys.check_config_keys(
        declared=defaults_keys | env_values,
        dmlc_declared=dmlc,
        python_reads=configkeys.collect_python_reads(py_read_files,
                                                     ctx.root),
        native_reads=configkeys.collect_native_reads(native_files,
                                                     ctx.root),
        documented=configkeys.doc_keys(ctx.root / "doc" / "parameters.md"),
        defaults_keys=defaults_keys,
        config_py_rel=rel(config_py, ctx.root),
        parameters_md_rel="doc/parameters.md",
    )


def _fam_stream(ctx: _Ctx) -> list[Finding]:
    stream_py = ctx.root / "rabit_tpu" / "obs" / "stream.py"
    emit_files = iter_python_files(ctx.root, ["rabit_tpu/**/*.py"])
    return streammetrics.check_stream_metrics(
        streammetrics.load_stream_metrics(stream_py),
        streammetrics.collect_stream_calls(emit_files, ctx.root),
        stream_py_rel=rel(stream_py, ctx.root))


def _fam_wire(ctx: _Ctx) -> list[Finding]:
    protocol_py = ctx.root / "rabit_tpu" / "tracker" / "protocol.py"
    tracker_py = ctx.root / "rabit_tpu" / "tracker" / "tracker.py"
    comm_h = ctx.root / "native" / "src" / "comm.h"
    comm_cc = ctx.root / "native" / "src" / "comm.cc"
    struct_files = iter_python_files(ctx.root, ["rabit_tpu/**/*.py"])
    return wire.check_wire(protocol_py, tracker_py, comm_h,
                           struct_files, ctx.root, comm_cc=comm_cc)


def _fam_reactor(ctx: _Ctx) -> list[Finding]:
    return reactor.check_reactor(ctx.graph, ctx.root)


def _fam_journal(ctx: _Ctx) -> list[Finding]:
    return journalcov.check_journal(ctx.graph, ctx.root)


def _fam_lockorder(ctx: _Ctx) -> list[Finding]:
    return lockorder.check_lock_order(ctx.graph, ctx.root)


def _fam_ownership(ctx: _Ctx) -> list[Finding]:
    return ownership.check_ownership(ctx.graph, ctx.root)


def _fam_resources(ctx: _Ctx) -> list[Finding]:
    # builds its OWN graph over a wider scope (tools/) — adding
    # that tree to the shared graph would perturb the v2 families'
    # private-name fallback resolution.
    return resources.check_resources(ctx.root)


def _fam_determinism(ctx: _Ctx) -> list[Finding]:
    return determinism.check_determinism(ctx.graph, ctx.root)


def _fam_parity(ctx: _Ctx) -> list[Finding]:
    return servingparity.check_parity(ctx.graph, ctx.root)


#: default-pass order: cheap lexical families first, then the families
#: sharing the whole-repo call graph (built once, on first use).
FAMILIES: dict[str, object] = {
    "locks": _fam_locks,
    "events": _fam_events,
    "config": _fam_config,
    "stream-metrics": _fam_stream,
    "wire": _fam_wire,
    "reactor": _fam_reactor,
    "journal": _fam_journal,
    "lock-order": _fam_lockorder,
    "ownership": _fam_ownership,
    "resources": _fam_resources,
    "determinism": _fam_determinism,
    "serving-parity": _fam_parity,
}


def run(root: Path, only: str | None = None
        ) -> tuple[dict[str, list[Finding]], dict[str, float]]:
    """Check families over a repo-layout tree rooted at ``root``:
    ordered ``{family: findings}`` plus per-family wall seconds."""
    ctx = _Ctx(root)
    by_family: dict[str, list[Finding]] = {}
    seconds: dict[str, float] = {}
    for name, fn in FAMILIES.items():
        if only is not None and name != only:
            continue
        t0 = time.perf_counter()
        fs = fn(ctx)
        seconds[name] = time.perf_counter() - t0
        fs.sort(key=lambda f: (f.path, f.line, f.rule))
        by_family[name] = fs
    return by_family, seconds


def _json_doc(new, suppressed, stale, by_family, seconds,
              new_fps: set) -> dict:
    return {
        "new": [f.__dict__ | {"fingerprint": f.fingerprint} for f in new],
        "suppressed": [f.fingerprint for f in suppressed],
        "stale_baseline": stale,
        "counts": {"new": len(new), "suppressed": len(suppressed),
                   "stale": len(stale)},
        "families": {
            name: {"findings": len(fs),
                   "new": sum(1 for f in fs if f.fingerprint in new_fps),
                   "seconds": round(seconds[name], 3)}
            for name, fs in by_family.items()},
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="tools.tpulint",
        description="project-specific static analysis "
                    "(doc/static_analysis.md)")
    ap.add_argument("--root", default=None,
                    help="repo-layout tree to lint (default: this repo)")
    ap.add_argument("--baseline", default=None,
                    help="baseline file (default: ROOT/tools/tpulint/"
                         "baseline.json)")
    ap.add_argument("--only", default=None, choices=sorted(FAMILIES),
                    metavar="FAMILY",
                    help="run one family: " + ", ".join(FAMILIES))
    ap.add_argument("--timings", action="store_true",
                    help="print per-family wall time after the summary")
    ap.add_argument("--write-baseline", action="store_true",
                    help="write current findings as TODO-justified "
                         "baseline entries and exit (the tool refuses to "
                         "load TODOs — fill in each justification)")
    ap.add_argument("--prune", action="store_true",
                    help="rewrite the baseline without stale entries "
                         "(live justifications preserved) and exit")
    ap.add_argument("--json", nargs="?", const="-", default=None,
                    metavar="PATH",
                    help="machine-readable findings: bare --json prints "
                         "the document on stdout, --json PATH writes it "
                         "to a file alongside the normal output")
    args = ap.parse_args(argv)

    if args.only and (args.prune or args.write_baseline):
        print("tpulint: --only cannot combine with --prune/"
              "--write-baseline (a single family's view would drop or "
              "overwrite every other family's baseline entries)",
              file=sys.stderr)
        return 2

    root = Path(args.root).resolve() if args.root else \
        Path(__file__).resolve().parents[2]
    baseline_path = Path(args.baseline) if args.baseline else \
        root / "tools" / "tpulint" / "baseline.json"

    by_family, seconds = run(root, only=args.only)
    findings = sorted((f for fs in by_family.values() for f in fs),
                      key=lambda f: (f.path, f.line, f.rule))

    if args.write_baseline:
        write_baseline(baseline_path, findings)
        print(f"tpulint: wrote {len(findings)} TODO suppression(s) to "
              f"{baseline_path}; fill in each justification before the "
              f"baseline will load")
        return 0

    try:
        baseline = load_baseline(baseline_path)
    except BaselineError as exc:
        print(f"tpulint: {exc}", file=sys.stderr)
        return 2

    new = [f for f in findings if f.fingerprint not in baseline]
    suppressed = [f for f in findings if f.fingerprint in baseline]
    stale = [] if args.only else \
        sorted(set(baseline) - {f.fingerprint for f in findings})

    if args.prune:
        kept = {fp: why for fp, why in baseline.items() if fp not in stale}
        save_baseline(baseline_path, kept)
        print(f"tpulint: pruned {len(stale)} stale baseline entr"
              f"{'y' if len(stale) == 1 else 'ies'} "
              f"({len(kept)} kept) in {baseline_path}")
        for fp in stale:
            print(f"tpulint: pruned: {fp}")
        return 0

    doc = _json_doc(new, suppressed, stale, by_family, seconds,
                    {f.fingerprint for f in new})
    if args.json == "-":
        print(json.dumps(doc, indent=1))
        return 1 if new else 0
    if args.json is not None:
        Path(args.json).write_text(json.dumps(doc, indent=1) + "\n",
                                   encoding="utf-8")
    for f in new:
        print(f.render())
    for fp in stale:
        print(f"tpulint: stale baseline entry (suppresses nothing): "
              f"{fp}")
    summary = (f"tpulint: {len(new)} new finding(s), "
               f"{len(suppressed)} baselined, {len(stale)} stale "
               f"baseline entr{'y' if len(stale) == 1 else 'ies'}")
    print(summary)
    if args.timings:
        for name, sec in seconds.items():
            print(f"tpulint: timing: {name:14} {sec:6.2f}s "
                  f"({len(by_family[name])} finding(s))")
    return 1 if new else 0


if __name__ == "__main__":
    sys.exit(main())
