"""``resource-leak`` / ``resource-exc-leak`` / ``resource-self-unreleased``
— every acquired handle reaches its release on every path.

The hazard is the fd budget (doc/scaling.md): the control plane rides
out a ~20k-fd ceiling at world 8192, and the ROADMAP's world-10^5 item
means one leaked socket per wave — or per chaos fault, or per standby
reconnect — is an outage, not a lint nit.  Unjoined non-daemon threads
are the same bug wearing a different hat: they pin interpreter
shutdown and leak their stacks.

Three rules over the dataflow lifecycle analysis
(tools/tpulint/dataflow.py):

* ``resource-leak`` — a normal exit (fallthrough or ``return``) is
  reachable with the handle still held;
* ``resource-exc-leak`` — normal paths release, but an intervening
  call can raise past the release with no ``with``/``finally``/handler
  covering the handle (the fix is a context manager or a
  ``try/finally``);
* ``resource-self-unreleased`` — the handle escapes into the instance
  (``self.attr = sock``, ``self._threads.append(t)``) and NO method of
  the class (or its MRO/subclasses) ever releases that attribute —
  ownership transferred to a container that never discharges it.

Escapes transfer the obligation, not void it: a returned handle is the
caller's problem (and the caller's acquire is tracked at ITS call
site); a handle passed into another call is assumed handed off.
``Thread(daemon=True)`` (or ``t.daemon = True``) is exempt — daemon
threads are fire-and-forget by design throughout the tracker.

Scope: the fd-budget-critical trees the ISSUE names —
tracker/relay/elastic/service/ha/chaos — plus tools/
(the expected leak crop lives in chaos/bench helpers).
"""

from __future__ import annotations

import ast
from pathlib import Path

from tools.tpulint import dataflow
from tools.tpulint.callgraph import CallGraph, ClassInfo
from tools.tpulint.core import Finding, iter_python_files

RULE_LEAK = "resource-leak"
RULE_EXC = "resource-exc-leak"
RULE_SELF = "resource-self-unreleased"

#: the fd-budget-critical surface (plus the helper trees the crop
#: historically lands in)
GLOBS = [
    "rabit_tpu/tracker/**/*.py",
    "rabit_tpu/relay/**/*.py",
    "rabit_tpu/elastic/**/*.py",
    "rabit_tpu/service/**/*.py",
    "rabit_tpu/ha/**/*.py",
    "rabit_tpu/chaos.py",
    "tools/*.py",
]


def _short(fi) -> str:
    return f"{fi.cls}.{fi.name}" if fi.cls else fi.name


def _self_attr_releases(node: ast.AST, release: frozenset) -> set[str]:
    """Instance attributes released anywhere under ``node``:
    ``self.X.close()`` (or through ``.pop()`` etc.), ``with self.X``,
    ``for t in self.X: t.join()``, the same comprehension-shaped, or
    ``self.X`` handed to another call (benefit of the doubt)."""
    out: set[str] = set()

    def self_attrs_in(e: ast.AST) -> set[str]:
        return {n.attr for n in dataflow.shallow_walk(e)
                if isinstance(n, ast.Attribute)
                and isinstance(n.value, ast.Name) and n.value.id == "self"}

    for n in dataflow.shallow_walk(node):
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute):
            if n.func.attr in release:
                out |= self_attrs_in(n.func.value)
            # self.X handed off (closer helpers, executor.submit, ...)
            for a in list(n.args) + [kw.value for kw in n.keywords]:
                out |= self_attrs_in(a)
        elif isinstance(n, ast.With):
            for item in n.items:
                out |= self_attrs_in(item.context_expr)
        elif isinstance(n, ast.Assign):
            # chan, self._chan = self._chan, None — the handle moved to
            # a local whose release the lifecycle analyzer tracks
            if any(isinstance(t, ast.Name) or
                   (isinstance(t, (ast.Tuple, ast.List)) and
                    any(isinstance(e, ast.Name) for e in t.elts))
                   for t in n.targets):
                out |= self_attrs_in(n.value)
        elif isinstance(n, ast.For) and isinstance(n.target, ast.Name):
            t = n.target.id
            for c in dataflow.shallow_walk(ast.Module(body=n.body,
                                                      type_ignores=[])):
                if isinstance(c, ast.Call) \
                        and isinstance(c.func, ast.Attribute) \
                        and c.func.attr in release \
                        and t in dataflow.names_in(c.func.value):
                    out |= self_attrs_in(n.iter)
        elif isinstance(n, (ast.ListComp, ast.SetComp, ast.GeneratorExp)):
            if n.generators and isinstance(n.generators[0].target, ast.Name):
                t = n.generators[0].target.id
                for c in ast.walk(n.elt):
                    if isinstance(c, ast.Call) \
                            and isinstance(c.func, ast.Attribute) \
                            and c.func.attr in release \
                            and t in dataflow.names_in(c.func.value):
                        out |= self_attrs_in(n.generators[0].iter)
    return out


def _class_release_scope(graph: CallGraph, info: ClassInfo) -> list:
    """Every method that may discharge this class's teardown
    obligations: its own, inherited ones, and subclass overrides."""
    seen: dict[str, object] = {}
    for c in graph.mro(info) + graph.subclasses.get(info.key, []):
        for m in c.methods.values():
            seen.setdefault(m.qual, m)
    return list(seen.values())


def check_resources(root: Path) -> list[Finding]:
    files = iter_python_files(root, GLOBS, exclude_parts=("data",))
    graph = CallGraph.build(files, root)
    findings: list[Finding] = []

    # stored-handle ledger: class key -> attr -> (kind, line, module)
    stored: dict[str, dict[str, tuple[str, int, str]]] = {}

    for qual in sorted(graph.funcs):
        fi = graph.funcs[qual]
        short = _short(fi)
        cls_key = f"{fi.module}::{fi.cls}" if fi.cls else None

        _local, self_acqs = dataflow.find_acquires(fi.node)
        for sa in self_acqs:
            if sa.daemon:
                continue
            if cls_key is not None:
                stored.setdefault(cls_key, {}).setdefault(
                    sa.attr, (sa.kind, sa.line, fi.module))

        for lc in dataflow.analyze_lifecycles(fi.node):
            acq = lc.acquire
            if lc.escaped:
                if cls_key is not None:
                    for attr in lc.self_attrs:
                        stored.setdefault(cls_key, {}).setdefault(
                            attr, (acq.kind, acq.line, fi.module))
                continue
            release = "/".join(sorted(dataflow.RELEASE_METHODS[acq.kind]))
            if lc.normal_leak is not None:
                findings.append(Finding(
                    rule=RULE_LEAK, path=fi.module, line=acq.line,
                    message=(f"{acq.kind} {acq.var!r} acquired in {short} "
                             f"never reaches {release}() on the path "
                             f"exiting at line {lc.normal_leak} — close "
                             f"it or transfer ownership"),
                    token=f"{short}:{acq.var}:{acq.kind}"))
            elif lc.exc_leak is not None:
                findings.append(Finding(
                    rule=RULE_EXC, path=fi.module, line=acq.line,
                    message=(f"{acq.kind} {acq.var!r} acquired in {short} "
                             f"leaks if line {lc.exc_leak} raises — no "
                             f"with/finally covers the exception exit; "
                             f"guard the {release}()"),
                    token=f"{short}:{acq.var}:{acq.kind}"))

    for cls_key in sorted(stored):
        info = graph.classes.get(cls_key)
        if info is None:
            continue
        released: set[str] = set()
        for m in _class_release_scope(graph, info):
            for kind in dataflow.RELEASE_METHODS.values():
                released |= _self_attr_releases(m.node, kind)
        for attr in sorted(stored[cls_key]):
            kind, line, module = stored[cls_key][attr]
            if attr in released:
                continue
            release = "/".join(sorted(dataflow.RELEASE_METHODS[kind]))
            findings.append(Finding(
                rule=RULE_SELF, path=module, line=line,
                message=(f"{kind} handle stored on self.{attr} but no "
                         f"method of {info.name} (or its MRO/subclasses) "
                         f"ever calls {release}() on it — the instance "
                         f"owns a handle it never tears down"),
                token=f"{info.name}.{attr}:{kind}"))
    return findings
