"""egpt-check core: the shared machinery every analyzer rides (ISSUE 8).

``scripts/lint_telemetry.py``'s five rules proved AST lints catch real
drift cheaply; this package is that seed grown into the repo's
correctness-tooling layer. One walk parses the runtime tree ONCE into
``Source`` records (path, text, AST, parent links, waivers); each rule
is a ``Rule`` subclass whose ``run(ctx)`` returns ``Finding`` objects
(file:line + message + fix hint). The runner (``run_checks`` /
``scripts/egpt_check.py``) applies waivers, renders text or JSON, and
exits non-zero on unwaived findings — the tier-1 contract is that the
shipped tree is CLEAN (``tests/test_egpt_check.py::test_repo_self_check``).

Waivers are in-source and must carry a justification — the grammar is
``egpt-check: ignore[<rule>] -- <reason>`` in a trailing comment. The
comment lives on the offending line or the line directly above; the
rule id in brackets must name a registered rule (several comma-separate).
A waiver with no ``-- reason`` is itself a finding (rule ``waiver``): an
unexplained suppression is exactly the silent rot this tool exists to
stop.

Annotations the rules read (details in each rule module and in
OBSERVABILITY.md "Static analysis"):

  * ``_GUARDED_BY = {"_attr": "_lock", "_stats": "_lock/w"}`` — class
    attribute mapping guarded attributes to their lock; ``/w`` guards
    writes only (the lock-free-snapshot read pattern).
  * ``_EXTERNAL_LOCK = "Owner._lock"`` — the whole class is serialized
    by its owner's lock (``ContinuousBatcher`` under ``ServingEngine``).
  * ``_HOT_ROOTS = ("step", "_dispatch_segment")`` — dispatch-path roots
    for the host-sync lint's reachability walk.
  * ``# egpt-check: harvest -- reason`` on/above a ``def`` — an
    annotated harvest point where host readbacks are the design.
"""

from __future__ import annotations

import ast
import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

# Trees the suite scans (tests/ stays out on purpose: fixtures and
# private test registries would drown every rule in noise; the telemetry
# fault-coverage rule reads tests/ itself, for arming evidence only).
SCAN_TREES = ("eventgpt_tpu", "scripts")

_WAIVER_RE = re.compile(
    r"#\s*egpt-check:\s*ignore\[([A-Za-z0-9_,\- ]+)\]\s*(?:--\s*(.*))?")
_HARVEST_RE = re.compile(r"#\s*egpt-check:\s*harvest(?:\s*--\s*(.*))?")


@dataclass
class Finding:
    """One violation: ``file:line`` + rule id + message + fix hint."""
    rule: str
    file: str            # repo-relative, '/'-separated
    line: int            # 1-based; 0 = file-level
    message: str
    hint: str = ""
    waived: bool = False
    waiver_reason: str = ""

    def render(self) -> str:
        loc = f"{self.file}:{self.line}" if self.line else self.file
        s = f"{loc}: [{self.rule}] {self.message}"
        if self.hint:
            s += f" (fix: {self.hint})"
        if self.waived:
            s += f" [waived: {self.waiver_reason}]"
        return s

    def as_dict(self) -> Dict:
        return {
            "rule": self.rule, "file": self.file, "line": self.line,
            "message": self.message, "hint": self.hint,
            "waived": self.waived,
            **({"waiver_reason": self.waiver_reason} if self.waived else {}),
        }


@dataclass
class Source:
    """One parsed file of the scanned tree. ``tree`` is None when the
    file does not parse (the runner emits an unparseable finding).
    ``waivers``/``harvests`` are line -> payload maps; a marker on line
    N covers findings on N and N+1 (comment-above style)."""
    rel: str
    path: str
    text: str
    tree: Optional[ast.AST]
    parse_error: str = ""
    waivers: Dict[int, Tuple[Tuple[str, ...], str]] = field(
        default_factory=dict)
    harvests: Dict[int, str] = field(default_factory=dict)
    _parents: Optional[Dict[ast.AST, ast.AST]] = None

    def parents(self) -> Dict[ast.AST, ast.AST]:
        """Child -> parent node map, built lazily once per file."""
        if self._parents is None:
            self._parents = {}
            if self.tree is not None:
                for node in ast.walk(self.tree):
                    for child in ast.iter_child_nodes(node):
                        self._parents[child] = node
        return self._parents

    def line(self, n: int) -> str:
        lines = self.text.splitlines()
        return lines[n - 1] if 1 <= n <= len(lines) else ""


@dataclass
class Context:
    """What every rule gets: the parsed tree plus the repo root (rules
    that need out-of-tree evidence — OBSERVABILITY.md, tests/ — read it
    themselves)."""
    root: str
    sources: List[Source]

    def source(self, rel: str) -> Optional[Source]:
        for s in self.sources:
            if s.rel == rel or s.rel.endswith(rel):
                return s
        return None


#: Every rule id any imported Rule subclass registered — waiver
#: validation checks against THIS set, not the running subset, so a
#: telemetry-only run does not flag a lock waiver as unknown.
KNOWN_RULE_IDS = {"waiver", "parse"}


class Rule:
    """One analyzer. ``id`` names it in waiver comments and reports;
    ``run`` returns findings (waiver application is the runner's)."""

    id: str = ""
    doc: str = ""

    def __init_subclass__(cls, **kw):
        super().__init_subclass__(**kw)
        if getattr(cls, "id", ""):
            KNOWN_RULE_IDS.add(cls.id)

    def run(self, ctx: Context) -> List[Finding]:  # pragma: no cover
        raise NotImplementedError


def _scan_files(root: str) -> List[str]:
    out: List[str] = []
    for scan in SCAN_TREES:
        p = os.path.join(root, scan)
        if os.path.isfile(p):
            out.append(p)
            continue
        for dirpath, dirnames, files in os.walk(p):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            out.extend(os.path.join(dirpath, f) for f in sorted(files)
                       if f.endswith(".py"))
    return sorted(out)


def _scan_markers(src: Source) -> None:
    """Populate the waiver / harvest line maps from the raw text (the
    AST drops comments, so markers are a line-scan)."""
    for i, line in enumerate(src.text.splitlines(), start=1):
        m = _WAIVER_RE.search(line)
        if m is not None:
            rules = tuple(r.strip() for r in m.group(1).split(",")
                          if r.strip())
            reason = (m.group(2) or "").strip()
            src.waivers[i] = (rules, reason)
        h = _HARVEST_RE.search(line)
        if h is not None:
            src.harvests[i] = (h.group(1) or "").strip()


def load_sources(root: str) -> List[Source]:
    """The shared walk: parse every scanned file once; every rule then
    reads the same ``Source`` records."""
    sources: List[Source] = []
    for path in _scan_files(root):
        rel = os.path.relpath(path, root).replace(os.sep, "/")
        with open(path) as f:
            text = f.read()
        try:
            tree = ast.parse(text, rel)
            err = ""
        except SyntaxError as e:
            tree, err = None, str(e)
        src = Source(rel=rel, path=path, text=text, tree=tree,
                     parse_error=err)
        _scan_markers(src)
        sources.append(src)
    return sources


def class_literal(cls: ast.ClassDef, name: str):
    """Pure-literal class attribute ``name`` (``_GUARDED_BY`` /
    ``_HOT_ROOTS`` grammar: ast.literal_eval, no imports). Handles both
    ``X = {...}`` and the dataclass-safe ``X: ClassVar[...] = {...}``.
    Returns (value, lineno) or (None, 0); raises ValueError on a
    non-literal value (the annotation contract is violated)."""
    for node in cls.body:
        tgt = None
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            tgt = node.targets[0].id
        elif isinstance(node, ast.AnnAssign) \
                and isinstance(node.target, ast.Name) \
                and node.value is not None:
            tgt = node.target.id
        if tgt != name:
            continue
        try:
            return ast.literal_eval(node.value), node.lineno
        except (ValueError, SyntaxError):
            raise ValueError(
                f"{name} must be a pure literal (ast.literal_eval)")
    return None, 0


def is_harvest(src: Source, fn: ast.AST) -> Tuple[bool, str]:
    """A function is an annotated harvest point when its ``def`` line,
    the line above it, or the line above its first decorator carries the
    ``# egpt-check: harvest -- reason`` marker."""
    lines = {fn.lineno, fn.lineno - 1}
    deco = getattr(fn, "decorator_list", None)
    if deco:
        lines.add(deco[0].lineno - 1)
    for ln in lines:
        if ln in src.harvests:
            return True, src.harvests[ln]
    return False, ""


def _apply_waivers(sources: Sequence[Source],
                   findings: List[Finding]) -> List[Finding]:
    by_rel = {s.rel: s for s in sources}
    out: List[Finding] = []
    for f in findings:
        src = by_rel.get(f.file)
        if src is not None and f.line:
            for ln in (f.line, f.line - 1):
                w = src.waivers.get(ln)
                if w is not None and f.rule in w[0]:
                    f.waived = True
                    f.waiver_reason = w[1]
                    break
        out.append(f)
    return out


def _waiver_findings(sources: Sequence[Source]) -> List[Finding]:
    """Malformed waivers are findings too: a suppression with no reason
    (or naming no registered rule) must not silently disable a check."""
    out: List[Finding] = []
    for src in sources:
        for ln, (rules, reason) in sorted(src.waivers.items()):
            if not reason:
                out.append(Finding(
                    "waiver", src.rel, ln,
                    "waiver without a justification",
                    hint="write '# egpt-check: ignore[<rule>] -- why it "
                         "is safe'"))
            unknown = [r for r in rules if r not in KNOWN_RULE_IDS]
            if unknown:
                out.append(Finding(
                    "waiver", src.rel, ln,
                    f"waiver names unknown rule(s) {unknown} "
                    f"(registered: {sorted(KNOWN_RULE_IDS)})",
                    hint="use a registered rule id"))
    return out


def run_checks(root: str, rules: Sequence[Rule],
               sources: Optional[List[Source]] = None) -> List[Finding]:
    """Run every rule over one shared parse of ``root``. Returns ALL
    findings, waived ones flagged — callers gate on the unwaived subset
    (``unwaived()``)."""
    if sources is None:
        sources = load_sources(root)
    ctx = Context(root=root, sources=sources)
    findings: List[Finding] = []
    for src in sources:
        if src.tree is None:
            findings.append(Finding(
                "parse", src.rel, 0, f"unparseable ({src.parse_error})"))
    for rule in rules:
        findings.extend(rule.run(ctx))
    findings.extend(_waiver_findings(sources))
    findings.sort(key=lambda f: (f.file, f.line, f.rule, f.message))
    return _apply_waivers(sources, findings)


def unwaived(findings: Sequence[Finding]) -> List[Finding]:
    return [f for f in findings if not f.waived]


def render_text(findings: Sequence[Finding],
                show_waived: bool = False) -> str:
    live = unwaived(findings)
    waived = [f for f in findings if f.waived]
    lines = [f.render() for f in live]
    if show_waived:
        lines += [f.render() for f in waived]
    lines.append(f"egpt-check: {len(live)} finding(s), "
                 f"{len(waived)} waived")
    return "\n".join(lines)


def render_json(findings: Sequence[Finding],
                rules: Sequence[Rule]) -> str:
    """The ``--json`` mode, for tooling that diffs runs across PRs: stable
    keys, per-rule counts, waived findings carried separately."""
    live = unwaived(findings)
    waived = [f for f in findings if f.waived]
    counts: Dict[str, int] = {r.id: 0 for r in rules}
    for f in live:
        counts[f.rule] = counts.get(f.rule, 0) + 1
    return json.dumps({
        "findings": [f.as_dict() for f in live],
        "waived": [f.as_dict() for f in waived],
        "counts": counts,
        "total": len(live),
        "total_waived": len(waived),
    }, indent=2)
