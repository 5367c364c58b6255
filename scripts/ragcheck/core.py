"""ragcheck framework: source model, rule registry, suppressions, baseline.

Three layers, all stdlib:

- :class:`Repo` parses the scan roots (the package) once and
  hands every rule the same ASTs; cross-file rules can lazily pull any
  other repo file (tests/, docs/, deploy manifests) through the same cache.
- Rules are objects with a stable ``id`` and a ``run(repo)`` generator of
  :class:`Finding`. A finding carries a *fingerprint* built from the rule
  id, the repo-relative path, and a rule-chosen stable ``key`` (never a
  line number — refactors that move code must not churn the baseline).
- The runner applies inline suppressions (``# ragcheck: disable=RULE-ID``
  on the flagged line or the line above), then gates against the committed
  baseline: a finding not in the baseline fails, and a baseline entry that
  no longer fires fails too ("stale — delete it"), which is what makes the
  baseline a ratchet: it can only shrink.
"""

from __future__ import annotations

import ast
import json
import os
import re
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

__all__ = [
    "Finding",
    "Repo",
    "ScopedDefIndex",
    "SourceFile",
    "dotted_name",
    "gate",
    "load_baseline",
    "run_analysis",
]


# ---------------------------------------------------------------------------
# findings
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Finding:
    """One rule violation at a source location.

    ``key`` is the stable identity used for baselining and must not embed
    line numbers; ``line`` is presentation only (``file:line`` output).
    """

    rule: str
    path: str  # repo-relative, forward slashes
    line: int
    message: str
    key: str

    @property
    def fingerprint(self) -> str:
        return f"{self.rule}::{self.path}::{self.key}"

    def render(self) -> str:
        return f"{self.path}:{self.line}: {self.rule}: {self.message}"

    def as_json(self) -> Dict[str, object]:
        return {
            "rule": self.rule,
            "path": self.path,
            "line": self.line,
            "message": self.message,
            "fingerprint": self.fingerprint,
        }


# ---------------------------------------------------------------------------
# source model
# ---------------------------------------------------------------------------


@dataclass
class SourceFile:
    path: str  # repo-relative
    text: str
    lines: List[str]
    tree: Optional[ast.AST]  # None when the file does not parse


def _norm(relpath: str) -> str:
    return relpath.replace(os.sep, "/")


class Repo:
    """The analyzed tree: eager scan roots + a lazy cache for everything
    else a cross-file rule wants (tests, docs, manifests)."""

    #: default scan roots, repo-relative (directories walk ``**/*.py``)
    SCAN_ROOTS: Tuple[str, ...] = ("rag_llm_k8s_tpu",)

    def __init__(self, root: str, scan_roots: Optional[Sequence[str]] = None):
        self.root = os.path.abspath(root)
        self._cache: Dict[str, Optional[SourceFile]] = {}
        self.scan_files: List[SourceFile] = []
        for sr in scan_roots if scan_roots is not None else self.SCAN_ROOTS:
            ap = os.path.join(self.root, sr)
            if os.path.isfile(ap):
                sf = self.get(sr)
                if sf is not None:
                    self.scan_files.append(sf)
            elif os.path.isdir(ap):
                for dirpath, dirnames, names in os.walk(ap):
                    dirnames[:] = [d for d in dirnames if d != "__pycache__"]
                    for n in sorted(names):
                        if not n.endswith(".py"):
                            continue
                        rel = _norm(
                            os.path.relpath(os.path.join(dirpath, n), self.root)
                        )
                        sf = self.get(rel)
                        if sf is not None:
                            self.scan_files.append(sf)
        self.scan_files.sort(key=lambda sf: sf.path)

    def get(self, relpath: str) -> Optional[SourceFile]:
        """Load + parse one python file (cached); None when absent."""
        relpath = _norm(relpath)
        if relpath in self._cache:
            return self._cache[relpath]
        ap = os.path.join(self.root, relpath)
        sf: Optional[SourceFile] = None
        if os.path.isfile(ap):
            with open(ap, encoding="utf-8") as f:
                text = f.read()
            try:
                tree = ast.parse(text, filename=relpath)
            except SyntaxError:
                tree = None
            sf = SourceFile(relpath, text, text.splitlines(), tree)
        self._cache[relpath] = sf
        return sf

    def text(self, relpath: str) -> Optional[str]:
        """Raw text of any repo file (docs, yaml); None when absent."""
        ap = os.path.join(self.root, _norm(relpath))
        if not os.path.isfile(ap):
            return None
        with open(ap, encoding="utf-8") as f:
            return f.read()

    def glob_py(self, reldir: str) -> List[SourceFile]:
        """Every ``*.py`` directly under ``reldir`` (tests/ etc.)."""
        ap = os.path.join(self.root, reldir)
        out: List[SourceFile] = []
        if os.path.isdir(ap):
            for n in sorted(os.listdir(ap)):
                if n.endswith(".py"):
                    sf = self.get(f"{reldir}/{n}")
                    if sf is not None:
                        out.append(sf)
        return out


# ---------------------------------------------------------------------------
# shared AST helpers
# ---------------------------------------------------------------------------


def dotted_name(node: ast.AST) -> Optional[str]:
    """``a.b.c`` for a Name/Attribute chain, else None (calls/subscripts in
    the chain end the walk — ``jit(f).lower`` has no dotted name)."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def terminal_attr(node: ast.AST) -> Optional[str]:
    """The last segment of a callee (``self._lock`` → ``_lock``)."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def receiver_of(call_func: ast.AST) -> Optional[ast.AST]:
    """The object a method is called on (``x.join`` → ``x``)."""
    if isinstance(call_func, ast.Attribute):
        return call_func.value
    return None


def name_parts(expr: ast.AST) -> List[str]:
    """Every identifier mentioned in an expression: Name ids plus every
    Attribute segment (``cache.k`` yields both ``cache`` and ``k``)."""
    out: List[str] = []
    for n in ast.walk(expr):
        if isinstance(n, ast.Name):
            out.append(n.id)
        elif isinstance(n, ast.Attribute):
            out.append(n.attr)
    return out


class ScopedDefIndex:
    """Lexically-scoped ``def`` resolution for a module.

    ``resolve(node, name)`` finds the function definitions a bare ``name``
    at ``node`` would bind to: local sibling ``def``s first, then each
    enclosing function's scope outward, then plain module-level ``def``s.
    Class bodies do not form closure scopes (a method named ``step`` must
    NOT shadow a traced local ``def step`` elsewhere in the file — the
    collision that motivates this index).
    """

    def __init__(self, tree: ast.AST):
        self._parent: Dict[int, ast.AST] = {}
        for parent in ast.walk(tree):
            for child in ast.iter_child_nodes(parent):
                self._parent[id(child)] = parent
        self._tree = tree
        # scope (FunctionDef or Module) -> {name: [defs]}
        self._by_scope: Dict[int, Dict[str, List[ast.AST]]] = {}
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scope = self._enclosing_function(node)
                if scope is None and self._has_class_ancestor(node):
                    continue  # methods are attributes, not lexical names
                key = id(scope) if scope is not None else id(tree)
                self._by_scope.setdefault(key, {}).setdefault(
                    node.name, []
                ).append(node)

    def _enclosing_function(self, node: ast.AST):
        cur = self._parent.get(id(node))
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return cur
            cur = self._parent.get(id(cur))
        return None

    def _has_class_ancestor(self, node: ast.AST) -> bool:
        cur = self._parent.get(id(node))
        while cur is not None:
            if isinstance(cur, ast.ClassDef):
                return True
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return False
            cur = self._parent.get(id(cur))
        return False

    def resolve(self, node: ast.AST, name: str) -> List[ast.AST]:
        scope = self._enclosing_function(node)
        while scope is not None:
            hits = self._by_scope.get(id(scope), {}).get(name, [])
            if hits:
                return hits
            scope = self._enclosing_function(scope)
        return self._by_scope.get(id(self._tree), {}).get(name, [])

    def qualname(self, node: ast.AST) -> str:
        """``Class.method.inner`` for a def/lambda — rule keys built from
        this stay unique when two scopes define the same bare name (a bare
        name would dedupe one finding into the other AND let one baseline
        entry mask every same-named function in the file)."""
        parts: List[str] = []
        cur: Optional[ast.AST] = node
        while cur is not None and not isinstance(cur, ast.Module):
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.ClassDef)):
                parts.append(cur.name)
            elif isinstance(cur, ast.Lambda):
                parts.append("<lambda>")
            cur = self._parent.get(id(cur))
        return ".".join(reversed(parts)) or "<module>"


class QualnameVisitor(ast.NodeVisitor):
    """NodeVisitor that tracks ``Class.method`` qualnames in ``self.stack``."""

    def __init__(self):
        self.stack: List[str] = []

    @property
    def qualname(self) -> str:
        return ".".join(self.stack) or "<module>"

    def _scoped(self, node):
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()

    visit_FunctionDef = _scoped
    visit_AsyncFunctionDef = _scoped
    visit_ClassDef = _scoped


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------

_DISABLE_RE = re.compile(r"#\s*ragcheck:\s*disable=([A-Za-z0-9_,\- ]+)")


def _disabled_rules(line_text: str) -> List[str]:
    m = _DISABLE_RE.search(line_text)
    if not m:
        return []
    return [t.strip() for t in m.group(1).split(",") if t.strip()]


def is_suppressed(finding: Finding, repo: Repo) -> bool:
    """``# ragcheck: disable=RULE`` (or ``all``) on the flagged line or the
    line directly above it suppresses the finding."""
    sf = repo.get(finding.path)
    if sf is None or finding.line <= 0:
        return False
    for ln in (finding.line, finding.line - 1):
        if 1 <= ln <= len(sf.lines):
            for rid in _disabled_rules(sf.lines[ln - 1]):
                if rid == "all" or rid == finding.rule:
                    return True
    return False


# ---------------------------------------------------------------------------
# runner + baseline gate
# ---------------------------------------------------------------------------


def run_analysis(
    root: str,
    rules: Optional[Sequence[object]] = None,
    scan_roots: Optional[Sequence[str]] = None,
) -> Tuple[Repo, List[Finding]]:
    """Run every rule over ``root``; returns (repo, suppressed-filtered,
    fingerprint-deduped findings sorted by location)."""
    if rules is None:
        from scripts.ragcheck.rules import ALL_RULES

        rules = [cls() for cls in ALL_RULES]
    repo = Repo(root, scan_roots=scan_roots)
    seen: Dict[str, Finding] = {}
    for rule in rules:
        for f in rule.run(repo):
            if is_suppressed(f, repo):
                continue
            seen.setdefault(f.fingerprint, f)
    findings = sorted(seen.values(), key=lambda f: (f.path, f.line, f.rule))
    return repo, findings


def load_baseline(path: str) -> Dict[str, str]:
    """{fingerprint: justification}. Every entry MUST carry a non-empty
    justification — an unexplained baseline entry is itself an error."""
    if not os.path.isfile(path):
        return {}
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    out: Dict[str, str] = {}
    for e in data.get("entries", []):
        fp = e.get("fingerprint", "")
        just = (e.get("justification") or "").strip()
        if not fp:
            raise ValueError(f"{path}: baseline entry missing 'fingerprint': {e}")
        if not just:
            raise ValueError(
                f"{path}: baseline entry for {fp!r} has no justification — "
                "every baselined finding must say why it is acceptable"
            )
        out[fp] = just
    return out


def gate(
    findings: Sequence[Finding], baseline: Dict[str, str]
) -> Tuple[List[Finding], List[str]]:
    """(new_findings, stale_baseline_fingerprints).

    New findings fail CI (fix, suppress inline, or baseline with a
    justification). Stale entries fail too: the fixed finding's baseline
    row must be DELETED in the same change — that is the ratchet, the
    baseline can only shrink.
    """
    fps = {f.fingerprint for f in findings}
    new = [f for f in findings if f.fingerprint not in baseline]
    stale = sorted(fp for fp in baseline if fp not in fps)
    return new, stale
