"""AST analysis core: file loading, rule driver, suppressions, report.

The engine is a two-phase whole-tree pass:

1. **collect** — every rule sees every file and records global facts in
   the shared :class:`AnalysisContext` (donating dispatch signatures,
   annotated functions, lock classes, ...).
2. **check / finalize** — per-file findings, then cross-file findings
   (e.g. lock-order cycles) once the whole graph is known.

Suppression syntax (recorded, never silent)::

    x = risky()  # openr-lint: disable=donation-hazard -- reason here
    # openr-lint: disable=lock-order -- applies to the NEXT line
    # openr-lint: disable-file=retrace-risk -- whole file

A finding on line L is suppressed by a directive on L or on the
directive-only line immediately above. ``disable=all`` matches every
rule. The reason string after ``--`` is carried into the report so
``make lint-analysis`` output and the JSON artifact show *why* each
exception exists; a suppression without a reason is itself reported
(rule ``suppression-hygiene``) — prose-free exceptions are how
invariants rot.

No jax / numpy imports here: the pass must run in well under a second
on the whole tree (tier-1 runs it as a meta-test).
"""

from __future__ import annotations

import ast
import io
import os
import re
import time
import tokenize
from dataclasses import dataclass, field
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

_SUPPRESS_RE = re.compile(
    r"#\s*openr-lint:\s*(disable|disable-file)="
    r"(?P<rules>[a-zA-Z0-9_,-]+)"
    r"(?:\s*--\s*(?P<reason>.*\S))?"
)

#: rule id for suppressions that carry no reason string
HYGIENE_RULE = "suppression-hygiene"
#: rule id for files the parser rejects
PARSE_RULE = "parse-error"
#: rule id for suppressions that no longer shield any finding
STALE_RULE = "suppression-stale"


@dataclass
class Finding:
    rule: str
    path: str  # repo-relative
    line: int
    col: int
    message: str
    suppressed: bool = False
    reason: str = ""

    def to_dict(self) -> Dict[str, object]:
        return {
            "rule": self.rule,
            "path": self.path,
            "line": self.line,
            "col": self.col,
            "message": self.message,
            "suppressed": self.suppressed,
            "reason": self.reason,
        }

    def __str__(self) -> str:
        tag = f" [suppressed: {self.reason or 'NO REASON'}]" if self.suppressed else ""
        return f"{self.path}:{self.line}:{self.col}: {self.rule}: {self.message}{tag}"


def _comment_lines(lines: Sequence[str]) -> Optional[frozenset]:
    """Line numbers (1-based) holding a real COMMENT token, so
    directive-shaped text inside string literals never registers. None
    when tokenization fails (unparseable file) — the caller falls back
    to the plain line scan."""
    try:
        return frozenset(
            tok.start[0]
            for tok in tokenize.generate_tokens(
                io.StringIO("\n".join(lines) + "\n").readline
            )
            if tok.type == tokenize.COMMENT
        )
    except (tokenize.TokenError, IndentationError, SyntaxError):
        return None


class Suppressions:
    """Per-file ``# openr-lint:`` directive table."""

    def __init__(self, lines: Sequence[str]) -> None:
        # line (1-based) -> {rule -> (reason, directive line)}
        self.by_line: Dict[int, Dict[str, Tuple[str, int]]] = {}
        self.file_level: Dict[str, Tuple[str, int]] = {}
        # directive sites with no reason (line, rules) for hygiene
        self.missing_reason: List[Tuple[int, str]] = []
        # every directive site: (line, rule ids) — audited for
        # staleness (a directive shielding nothing is rot)
        self.sites: List[Tuple[int, Tuple[str, ...]]] = []
        comments = _comment_lines(lines)
        for i, raw in enumerate(lines, start=1):
            if comments is not None and i not in comments:
                # directive text inside a string literal (docstring
                # syntax examples) is not a directive
                continue
            m = _SUPPRESS_RE.search(raw)
            if m is None:
                continue
            rules = [r.strip() for r in m.group("rules").split(",") if r.strip()]
            reason = (m.group("reason") or "").strip()
            # a directive-only line may wrap its reason over further
            # comment-only lines; it shields the first CODE line below
            shield = None
            if raw.lstrip().startswith("#"):
                j = i + 1
                while j <= len(lines) and lines[j - 1].lstrip().startswith("#"):
                    cont = lines[j - 1].lstrip().lstrip("#").strip()
                    if reason and cont:
                        reason = f"{reason} {cont}"
                    j += 1
                shield = j
            if not reason:
                self.missing_reason.append((i, ",".join(rules)))
            self.sites.append((i, tuple(rules)))
            table = {r: (reason, i) for r in rules}
            if m.group(1) == "disable-file":
                self.file_level.update(table)
                continue
            self.by_line.setdefault(i, {}).update(table)
            if shield is not None:
                self.by_line.setdefault(shield, {}).update(table)

    def lookup(self, rule: str, line: int) -> Optional[str]:
        """Reason string (possibly empty) if suppressed, else None."""
        hit = self.lookup_site(rule, line)
        return hit[0] if hit is not None else None

    def lookup_site(
        self, rule: str, line: int
    ) -> Optional[Tuple[str, int, str]]:
        """(reason, directive line, matched rule id — ``rule`` or
        ``"all"``) if suppressed, else None. The directive line is what
        the staleness audit keys on."""
        for table in (self.by_line.get(line, {}), self.file_level):
            if rule in table:
                reason, dline = table[rule]
                return reason, dline, rule
            if "all" in table:
                reason, dline = table["all"]
                return reason, dline, "all"
        return None


class SourceFile:
    """One parsed module plus its suppression table."""

    def __init__(self, abspath: str, relpath: str) -> None:
        self.abspath = abspath
        self.path = relpath
        with open(abspath, "r", encoding="utf-8") as f:
            self.text = f.read()
        self.lines = self.text.splitlines()
        self.suppressions = Suppressions(self.lines)
        self.tree: Optional[ast.Module] = None
        self.parse_error: Optional[str] = None
        try:
            self.tree = ast.parse(self.text, filename=relpath)
        except SyntaxError as exc:
            self.parse_error = f"{exc.msg} (line {exc.lineno})"

    # -- AST helpers shared by the rules ----------------------------

    def functions(self) -> Iterator[Tuple[ast.AST, Optional[str]]]:
        """Yield every (FunctionDef | AsyncFunctionDef, enclosing class
        name or None), including nested functions."""
        assert self.tree is not None

        def walk(node: ast.AST, cls: Optional[str]) -> Iterator:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.ClassDef):
                    yield from walk(child, child.name)
                elif isinstance(
                    child, (ast.FunctionDef, ast.AsyncFunctionDef)
                ):
                    yield child, cls
                    yield from walk(child, cls)
                else:
                    yield from walk(child, cls)

        yield from walk(self.tree, None)

    def classes(self) -> Iterator[ast.ClassDef]:
        assert self.tree is not None
        for node in ast.walk(self.tree):
            if isinstance(node, ast.ClassDef):
                yield node


def dotted_name(node: ast.AST) -> Optional[str]:
    """'jax.jit' for Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def decorator_info(dec: ast.AST) -> Tuple[Optional[str], Optional[ast.Call]]:
    """(dotted decorator name, the Call node if the decorator is a
    call). ``@functools.partial(jax.jit, ...)`` reports the *partial
    target* name 'jax.jit' with the partial Call, so rules see through
    the standard jit idiom."""
    call = dec if isinstance(dec, ast.Call) else None
    name = dotted_name(dec.func if call is not None else dec)
    if (
        call is not None
        and name in ("functools.partial", "partial")
        and call.args
    ):
        inner = dotted_name(call.args[0])
        if inner is not None:
            return inner, call
    return name, call


def unwrap_aot_call(
    node: ast.Call,
) -> Optional[Tuple[str, List[ast.expr]]]:
    """See through ``aot_call(tag, fn, (dyn...), {statics})`` (the
    committed-dispatch executable cache, ops.aot_cache): returns the
    wrapped dispatch's (dotted name,
    positional dyn-arg expressions) so call-site rules —
    donation-hazard, sharding-spec — keep their precision after a hot
    dispatch moves behind the AOT cache. The statics mapping is
    intentionally dropped: statics are hashable policy values (band
    tuples, n, k, mesh), never device buffers."""
    callee = dotted_name(node.func)
    if callee is None or callee.split(".")[-1] not in (
        "aot_call", "warm",
    ):
        return None
    if len(node.args) < 3:
        return None
    inner = dotted_name(node.args[1])
    if inner is None:
        return None
    dyn = node.args[2]
    if not isinstance(dyn, (ast.Tuple, ast.List)):
        return None
    return inner, list(dyn.elts)


def call_kwarg(call: ast.Call, name: str) -> Optional[ast.expr]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def literal_or_none(node: Optional[ast.expr]):
    if node is None:
        return None
    try:
        return ast.literal_eval(node)
    except (ValueError, SyntaxError):
        return None


class Rule:
    """Base checker. Subclasses set ``id``/``description`` and override
    any of the three phases."""

    id: str = ""
    description: str = ""

    def collect(self, sf: SourceFile, ctx: "AnalysisContext") -> None:
        pass

    def check(
        self, sf: SourceFile, ctx: "AnalysisContext"
    ) -> Iterable[Finding]:
        return ()

    def finalize(self, ctx: "AnalysisContext") -> Iterable[Finding]:
        return ()


@dataclass
class AnalysisContext:
    """Whole-tree facts shared between phases. ``store`` is a per-rule
    scratch dict keyed by rule id."""

    root: str
    files: List[SourceFile] = field(default_factory=list)
    store: Dict[str, dict] = field(default_factory=dict)

    def scratch(self, rule_id: str) -> dict:
        return self.store.setdefault(rule_id, {})

    def file_for(self, relpath: str) -> Optional[SourceFile]:
        for sf in self.files:
            if sf.path == relpath:
                return sf
        return None


@dataclass
class Report:
    findings: List[Finding]
    files_scanned: int
    duration_s: float
    rules: List[str]

    @property
    def unsuppressed(self) -> List[Finding]:
        return [f for f in self.findings if not f.suppressed]

    @property
    def exit_code(self) -> int:
        return 1 if self.unsuppressed else 0

    def to_dict(self) -> Dict[str, object]:
        per_rule: Dict[str, int] = {r: 0 for r in self.rules}
        for f in self.findings:
            if not f.suppressed:
                per_rule[f.rule] = per_rule.get(f.rule, 0) + 1
        return {
            "files_scanned": self.files_scanned,
            "duration_s": round(self.duration_s, 3),
            "rules": list(self.rules),
            "findings_total": len(self.unsuppressed),
            "findings_suppressed": len(self.findings)
            - len(self.unsuppressed),
            "findings_per_rule": per_rule,
            "findings": [f.to_dict() for f in self.findings],
        }


def discover_files(root: str, targets: Sequence[str]) -> List[str]:
    """Python files under each target (file or directory), sorted,
    __pycache__ pruned."""
    out: List[str] = []
    for target in targets:
        path = target if os.path.isabs(target) else os.path.join(root, target)
        if os.path.isfile(path):
            out.append(path)
            continue
        for dirpath, dirnames, filenames in os.walk(path):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fn in filenames:
                if fn.endswith(".py"):
                    out.append(os.path.join(dirpath, fn))
    return sorted(set(out))


def run_analysis(
    root: str,
    targets: Sequence[str] = ("openr_tpu",),
    rules: Optional[Sequence[Rule]] = None,
    audit_suppressions: bool = False,
) -> Report:
    """Run every rule over the tree; returns the full report (findings
    carry their suppression state — nothing is dropped silently).

    With ``audit_suppressions``, every directive that shielded no
    finding of a rule that RAN this pass is itself reported (rule
    ``suppression-stale``, unsuppressed — the audit's findings cannot
    be suppressed away): the code it excused has moved or been fixed,
    and a directive shielding nothing is how dead exceptions hide live
    regressions. Only meaningful on full-rule runs — a rule-subset run
    skips directives for rules that did not run."""
    if rules is None:
        from openr_tpu.analysis.rules import ALL_RULES

        rules = [cls() for cls in ALL_RULES]
    t0 = time.perf_counter()
    ctx = AnalysisContext(root=root)
    findings: List[Finding] = []
    for abspath in discover_files(root, targets):
        rel = os.path.relpath(abspath, root)
        sf = SourceFile(abspath, rel)
        if sf.parse_error is not None:
            findings.append(
                Finding(PARSE_RULE, rel, 1, 0, sf.parse_error)
            )
            continue
        ctx.files.append(sf)

    for rule in rules:
        for sf in ctx.files:
            rule.collect(sf, ctx)
    for rule in rules:
        for sf in ctx.files:
            findings.extend(rule.check(sf, ctx))
        findings.extend(rule.finalize(ctx))

    # suppression application + hygiene (a directive with no reason is
    # itself a finding so undocumented exceptions cannot accumulate)
    resolved: List[Finding] = []
    used_sites: set = set()  # (path, directive line, matched rule id)
    for f in findings:
        sf = ctx.file_for(f.path)
        if sf is not None:
            hit = sf.suppressions.lookup_site(f.rule, f.line)
            if hit is not None:
                reason, dline, matched = hit
                f.suppressed = True
                f.reason = reason
                used_sites.add((f.path, dline, matched))
    resolved.extend(findings)
    if audit_suppressions:
        ran = {r.id for r in rules}
        for sf in ctx.files:
            for dline, dir_rules in sf.suppressions.sites:
                for r in dir_rules:
                    if r != "all" and r not in ran:
                        continue  # rule did not run: cannot judge
                    if (sf.path, dline, r) in used_sites:
                        continue
                    resolved.append(
                        Finding(
                            STALE_RULE,
                            sf.path,
                            dline,
                            0,
                            f"suppression of '{r}' shields no finding "
                            "— the excused code moved or was fixed; "
                            "delete the directive",
                        )
                    )
    for sf in ctx.files:
        for line, rules_str in sf.suppressions.missing_reason:
            resolved.append(
                Finding(
                    HYGIENE_RULE,
                    sf.path,
                    line,
                    0,
                    f"suppression of '{rules_str}' carries no reason "
                    "string (append ' -- <why>')",
                )
            )
    resolved.sort(key=lambda f: (f.path, f.line, f.rule))
    return Report(
        findings=resolved,
        files_scanned=len(ctx.files),
        duration_s=time.perf_counter() - t0,
        rules=[r.id for r in rules],
    )
