"""m3lint engine: module loading, waiver bookkeeping, rule dispatch, CLI.

The engine is deliberately import-light (stdlib ``ast`` only): it must run
before every test lane in well under the ~10s budget, and it must never
import m3_tpu itself (which would pull in jax and its start-up cost).
"""

from __future__ import annotations

import argparse
import ast
import io
import os
import re
import sys
import time
import tokenize
from dataclasses import dataclass, field

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PKG = os.path.join(REPO, "m3_tpu")

_WAIVER_RE = re.compile(r"#\s*m3lint:\s*disable=([a-z0-9,\-\s]+)")


def attr_chain(node: ast.AST) -> str | None:
    """Dotted name for Name/Attribute chains ('self._lock', 'os.path.x').

    The one name-resolution primitive every rule family shares — it lives
    here so a refinement applies to all of them at once."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


@dataclass(frozen=True)
class Finding:
    rule: str
    path: str          # absolute path
    line: int
    message: str

    def render(self) -> str:
        rel = os.path.relpath(self.path, REPO)
        return f"{rel}:{self.line}: {self.rule} {self.message}"


@dataclass
class Waiver:
    line: int           # line the comment sits on
    rules: tuple[str, ...]
    own_line: bool      # comment-only line -> applies to the NEXT line
    used: set = field(default_factory=set)  # rules it actually suppressed


class Module:
    """One parsed source file plus its waiver table."""

    def __init__(self, path: str):
        self.path = os.path.abspath(path)
        with open(self.path, encoding="utf-8") as f:
            self.source = f.read()
        self.lines = self.source.splitlines()
        self.tree = ast.parse(self.source, filename=self.path)
        self.waivers: list[Waiver] = []
        # waivers come from COMMENT tokens only — a docstring QUOTING the
        # syntax (this feature gets documented) must not register as a
        # waiver and then fail the gate as lint-unused-waiver. The
        # "m3lint:" pre-filter keeps the tokenize pass off the 100+
        # files that have no waivers at all.
        if "m3lint:" in self.source:
            try:
                toks = list(tokenize.generate_tokens(
                    io.StringIO(self.source).readline))
            except (tokenize.TokenError, IndentationError):
                toks = []  # ast.parse succeeded, so this never fires
            for tok in toks:
                if tok.type != tokenize.COMMENT:
                    continue
                m = _WAIVER_RE.search(tok.string)
                if not m:
                    continue
                rules = tuple(r.strip() for r in m.group(1).split(",")
                              if r.strip())
                line = tok.start[0]
                own = self.lines[line - 1][: tok.start[1]].strip() == ""
                self.waivers.append(
                    Waiver(line=line, rules=rules, own_line=own))

    @property
    def rel(self) -> str:
        return os.path.relpath(self.path, PKG)

    def waiver_for(self, rule: str, line: int) -> Waiver | None:
        """A waiver covers its own line; a comment-only waiver covers the
        next line instead (the conventional place above a `with` or call)."""
        for w in self.waivers:
            if rule not in w.rules:
                continue
            target = w.line + 1 if w.own_line else w.line
            if target == line:
                return w
        return None


class Project:
    """The set of modules under analysis plus repo-level context."""

    def __init__(self, modules: list[Module], whole_tree: bool):
        self.modules = modules
        self.whole_tree = whole_tree  # project-level invariants only then
        self.by_path = {m.path: m for m in modules}
        self.parse_failures: list[Finding] = []


def _walk_package() -> list[str]:
    paths = []
    for dirpath, dirs, files in os.walk(PKG):
        # sorted so module order (and e.g. which duplicate fault-point
        # site counts as "first declared") is machine-independent
        dirs[:] = sorted(d for d in dirs if d != "__pycache__")
        for fname in sorted(files):
            if fname.endswith(".py"):
                paths.append(os.path.join(dirpath, fname))
    return paths


def load_project(paths: list[str] | None = None) -> Project:
    whole_tree = paths is None
    file_paths = _walk_package() if whole_tree else list(paths)
    modules: list[Module] = []
    failures: list[Finding] = []
    for p in file_paths:
        try:
            modules.append(Module(p))
        except (OSError, SyntaxError) as e:
            failures.append(Finding(
                rule="lint-parse-error", path=os.path.abspath(p),
                line=getattr(e, "lineno", 1) or 1,
                message=f"unreadable/unparseable: {e}"))
    proj = Project(modules, whole_tree=whole_tree)
    proj.parse_failures = failures
    return proj


def _checkers():
    # imported lazily so `python -m tools.m3lint --list-rules` never pays
    # for a rule module with a syntax error twice
    from tools.m3lint import rules_concurrency, rules_invariants, rules_jax

    return (
        rules_concurrency.check,
        rules_jax.check,
        rules_invariants.check,
    )


def all_rules() -> dict[str, str]:
    from tools.m3lint import rules_concurrency, rules_invariants, rules_jax

    out: dict[str, str] = {
        "lint-parse-error": "a linted file failed to parse",
        "lint-unused-waiver": "a waiver comment that suppresses nothing",
    }
    for mod in (rules_concurrency, rules_jax, rules_invariants):
        out.update(mod.RULES)
    return out


def lint_project(proj: Project, select: tuple[str, ...] = ()) -> list[Finding]:
    """Run every checker; apply waivers; flag stale waivers.

    ``select`` restricts to findings whose rule id starts with one of the
    given prefixes (waiver accounting is then restricted the same way, so
    fixture tests can exercise one family at a time).
    """
    raw: list[Finding] = list(proj.parse_failures)
    for check in _checkers():
        raw.extend(check(proj))
    if select:
        raw = [f for f in raw if f.rule.startswith(select)]

    surviving: list[Finding] = []
    for f in raw:
        mod = proj.by_path.get(f.path)
        w = mod.waiver_for(f.rule, f.line) if mod is not None else None
        if w is not None:
            w.used.add(f.rule)
        else:
            surviving.append(f)

    # a waiver nothing hides behind is itself a finding: the enforced
    # baseline must stay exactly as strong as the code claims it is
    for mod in proj.modules:
        for w in mod.waivers:
            for rule in w.rules:
                if select and not rule.startswith(select):
                    continue
                if rule not in w.used:
                    surviving.append(Finding(
                        rule="lint-unused-waiver", path=mod.path, line=w.line,
                        message=f"waiver for {rule} suppresses nothing — "
                                f"delete it (or the fix regressed)"))
    surviving.sort(key=lambda f: (f.path, f.line, f.rule))
    return surviving


def lint_paths(paths: list[str], select: tuple[str, ...] = ()) -> list[Finding]:
    """Lint explicit files (fixture tests use this)."""
    return lint_project(load_project(paths), select=select)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.m3lint",
        description="m3_tpu static analysis (lock discipline, jax purity, "
                    "project invariants)")
    ap.add_argument("paths", nargs="*",
                    help="files to lint (default: the whole m3_tpu package "
                         "plus project-level invariants)")
    ap.add_argument("--select", default="",
                    help="comma-separated rule-id prefixes to run "
                         "(e.g. 'lock-,jax-')")
    ap.add_argument("--list-rules", action="store_true")
    args = ap.parse_args(argv)

    if args.list_rules:
        for rule, desc in sorted(all_rules().items()):
            print(f"{rule:24s} {desc}")
        return 0

    select = tuple(s.strip() for s in args.select.split(",") if s.strip())
    t0 = time.perf_counter()
    proj = load_project(args.paths or None)
    findings = lint_project(proj, select=select)
    dt = time.perf_counter() - t0
    if findings:
        print("m3lint: FAILED", file=sys.stderr)
        for f in findings:
            print(f"  {f.render()}", file=sys.stderr)
        print(f"m3lint: {len(findings)} finding(s) in {len(proj.modules)} "
              f"modules ({dt:.2f}s)", file=sys.stderr)
        return 1
    waived = sum(len(w.used) for m in proj.modules for w in m.waivers)
    print(f"m3lint: OK — {len(proj.modules)} modules clean "
          f"({waived} explicit waivers) in {dt:.2f}s")
    return 0
