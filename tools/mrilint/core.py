"""mrilint runner: file discovery, suppressions, baseline, CLI.

The baseline (``baseline.txt``) is a burn-down record: every line is a
known finding keyed WITHOUT line numbers (``rule|path|stable-key``) so
unrelated edits don't churn it.  New findings fail the run; findings
that disappear also fail the run until ``--update-baseline`` prunes
them — the file may only shrink, never grow.

Exit codes follow the repo contract: 0 clean, 2 usage/internal error.
Findings exit 1 deliberately — lint failure is neither usage error nor
degraded-but-complete output, and 1 is otherwise reserved.
"""
from __future__ import annotations

import argparse
import ast
import re
import subprocess
import sys
from collections import Counter
from dataclasses import dataclass
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[2]
PACKAGE = "parallel_computation_of_an_inverted_index_using_map_reduce_tpu"
#: default lint scope (tests are exercised by pytest, not contract-bound)
DEFAULT_TARGETS = (PACKAGE, "tools", "mri_tpu.py")
_EXCLUDE_PARTS = {"__pycache__", "_build", ".git"}
BASELINE_PATH = Path(__file__).resolve().parent / "baseline.txt"

_ALLOW_RE = re.compile(r"#\s*mrilint:\s*allow\(([^)]*)\)")
_HOLDS_RE = re.compile(r"#\s*mrilint:\s*holds\(([^)]*)\)")


@dataclass(frozen=True)
class Finding:
    rule: str
    path: str      # repo-relative posix path
    line: int
    key: str       # line-number-free stable key for the baseline
    message: str

    @property
    def baseline_key(self) -> str:
        return f"{self.rule}|{self.path}|{self.key}"

    def render(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


class Source:
    """One parsed file: AST with parent links + comment annotations."""

    def __init__(self, path: Path, root: Path = REPO_ROOT):
        self.path = path
        self.rel = path.resolve().relative_to(root).as_posix()
        self.text = path.read_text(encoding="utf-8", errors="replace")
        self.lines = self.text.splitlines()
        self.tree = ast.parse(self.text)
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                child._mrilint_parent = node  # type: ignore[attr-defined]
        # line (1-based) -> set of rule names allowed there
        self._allow: dict[int, set[str]] = {}
        for i, line in enumerate(self.lines, start=1):
            m = _ALLOW_RE.search(line)
            if m:
                rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
                self._allow.setdefault(i, set()).update(rules)

    def parent(self, node: ast.AST) -> ast.AST | None:
        return getattr(node, "_mrilint_parent", None)

    def ancestors(self, node: ast.AST):
        cur = self.parent(node)
        while cur is not None:
            yield cur
            cur = self.parent(cur)

    def statement_of(self, node: ast.AST) -> ast.AST:
        cur = node
        while not isinstance(cur, ast.stmt):
            nxt = self.parent(cur)
            if nxt is None:
                return cur
            cur = nxt
        return cur

    def allowed(self, node: ast.AST, rule: str) -> bool:
        """Suppressed iff ``# mrilint: allow(rule)`` sits anywhere on
        the enclosing statement's lines or the line directly above."""
        stmt = self.statement_of(node)
        lo = getattr(stmt, "lineno", 1) - 1
        hi = getattr(stmt, "end_lineno", lo + 1)
        for ln in range(lo, hi + 1):
            if rule in self._allow.get(ln, ()):
                return True
        return False

    def holds_locks(self, func: ast.AST) -> set[str]:
        """Locks a ``# mrilint: holds(<lock>)`` annotation on the def
        line (or the line above) declares the caller already owns."""
        locks: set[str] = set()
        lineno = getattr(func, "lineno", None)
        if lineno is None:
            return locks
        for ln in (lineno - 1, lineno):
            if 1 <= ln <= len(self.lines):
                m = _HOLDS_RE.search(self.lines[ln - 1])
                if m:
                    locks.update(x.strip().replace(" ", "")
                                 for x in m.group(1).split(",") if x.strip())
        return locks

    def enclosing_function(self, node: ast.AST):
        for anc in self.ancestors(node):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return anc
        return None

    def enclosing_class(self, node: ast.AST):
        for anc in self.ancestors(node):
            if isinstance(anc, ast.ClassDef):
                return anc
        return None


def _checkers():
    from .checks import CHECKERS
    return CHECKERS


def iter_files(targets=DEFAULT_TARGETS) -> list[Path]:
    files: list[Path] = []
    for t in targets:
        p = (REPO_ROOT / t) if not Path(t).is_absolute() else Path(t)
        if p.is_file() and p.suffix == ".py":
            files.append(p)
        elif p.is_dir():
            for f in sorted(p.rglob("*.py")):
                # exclusion is relative to the target, so an explicitly
                # passed fixtures dir still lints
                if not _EXCLUDE_PARTS.intersection(f.relative_to(p).parts):
                    files.append(f)
    return files


def changed_files() -> list[Path]:
    """Default-scope .py files touched since main (merge-base) plus
    anything uncommitted/untracked — the fast-iteration scope."""
    names: set[str] = set()
    try:
        base = subprocess.run(
            ["git", "-C", str(REPO_ROOT), "merge-base", "HEAD", "main"],
            capture_output=True, text=True, timeout=30)
        if base.returncode == 0:
            diff = subprocess.run(
                ["git", "-C", str(REPO_ROOT), "diff", "--name-only",
                 base.stdout.strip(), "HEAD"],
                capture_output=True, text=True, timeout=30)
            names.update(diff.stdout.split())
        status = subprocess.run(
            ["git", "-C", str(REPO_ROOT), "status", "--porcelain"],
            capture_output=True, text=True, timeout=30)
        for line in status.stdout.splitlines():
            names.add(line[3:].split(" -> ")[-1].strip())
    except (OSError, subprocess.SubprocessError) as e:
        print(f"mrilint: --changed needs git: {e}", file=sys.stderr)
        raise SystemExit(2)
    in_scope = {f.resolve() for f in iter_files()}
    out = [REPO_ROOT / n for n in sorted(names) if n.endswith(".py")]
    return [p for p in out if p.exists() and p.resolve() in in_scope]


def run_lint(files: list[Path]) -> list[Finding]:
    findings: list[Finding] = []
    for path in files:
        try:
            src = Source(path)
        except SyntaxError as e:
            rel = path.resolve().relative_to(REPO_ROOT).as_posix()
            findings.append(Finding(
                rule="parse-error", path=rel, line=e.lineno or 1,
                key="syntax", message=f"cannot parse: {e.msg}"))
            continue
        for checker in _checkers():
            findings.extend(checker.check(src))
    findings.sort(key=lambda f: (f.path, f.line, f.rule, f.key))
    return findings


def run_repo_checks() -> list[Finding]:
    from .checks import obs_metrics, readme_knobs
    return (readme_knobs.check_repo(REPO_ROOT)
            + obs_metrics.check_repo(REPO_ROOT))


def load_baseline(path: Path = BASELINE_PATH) -> Counter:
    if not path.exists():
        return Counter()
    entries = [ln.strip() for ln in path.read_text().splitlines()
               if ln.strip() and not ln.lstrip().startswith("#")]
    return Counter(entries)


def write_baseline(entries: Counter, path: Path = BASELINE_PATH) -> None:
    lines = ["# mrilint baseline — known findings, one per line.",
             "# This file may only SHRINK: fix a finding, then run",
             "#   python -m tools.mrilint --update-baseline",
             "# New findings are never added here; fix or suppress them.",
             ""]
    for key in sorted(entries.elements()):
        lines.append(key)
    path.write_text("\n".join(lines) + "\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="mrilint", description="repo-contract static analysis")
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to lint (default: the repo scope)")
    ap.add_argument("--changed", action="store_true",
                    help="lint only files touched since main")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report every finding, ignoring the baseline")
    ap.add_argument("--update-baseline", action="store_true",
                    help="prune fixed findings from the baseline "
                         "(shrink-only; never adds)")
    ap.add_argument("--write-readme", action="store_true",
                    help="regenerate the README env-knob and metrics "
                         "tables")
    args = ap.parse_args(argv)

    if args.write_readme:
        from .checks import obs_metrics, readme_knobs
        readme_knobs.write_readme(REPO_ROOT)
        obs_metrics.write_readme(REPO_ROOT)
        print("mrilint: README env-knob and metrics tables regenerated")
        return 0

    full_scope = not args.paths and not args.changed
    if args.changed:
        files = changed_files()
    elif args.paths:
        files = iter_files(args.paths)
    else:
        files = iter_files()

    if args.update_baseline and not full_scope:
        print("mrilint: --update-baseline requires the full default "
              "scope (no paths, no --changed)", file=sys.stderr)
        return 2

    findings = run_lint(files)
    if full_scope:
        findings.extend(run_repo_checks())

    if args.no_baseline:
        for f in findings:
            print(f.render())
        print(f"mrilint: {len(findings)} finding(s), baseline ignored")
        return 1 if findings else 0

    baseline = load_baseline()
    if not full_scope:
        # subset run: only this subset's slice of the baseline applies
        rels = {f.resolve().relative_to(REPO_ROOT).as_posix()
                for f in files}
        baseline = Counter({k: n for k, n in baseline.items()
                            if k.split("|", 2)[1] in rels})

    current = Counter(f.baseline_key for f in findings)
    new = current - baseline
    stale = baseline - current

    if args.update_baseline:
        write_baseline(baseline & current)
        print(f"mrilint: baseline pruned by {sum(stale.values())} "
              f"entr{'y' if sum(stale.values()) == 1 else 'ies'}, "
              f"{sum((baseline & current).values())} remain")
        if new:
            print("mrilint: NEW findings are never added to the "
                  "baseline — fix or suppress them:", file=sys.stderr)

    rc = 0
    if new:
        # print at most new[key] occurrences per key (the rest are
        # covered by the baseline)
        to_show = Counter(new)
        for f in findings:
            if to_show[f.baseline_key] > 0:
                to_show[f.baseline_key] -= 1
                print(f.render())
        print(f"mrilint: {sum(new.values())} new finding(s) "
              f"(not in baseline)", file=sys.stderr)
        rc = 1
    if stale and not args.update_baseline:
        for key in sorted(stale.elements()):
            print(f"stale baseline entry (finding fixed): {key}")
        print("mrilint: baseline must shrink — run "
              "`python -m tools.mrilint --update-baseline`",
              file=sys.stderr)
        rc = 1
    if rc == 0 and not args.update_baseline:
        known = sum((current & baseline).values())
        print(f"mrilint: clean ({len(files)} files, "
              f"{known} baselined finding(s) remaining)")
    return rc


if __name__ == "__main__":
    sys.exit(main())
