"""Shared driver behind the CLI and the tier-1 ``tests/test_lint.py`` gate.

Runs the per-file rules (R1-R8) over every linted file, then builds the
swarmflow :class:`~.project.ProjectIndex` over the same file set (warm
runs reuse the content-hash cache) and runs the interprocedural rules
(R9/R10) once against it. ``--changed-only`` narrows the per-file pass to
files changed vs the merge base plus their reverse-dependency closure
from the import graph — the pre-commit fast path.
"""

from __future__ import annotations

import dataclasses
import os
import subprocess
from typing import Callable

from chiaswarm_tpu.analysis import baseline as baseline_mod
from chiaswarm_tpu.analysis.core import (
    Finding, ProjectRule, all_rules, analyze_paths, get_rule,
    iter_python_files,
)
from chiaswarm_tpu.analysis.project import DEFAULT_CACHE_NAME, ProjectIndex


#: the repo surfaces the lint gate covers — single source of truth for
#: the CLI default paths, tests/test_lint.py, and the CI job
DEFAULT_LINT_PATHS = ("chiaswarm_tpu", "tests", "tools",
                      "__graft_entry__.py", "chip_smoke.py")


@dataclasses.dataclass
class RunResult:
    exit_code: int
    new: list[Finding]
    suppressed: list[Finding]
    stale: list[str]
    errors: list[str]
    report: str
    checked_files: int = 0
    total_files: int = 0


def repo_root() -> str:
    """The directory findings are reported relative to (and where the
    default baseline lives): the repo checkout containing this package."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _scope_checker(paths: list[str], root: str,
                   rules) -> Callable[[str], bool]:
    """Predicate: did THIS run (its paths + selected rules) re-check the
    file/rule a baseline key refers to? Out-of-scope entries are neither
    stale nor erasable."""
    rule_names = {r.name for r in rules}
    prefixes: list[str] = []
    exact: set[str] = set()
    for p in paths:
        rel = os.path.relpath(os.path.abspath(p), root).replace(os.sep, "/")
        if rel == ".":
            prefixes.append("")  # whole repo
        elif os.path.isdir(p):
            prefixes.append(rel.rstrip("/") + "/")
        else:
            exact.add(rel)

    def in_scope(key: str) -> bool:
        rule, path, _, _ = key.split("::", 3)
        return rule in rule_names and (
            path in exact or any(path.startswith(px) for px in prefixes))

    return in_scope


def _git_changed_files(root: str) -> set[str] | None:
    """Root-relative posix paths of .py files changed vs the merge base
    with origin/main (falling back to origin/master, then local main,
    then plain HEAD = uncommitted work only), plus untracked files.
    None when git itself is unusable here."""
    def git(*args: str):
        try:
            return subprocess.run(["git", "-C", root, *args],
                                  capture_output=True, text=True,
                                  timeout=30)
        except (OSError, subprocess.TimeoutExpired):
            return None
    base = None
    for ref in ("origin/main", "origin/master", "main"):
        p = git("merge-base", "HEAD", ref)
        if p is not None and p.returncode == 0:
            base = p.stdout.strip()
            break
    # --relative: paths come back relative to ``root`` (the -C dir), not
    # the git toplevel — they must intersect the lint surface even when
    # this package sits below the top of a larger checkout
    p = git("diff", "--name-only", "--relative", base or "HEAD")
    if p is None or p.returncode != 0:
        return None
    changed = {ln.strip() for ln in p.stdout.splitlines() if ln.strip()}
    p = git("ls-files", "--others", "--exclude-standard")
    if p is not None and p.returncode == 0:
        changed |= {ln.strip() for ln in p.stdout.splitlines()
                    if ln.strip()}
    return {c.replace(os.sep, "/") for c in changed if c.endswith(".py")}


def run(paths: list[str],
        *,
        baseline_path: str | None = None,
        strict: bool = False,
        select: list[str] | None = None,
        write_baseline: bool = False,
        root: str | None = None,
        changed_only: bool = False,
        cache: bool = True) -> RunResult:
    """Lint ``paths``; returns exit code 0 when clean.

    - new (non-baselined) findings -> exit 1
    - stale baseline entries -> exit 1 under ``strict``, warning otherwise
    - unparseable files / bad input -> exit 2
    """
    root = root or repo_root()
    if baseline_path is None:
        baseline_path = os.path.join(
            root, baseline_mod.DEFAULT_BASELINE_NAME)
    try:
        rules = [get_rule(s) for s in select] if select else all_rules()
    except KeyError as exc:
        # typo'd --select is bad input (exit 2), not lint findings
        return RunResult(2, [], [], [], [str(exc)],
                         f"swarmlint: {exc.args[0]}")
    file_rules = [r for r in rules if not isinstance(r, ProjectRule)]
    project_rules = [r for r in rules if isinstance(r, ProjectRule)]

    errors: list[str] = []
    error_paths: set[str] = set()

    def record_error(rel: str, exc: Exception) -> None:
        errors.append(f"{rel}: {exc}")
        error_paths.add(rel)

    # one enumeration of the lint surface; the project index and the
    # changed-only closure both work off it. The index is only built
    # when something consumes it — a --select R1 subset run must stay as
    # cheap as it was before the whole-program layer existed
    files = list(iter_python_files([os.path.abspath(p) for p in paths
                                    if os.path.exists(p)], root=root))
    index = None
    if project_rules or changed_only:
        index = ProjectIndex.build(
            files, cache_path=(os.path.join(root, DEFAULT_CACHE_NAME)
                               if cache else None))

    only_files: set[str] | None = None
    allowed_rel: set[str] | None = None
    note = ""
    if changed_only:
        changed = _git_changed_files(root)
        if changed is None:
            return RunResult(
                2, [], [], [], ["--changed-only requires a usable git "
                                "checkout"],
                "swarmlint: --changed-only requires a usable git checkout")
        in_surface = {rel for _, rel in files}
        # the closure walks the import graph (which only knows parseable
        # files) — union the raw changed set back in so a changed file
        # with a syntax error is still OPENED and fails the run loudly
        allowed_rel = (index.reverse_closure(changed & in_surface)
                       | (changed & in_surface))
        only_files = {ap for ap, rel in files if rel in allowed_rel}
        note = (f"changed-only: linting {len(only_files)} of "
                f"{len(files)} files ({len(changed & in_surface)} changed "
                f"+ reverse-dependency closure)")

    findings = analyze_paths(paths, file_rules, root=root,
                             on_error=record_error, only_files=only_files)
    for rule in project_rules:
        for f in rule.check_project(index):
            if f.path in error_paths:
                continue
            if allowed_rel is not None and f.path not in allowed_rel \
                    and not any(hop[0] in allowed_rel for hop in f.chain):
                continue
            findings.append(f)
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))

    scope_paths = sorted(only_files) if only_files is not None else paths
    scope = _scope_checker(scope_paths, root, rules)

    def in_scope(key: str) -> bool:
        # a file that failed to parse was NOT re-checked: its baseline
        # entries are neither stale nor safe to drop on a rewrite
        return scope(key) and key.split("::", 3)[1] not in error_paths

    if write_baseline:
        if select:
            return RunResult(
                2, [], [], [], ["--write-baseline with --select would "
                                "erase other rules' entries"],
                "swarmlint: refusing --write-baseline with --select — a "
                "partial rule run cannot regenerate the full baseline")
        if changed_only:
            return RunResult(
                2, [], [], [], ["--write-baseline with --changed-only "
                                "would regenerate from a partial run"],
                "swarmlint: refusing --write-baseline with --changed-only "
                "— a partial file run cannot regenerate the full baseline")
        if errors:
            # refuse to write a silently incomplete baseline
            report = "\n".join(
                [f"error: {e}" for e in errors]
                + ["swarmlint: baseline NOT written — fix unparseable "
                   "files first"])
            return RunResult(2, [], [], [], errors, report)
        # preserve entries this run never re-checked (out-of-scope paths)
        try:
            existing = baseline_mod.load_baseline(baseline_path).entries
        except Exception as exc:
            return RunResult(
                2, [], [], [], [f"{baseline_path}: {exc}"],
                f"swarmlint: cannot read existing baseline "
                f"{baseline_path}: {exc}")
        keep = {k: n for k, n in existing.items() if not in_scope(k)}
        n = baseline_mod.write_baseline(baseline_path, findings, keep)
        report = (f"wrote {n} baseline entr{'y' if n == 1 else 'ies'} "
                  f"({len(findings)} findings, {len(keep)} out-of-scope "
                  f"kept) to {baseline_path}")
        return RunResult(0, [], findings, [], errors, report)

    try:
        bl = baseline_mod.load_baseline(baseline_path)
    except Exception as exc:
        # truncated / merge-conflicted / wrong-schema baseline: bad
        # input (exit 2), not a lint failure
        return RunResult(
            2, [], [], [], [f"{baseline_path}: {exc}"],
            f"swarmlint: unreadable baseline {baseline_path}: {exc}")
    new, suppressed, stale = bl.split(findings, in_scope=in_scope)

    lines: list[str] = ([note] if note else []) + [f.render() for f in new]
    for key in stale:
        lines.append(
            f"stale baseline entry (finding no longer present — delete it "
            f"from {os.path.basename(baseline_path)}): {key}")
    for e in errors:
        lines.append(f"error: {e}")
    lines.append(
        f"swarmlint: {len(new)} finding{'s' if len(new) != 1 else ''}, "
        f"{len(suppressed)} baselined, {len(stale)} stale baseline "
        f"entr{'y' if len(stale) == 1 else 'ies'}")

    exit_code = 0
    if errors:
        exit_code = 2
    elif new or (strict and stale):
        exit_code = 1
    return RunResult(exit_code, new, suppressed, stale, errors,
                     "\n".join(lines),
                     checked_files=(len(only_files)
                                    if only_files is not None
                                    else len(files)),
                     total_files=len(files))
