"""sdlint pass-manager: one parse per file, pluggable visitor passes,
per-line waivers, and a checked-in baseline ratchet.

Grown from ``utils/lint.py`` (the 135-line stdlib AST gate) into the
rigor layer the wedge postmortems of rounds 4-5 demanded: the single
most damaging production failure mode here is *conventional* — an
unguarded jax touchpoint that parks the lone job worker forever — and
conventions only hold when a test enforces them. The image ships no
external linters, so the framework is pure stdlib ``ast``.

Architecture
------------
- :class:`FileContext` parses each source file ONCE and hands every pass
  the same tree plus helpers (lines, scope, lazy parent map, waivers).
- :class:`AnalysisPass` is the plugin protocol: ``id`` + ``run(ctx)``
  yielding :class:`Finding` rows. Passes live in ``analysis/passes/``.
- :class:`PassManager` walks a tree, runs the registered passes, and
  drops findings waived on their own line:
  ``# lint: ok`` waives every pass; ``# lint: ok(pass-id, ...)`` waives
  only the named ones.
- The baseline ratchet (``analysis/baseline.txt``): pre-existing
  findings are keyed by ``relpath::pass-id::message`` (no line numbers,
  so unrelated edits don't churn the file) and allowed as a multiset;
  anything beyond the baseline is NEW and fails the run. Fixing an old
  finding leaves a stale entry — shrink the file with
  ``--update-baseline`` — so the debt only ratchets down.

Run: ``python -m spacedrive_tpu.analysis`` (exit 0 = no new findings).
``--json`` renders the same verdict machine-readably (editor/CI
tooling); ``--changed`` scopes the scan to files the working tree
touches vs HEAD (plus untracked) — the fast pre-commit form.
See docs/static-analysis.md for the pass list and workflow.
"""

from __future__ import annotations

import ast
import json
import re
import subprocess
import sys
import time
from collections import Counter
from dataclasses import dataclass
from pathlib import Path
from typing import Iterable, Iterator

#: matches both the blanket waiver ``# lint: ok`` and the scoped form
#: ``# lint: ok(pass-id, other-pass)``
WAIVER_RE = re.compile(r"#\s*lint:\s*ok(?:\s*\(([^)]*)\))?")

#: directory parts never scanned (build output, bench fixture cache)
SKIP_PARTS = ("_build", ".bench_cache", "__pycache__")


@dataclass(frozen=True)
class Finding:
    """One defect reported by one pass, pinned to a source line."""

    path: str       #: path as scanned (printable, clickable)
    relpath: str    #: posix path relative to the scan root (baseline key)
    lineno: int
    pass_id: str
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.lineno}: [{self.pass_id}] {self.message}"

    def as_dict(self) -> dict:
        return {"path": self.path, "relpath": self.relpath,
                "line": self.lineno, "pass": self.pass_id,
                "message": self.message}

    @property
    def baseline_key(self) -> str:
        # no lineno: baselined findings must survive unrelated edits above
        # them, or the ratchet would churn on every refactor
        return f"{self.relpath}::{self.pass_id}::{self.message}"


class FileContext:
    """Everything a pass needs about one file, parsed exactly once."""

    def __init__(self, path: Path, relpath: str, source: str,
                 tree: ast.Module) -> None:
        self.path = path
        self.relpath = relpath
        self.source = source
        self.lines = source.splitlines()
        self.tree = tree
        self._parents: dict[ast.AST, ast.AST] | None = None

    @classmethod
    def parse(cls, path: Path, root: Path | None = None) -> "FileContext":
        """Parse ``path``; raises SyntaxError (the manager converts it to a
        ``syntax`` finding so one broken file can't mask the rest)."""
        source = path.read_text()
        tree = ast.parse(source, filename=str(path))
        try:
            relpath = path.resolve().relative_to(
                (root or path.parent).resolve()).as_posix()
        except ValueError:
            relpath = path.name
        return cls(path, relpath, source, tree)

    # -- scoping -------------------------------------------------------------
    @property
    def top_dir(self) -> str:
        """First directory component under the scan root ('' for files at
        the root itself) — how passes scope to production subsystems."""
        return self.relpath.split("/")[0] if "/" in self.relpath else ""

    def in_dirs(self, *dirs: str) -> bool:
        return self.top_dir in dirs

    # -- structure helpers ---------------------------------------------------
    def parent(self, node: ast.AST) -> ast.AST | None:
        """Lazy parent map over the shared tree (built once, all passes)."""
        if self._parents is None:
            self._parents = {}
            for outer in ast.walk(self.tree):
                for child in ast.iter_child_nodes(outer):
                    self._parents[child] = outer
        return self._parents.get(node)

    def finding(self, lineno: int, pass_id: str, message: str) -> Finding:
        return Finding(str(self.path), self.relpath, lineno, pass_id, message)

    # -- waivers -------------------------------------------------------------
    def waived(self, lineno: int, pass_id: str) -> bool:
        if not (0 < lineno <= len(self.lines)):
            return False
        m = WAIVER_RE.search(self.lines[lineno - 1])
        if m is None:
            return False
        scoped = m.group(1)
        if scoped is None:
            return True  # blanket ``# lint: ok``
        return pass_id in {p.strip() for p in scoped.split(",") if p.strip()}


class AnalysisPass:
    """Plugin protocol: subclass, set ``id``, yield findings from run()."""

    id: str = ""
    description: str = ""

    def run(self, ctx: FileContext) -> Iterator[Finding]:
        raise NotImplementedError


class ProjectContext:
    """Every parsed file of one scan plus the lazily-built call graph —
    what a :class:`ProjectPass` analyzes. ``files`` maps relpath →
    :class:`FileContext` (parsed once by the manager, shared with the
    per-file passes)."""

    def __init__(self, files: dict[str, FileContext], root: Path) -> None:
        self.files = files
        self.root = root
        self._graph = None

    @property
    def graph(self):
        """The project call graph (analysis/callgraph.py), built on
        first use and shared by every project pass of the run."""
        if self._graph is None:
            from .callgraph import build_graph

            self._graph = build_graph(self.files, self.root.name)
        return self._graph


class ProjectPass(AnalysisPass):
    """A whole-program pass: sees every file of the scan at once (plus
    the call graph), so it can report the cross-module shapes —
    blocking I/O two calls below a lock, an event-loop stall through a
    helper in another module — that no per-file pass can."""

    def run_project(self, project: ProjectContext) -> Iterator[Finding]:
        raise NotImplementedError

    def run(self, ctx: FileContext) -> Iterator[Finding]:
        # a project pass has no per-file form; the manager routes it
        # through run_project() over however many files the scan holds
        return iter(())


def dotted_name(node: ast.AST) -> str | None:
    """'jax.numpy.zeros' for a Name/Attribute chain, else None. The shared
    call-classification helper every pass uses."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class PassManager:
    """Run registered passes over a file or tree; apply waivers.

    Per-file passes see one :class:`FileContext` at a time; project
    passes (:class:`ProjectPass`) see the whole parsed scan at once.
    ``check_file`` builds a single-file project (the fixture form:
    intra-file interprocedural analysis still works), ``check_tree``
    the full one, and ``check_changed`` parses the WHOLE tree to keep
    the call graph sound but prunes the project passes' reporting to
    the impacted component (changed functions plus every transitive
    caller — a callee edit can create or kill a finding anchored
    upstream)."""

    def __init__(self, passes: Iterable[AnalysisPass], root: Path) -> None:
        self.passes = list(passes)
        self.root = root
        self.file_passes = [p for p in self.passes
                            if not isinstance(p, ProjectPass)]
        self.project_passes = [p for p in self.passes
                               if isinstance(p, ProjectPass)]

    def _parse(self, path: Path) -> "tuple[FileContext | None, " \
                                    "Finding | None]":
        try:
            return FileContext.parse(path, self.root), None
        except SyntaxError as e:
            relpath = path.name
            try:
                relpath = path.resolve().relative_to(
                    self.root.resolve()).as_posix()
            except ValueError:
                pass
            return None, Finding(str(path), relpath, e.lineno or 0,
                                 "syntax", f"syntax error: {e.msg}")

    def _run_project(self, project: ProjectContext) -> list[Finding]:
        findings: list[Finding] = []
        for pp in self.project_passes:
            findings.extend(pp.run_project(project))
        return findings

    def _apply_waivers(self, findings: list[Finding],
                       files: dict[str, FileContext]) -> list[Finding]:
        out = []
        for f in findings:
            ctx = files.get(f.relpath)
            if ctx is not None and ctx.waived(f.lineno, f.pass_id):
                continue
            out.append(f)
        out.sort(key=lambda f: (f.relpath, f.lineno, f.pass_id, f.message))
        return out

    def check_file(self, path: Path) -> list[Finding]:
        ctx, syntax = self._parse(path)
        if ctx is None:
            return [syntax]
        findings: list[Finding] = []
        for ap in self.file_passes:
            findings.extend(ap.run(ctx))
        files = {ctx.relpath: ctx}
        findings.extend(self._run_project(ProjectContext(files, self.root)))
        return self._apply_waivers(findings, files)

    def _tree_paths(self) -> list[Path]:
        return [p for p in sorted(self.root.rglob("*.py"))
                if not any(part in SKIP_PARTS for part in p.parts)]

    def check_tree(self) -> list[Finding]:
        files: dict[str, FileContext] = {}
        findings: list[Finding] = []
        for path in self._tree_paths():
            ctx, syntax = self._parse(path)
            if ctx is None:
                findings.append(syntax)
                continue
            files[ctx.relpath] = ctx
            for ap in self.file_passes:
                findings.extend(ap.run(ctx))
        findings.extend(self._run_project(ProjectContext(files, self.root)))
        return self._apply_waivers(findings, files)

    def check_changed(self) -> tuple[list[Finding], list[str]]:
        """Scan only the ``*.py`` files under the root that git reports
        as modified vs HEAD or untracked — the fast pre-commit scope.
        The per-file passes run on exactly those files; the project
        passes run over the whole parsed tree (anything less would
        blind the call graph) with their findings pruned to the
        impacted component. Returns (findings, scanned-relpaths)."""
        changed = sorted(set(changed_files(self.root)))
        changed_rel: list[str] = []
        for path in changed:
            if any(part in SKIP_PARTS for part in path.parts):
                continue
            try:
                changed_rel.append(path.resolve().relative_to(
                    self.root.resolve()).as_posix())
            except ValueError:
                changed_rel.append(path.name)
        files: dict[str, FileContext] = {}
        findings: list[Finding] = []
        for path in self._tree_paths():
            ctx, syntax = self._parse(path)
            in_scope = syntax.relpath in changed_rel if ctx is None \
                else ctx.relpath in changed_rel
            if ctx is None:
                if in_scope:
                    findings.append(syntax)
                continue
            files[ctx.relpath] = ctx
            if in_scope:
                for ap in self.file_passes:
                    findings.extend(ap.run(ctx))
        if self.project_passes and changed_rel:
            project = ProjectContext(files, self.root)
            impacted = project.graph.impacted_files(changed_rel)
            findings.extend(f for f in self._run_project(project)
                            if f.relpath in impacted)
        return self._apply_waivers(findings, files), changed_rel


def changed_files(root: Path) -> list[Path]:
    """``*.py`` files under ``root`` the working tree touches: modified
    or added vs HEAD plus untracked (a brand-new module must not escape
    its own pre-commit run). Raises SystemExit outside a git checkout —
    --changed has no meaning there."""
    top = subprocess.run(["git", "rev-parse", "--show-toplevel"],
                         cwd=str(root), capture_output=True, text=True)
    repo = Path(top.stdout.strip()) if top.returncode == 0 else root
    # each command's output is anchored by ITS convention — `diff` prints
    # repo-toplevel-relative paths regardless of cwd, `ls-files --others`
    # prints cwd-relative ones. Resolving each against its own anchor
    # (instead of probing both) keeps a root-relative untracked name from
    # aliasing a same-named file at the repo toplevel (which would make
    # a brand-new module silently escape its own pre-commit run).
    cmds = (
        (repo, ["git", "diff", "--name-only", "-z", "HEAD", "--", "*.py"]),
        (root, ["git", "ls-files", "--others", "--exclude-standard", "-z",
                "--", "*.py"]),
    )
    paths: set[Path] = set()
    for anchor, cmd in cmds:
        try:
            # -z: NUL-separated, UNQUOTED names — without it git's
            # core.quotepath octal-escapes any non-ASCII filename and the
            # mangled path would silently fail the exists() check below
            proc = subprocess.run(cmd, cwd=str(root), capture_output=True,
                                  timeout=30)
        except (OSError, subprocess.TimeoutExpired) as e:
            raise SystemExit(f"--changed: cannot run git: {e}")
        if proc.returncode != 0:
            raise SystemExit("--changed needs a git checkout: "
                             + proc.stderr.decode(errors="replace").strip())
        for raw in proc.stdout.split(b"\0"):
            name = raw.decode("utf-8", errors="surrogateescape").strip()
            if name:
                paths.add((anchor / name).resolve())
    out: list[Path] = []
    for path in sorted(paths):
        if not path.exists():
            continue  # deleted files have no tree to scan
        try:
            path.relative_to(root.resolve())
        except ValueError:
            continue  # outside the scan root (tests/, bench.py, docs)
        out.append(path)
    return out


# -- baseline ratchet ---------------------------------------------------------

def load_baseline(path: Path) -> Counter:
    """Baseline file → multiset of finding keys. Missing file = empty."""
    counts: Counter = Counter()
    try:
        text = path.read_text()
    except OSError:
        return counts
    for line in text.splitlines():
        line = line.strip()
        if line and not line.startswith("#"):
            counts[line] += 1
    return counts


def save_baseline(path: Path, findings: Iterable[Finding]) -> None:
    keys = sorted(f.baseline_key for f in findings)
    header = ("# sdlint baseline — pre-existing findings the ratchet "
              "tolerates.\n"
              "# One `relpath::pass-id::message` per line; new findings "
              "beyond this\n"
              "# multiset fail the run. Regenerate (only to SHRINK it) "
              "with:\n"
              "#   python -m spacedrive_tpu.analysis --update-baseline\n")
    path.write_text(header + "".join(k + "\n" for k in keys))


def ratchet(findings: list[Finding],
            baseline: Counter) -> tuple[list[Finding], Counter]:
    """Split findings into (new, stale-baseline-entries). A finding is NEW
    when its key occurs more times than the baseline allows."""
    budget = Counter(baseline)
    new: list[Finding] = []
    for f in findings:
        if budget[f.baseline_key] > 0:
            budget[f.baseline_key] -= 1
        else:
            new.append(f)
    stale = +budget  # entries the tree no longer produces: shrinkable debt
    return new, stale


# -- CLI ----------------------------------------------------------------------

def default_root() -> Path:
    """The spacedrive_tpu package directory (what the suite gates)."""
    return Path(__file__).resolve().parents[1]


def default_baseline_path() -> Path:
    return Path(__file__).resolve().parent / "baseline.txt"


def build_manager(root: Path,
                  pass_ids: list[str] | None = None) -> PassManager:
    from .passes import all_passes

    passes = all_passes()
    if pass_ids:
        known = {p.id for p in passes}
        unknown = [pid for pid in pass_ids if pid not in known]
        if unknown:
            raise SystemExit(f"unknown pass id(s): {', '.join(unknown)} "
                             f"(known: {', '.join(sorted(known))})")
        passes = [p for p in passes if p.id in pass_ids]
    return PassManager(passes, root)


def main(argv: list[str] | None = None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        prog="python -m spacedrive_tpu.analysis",
        description="sdlint: multi-pass static analysis with a baseline "
                    "ratchet (exit 0 = no findings beyond the baseline)")
    parser.add_argument("root", nargs="?", default=None,
                        help="tree to scan (default: the spacedrive_tpu "
                             "package)")
    parser.add_argument("--baseline", default=None,
                        help="baseline file (default: analysis/baseline.txt)")
    parser.add_argument("--no-baseline", action="store_true",
                        help="report every finding; exit 1 if any")
    parser.add_argument("--update-baseline", action="store_true",
                        help="rewrite the baseline to the current findings "
                             "(use only to shrink debt or adopt a new pass)")
    parser.add_argument("--passes", default=None,
                        help="comma-separated pass ids to run (default: all)")
    parser.add_argument("--list-passes", action="store_true")
    fmt = parser.add_mutually_exclusive_group()
    fmt.add_argument("--json", action="store_true", dest="as_json",
                     help="machine-readable verdict on stdout (findings, "
                          "new, stale keys); exit code unchanged")
    fmt.add_argument("--sarif", action="store_true", dest="as_sarif",
                     help="SARIF 2.1.0 log on stdout (baselined findings "
                          "carry a suppression); exit code unchanged")
    parser.add_argument("--max-wall-s", type=float, default=None,
                        metavar="S",
                        help="fail (exit 1) if the scan itself takes longer "
                             "than S seconds — the pre-commit wall budget")
    parser.add_argument("--changed", action="store_true",
                        help="scan only *.py files modified vs HEAD or "
                             "untracked (git-scoped pre-commit run); the "
                             "ratchet still applies, stale entries for "
                             "unscanned files are not reported")
    args = parser.parse_args(argv)

    from .passes import all_passes

    if args.list_passes:
        for ap in all_passes():
            print(f"{ap.id:22s} {ap.description}")
        return 0

    root = Path(args.root) if args.root else default_root()
    baseline_path = (Path(args.baseline) if args.baseline
                     else default_baseline_path())
    pass_ids = ([p.strip() for p in args.passes.split(",") if p.strip()]
                if args.passes else None)
    manager = build_manager(root, pass_ids)
    scanned: list[str] | None = None
    t0 = time.monotonic()
    if args.changed:
        if args.update_baseline:
            raise SystemExit("--update-baseline needs the full tree "
                             "(a --changed subset would DROP every "
                             "baselined finding outside it)")
        findings, scanned = manager.check_changed()
    else:
        findings = manager.check_tree()
    # microseconds: a tiny tree scans in under a millisecond, and a wall
    # rounded to 0.0 would pass even a zero budget
    wall_s = round(time.monotonic() - t0, 6)
    over_budget = (args.max_wall_s is not None and wall_s > args.max_wall_s)

    if args.update_baseline:
        save_baseline(baseline_path, findings)
        if args.as_json:
            print(json.dumps({"baseline": str(baseline_path),
                              "rewritten": len(findings)}, indent=2))
        else:
            print(f"baseline rewritten: {len(findings)} finding(s) -> "
                  f"{baseline_path}")
        return 0

    if args.no_baseline:
        if args.as_sarif:
            print(json.dumps(_sarif_doc(findings, findings, manager, root),
                             indent=2))
        elif args.as_json:
            print(json.dumps({
                "root": str(root), "baseline": None,
                "scanned": scanned, "wall_s": wall_s,
                "findings": [f.as_dict() for f in findings],
                "new": [f.as_dict() for f in findings], "stale": [],
            }, indent=2))
        else:
            for f in findings:
                print(f.render())
            print(f"{len(findings)} finding(s) in {wall_s}s")
        if over_budget:
            print(f"WALL BUDGET EXCEEDED: {wall_s}s > "
                  f"{args.max_wall_s}s", file=sys.stderr)
            return 1
        return 1 if findings else 0

    new, stale = ratchet(findings, load_baseline(baseline_path))
    if scanned is not None:
        # a changed-scope run never visits most files, so their baseline
        # entries look "stale" — only report staleness the scan can see
        scanned_set = set(scanned)
        stale = Counter({k: v for k, v in stale.items()
                         if k.split("::", 1)[0] in scanned_set})
    if args.as_sarif:
        print(json.dumps(_sarif_doc(findings, new, manager, root), indent=2))
        if over_budget:
            print(f"WALL BUDGET EXCEEDED: {wall_s}s > "
                  f"{args.max_wall_s}s", file=sys.stderr)
            return 1
        return 1 if new else 0
    if args.as_json:
        print(json.dumps({
            "root": str(root), "baseline": str(baseline_path),
            "scanned": scanned, "wall_s": wall_s,
            "findings": [f.as_dict() for f in findings],
            "new": [f.as_dict() for f in new],
            "stale": sorted(stale.elements()),
        }, indent=2))
        if over_budget:
            print(f"WALL BUDGET EXCEEDED: {wall_s}s > "
                  f"{args.max_wall_s}s", file=sys.stderr)
            return 1
        return 1 if new else 0
    for f in new:
        print(f.render())
    print(f"{len(findings)} finding(s): {len(new)} new, "
          f"{len(findings) - len(new)} baselined, "
          f"{sum(stale.values())} stale baseline entr"
          f"{'y' if sum(stale.values()) == 1 else 'ies'} ({wall_s}s)")
    if stale:
        print("stale baseline entries (fixed findings — shrink with "
              "--update-baseline):")
        for key in sorted(stale):
            print(f"  {key}")
    if over_budget:
        print(f"WALL BUDGET EXCEEDED: {wall_s}s > "
              f"{args.max_wall_s}s", file=sys.stderr)
        return 1
    return 1 if new else 0


def _sarif_doc(findings: list[Finding], new: list[Finding],
               manager: PassManager, root: Path) -> dict:
    from .sarif import to_sarif

    return to_sarif(findings, new, manager.passes, root)
