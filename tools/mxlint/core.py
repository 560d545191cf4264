"""mxlint framework: findings, suppressions, baseline, pass pipeline.

One :class:`Project` per run. Every file is parsed ONCE — into a
process-wide ``(mtime, size)``-keyed cache shared by ALL passes and
ALL runs in the process (the tier-1 gate, the alert cross-check test
and the CLI smoke each run full scans; without the cache every one of
them re-parsed and re-tokenized the whole scope). Each registered pass
visits the shared tree and appends :class:`Finding`\\ s; passes that
need cross-file state (label-set consistency, dashboard cross-check,
env-registry membership, the whole-program lock graph) accumulate it
on themselves during the per-file phase and emit project findings in
``finalize``. Trees in the cache are shared: passes MUST treat them as
immutable.
"""
from __future__ import annotations

import ast
import io
import json
import os
import re
import tokenize

__all__ = ["Finding", "FileContext", "LintPass", "Project",
           "iter_python_files", "lint_file", "load_baseline", "run",
           "cached_context", "warm_cache", "changed_files",
           "DEFAULT_PATHS", "repo_root"]

#: the acceptance scope: the package and the tools
DEFAULT_PATHS = ("mxnet_tpu", "tools")

#: directories never scanned (fixtures hold INTENTIONAL violations)
_SKIP_PARTS = ("__pycache__", "fixtures", ".jax_cache", "dashboards")

_SUPPRESS_RE = re.compile(
    r"#\s*mxlint:\s*disable(?P<file>-file)?\s*=\s*"
    r"(?P<rules>[a-z0-9_,\-\s]+)")


def repo_root():
    return os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


class Finding:
    """One diagnostic: rule id, repo-relative path, position, message."""

    __slots__ = ("rule", "path", "line", "col", "message")

    def __init__(self, rule, path, line, col, message):
        self.rule = rule
        self.path = path
        self.line = int(line)
        self.col = int(col)
        self.message = message

    def key(self):
        """Baseline identity. Line numbers are EXCLUDED so unrelated
        edits above a baselined finding don't un-baseline it; the
        message carries enough context to stay unique in practice."""
        return f"{self.path}::{self.rule}::{self.message}"

    def sort_key(self):
        return (self.path, self.line, self.col, self.rule)

    def __repr__(self):
        return (f"{self.path}:{self.line}:{self.col}: "
                f"[{self.rule}] {self.message}")


class FileContext:
    """One parsed file + its suppression map.

    Suppression scanning needs a full tokenize — by far the most
    expensive per-file step after parsing — so it runs LAZILY on the
    first ``suppressed()`` query: a clean file (the common case) never
    tokenizes at all."""

    def __init__(self, path, relpath, source, tree):
        self.path = path
        self.relpath = relpath
        self.source = source
        self.tree = tree
        self.line_suppress = None   # line -> set(rules), lazy
        self.file_suppress = None   # rules suppressed file-wide, lazy
        self._nodes = None

    @property
    def nodes(self):
        """Flat preorder walk of the tree, computed once and cached on
        the (process-cached) context: passes iterate this list instead
        of each re-running ``ast.walk`` — the walk, not the parse, is
        the dominant cost of a scan once trees are cached."""
        if self._nodes is None:
            self._nodes = list(ast.walk(self.tree))
        return self._nodes

    def _scan_suppressions(self):
        self.line_suppress = {}
        self.file_suppress = set()
        lines = self.source.splitlines()
        try:
            tokens = tokenize.generate_tokens(
                io.StringIO(self.source).readline)
            for tok in tokens:
                if tok.type != tokenize.COMMENT:
                    continue
                m = _SUPPRESS_RE.search(tok.string)
                if not m:
                    continue
                rules = {r.strip() for r in m.group("rules").split(",")
                         if r.strip()}
                if m.group("file"):
                    self.file_suppress |= rules
                    continue
                line = tok.start[0]
                self.line_suppress.setdefault(line, set()).update(rules)
                # a comment ALONE on its line covers the next line (the
                # statement it annotates)
                prefix = lines[line - 1][:tok.start[1]]
                if not prefix.strip():
                    self.line_suppress.setdefault(line + 1,
                                                  set()).update(rules)
        except (tokenize.TokenError, IndentationError):
            pass

    def suppressed(self, finding):
        if self.file_suppress is None:
            self._scan_suppressions()
        if finding.rule in self.file_suppress or "all" in self.file_suppress:
            return True
        rules = self.line_suppress.get(finding.line, ())
        return finding.rule in rules or "all" in rules

    def finding(self, rule, node, message):
        return Finding(rule, self.relpath, getattr(node, "lineno", 1),
                       getattr(node, "col_offset", 0), message)


class LintPass:
    """Base pass: subclass, set ``name``/``rules``, implement
    ``check(ctx) -> list[Finding]``; optionally ``applies(relpath)``
    to scope the pass and ``finalize(project) -> list[Finding]`` for
    cross-file checks."""

    name = "base"
    rules = ()

    def applies(self, relpath):
        return True

    def check(self, ctx):
        return []

    def finalize(self, project):
        return []


class Project:
    """One lint run: root, pass instances, findings, counts."""

    def __init__(self, root=None, passes=None):
        from . import passes as _passes
        self.root = os.path.abspath(root or repo_root())
        self.passes = passes if passes is not None else _passes.all_passes()
        self.findings = []          # unsuppressed findings
        self.suppressed = []        # findings silenced inline
        self.contexts = []
        self.full_scan = False      # True when the default scope ran

    # -- scanning ----------------------------------------------------------
    def lint_source(self, source, relpath):
        """Lint one in-memory source blob (the fixture-test entry)."""
        try:
            tree = ast.parse(source, filename=relpath)
        except SyntaxError as e:
            f = Finding("syntax-error", relpath, e.lineno or 1, 0,
                        f"file does not parse: {e.msg}")
            self.findings.append(f)
            return [f]
        ctx = FileContext(os.path.join(self.root, relpath), relpath,
                          source, tree)
        return self._lint_context(ctx)

    def _lint_context(self, ctx):
        self.contexts.append(ctx)
        out = []
        for p in self.passes:
            if not p.applies(ctx.relpath):
                continue
            for f in p.check(ctx):
                (self.suppressed if ctx.suppressed(f)
                 else self.findings).append(f)
                out.append(f)
        return out

    def lint_path(self, path):
        relpath = os.path.relpath(os.path.abspath(path),
                                  self.root).replace(os.sep, "/")
        ctx = cached_context(path, relpath)
        if isinstance(ctx, Finding):
            self.findings.append(ctx)
            return [ctx]
        return self._lint_context(ctx)

    def finalize(self):
        ctx_by_path = {c.relpath: c for c in self.contexts}
        for p in self.passes:
            for f in p.finalize(self):
                ctx = ctx_by_path.get(f.path)
                if ctx is not None and ctx.suppressed(f):
                    self.suppressed.append(f)
                else:
                    self.findings.append(f)
        self.findings.sort(key=Finding.sort_key)
        return self.findings


# -- shared AST cache -------------------------------------------------------
#
# One parse + one tokenize per (file, mtime, size) per PROCESS. The
# FileContext itself is cached (tree + suppression maps) because both
# are pure functions of the bytes; syntax errors cache as the Finding
# they produce. ~4 full scans run per test session — this turns three
# of them into dict lookups.

_CTX_CACHE = {}


def cached_context(path, relpath):
    """A (possibly cached) :class:`FileContext` for ``path``, or a
    ``syntax-error`` :class:`Finding` when the file does not parse."""
    path = os.path.abspath(path)
    try:
        st = os.stat(path)
        key = (st.st_mtime_ns, st.st_size, relpath)
    except OSError:
        key = None
    hit = _CTX_CACHE.get(path)
    if key is not None and hit is not None and hit[0] == key:
        return hit[1]
    with open(path, encoding="utf-8") as fh:
        source = fh.read()
    try:
        tree = ast.parse(source, filename=relpath)
        ctx = FileContext(path, relpath, source, tree)
    except SyntaxError as e:
        ctx = Finding("syntax-error", relpath, e.lineno or 1, 0,
                      f"file does not parse: {e.msg}")
    if key is not None:
        _CTX_CACHE[path] = (key, ctx)
    return ctx


def _warm_one(args):
    """Parse+tokenize one file (``--jobs`` worker; module-level so it
    pickles). Returns ``(path, key, ctx-or-finding)``."""
    path, relpath = args
    ctx = cached_context(path, relpath)
    key = _CTX_CACHE.get(path, (None,))[0]
    return path, key, ctx


def warm_cache(root, paths=DEFAULT_PATHS, jobs=1):
    """Pre-populate the context cache, optionally with ``jobs``
    parallel worker processes (parse + tokenize dominate a cold scan;
    pass checks stay serial — they accumulate cross-file state)."""
    work = [(p, os.path.relpath(p, root).replace(os.sep, "/"))
            for p in iter_python_files(root, paths)]
    if jobs <= 1 or len(work) < 4:
        for item in work:
            _warm_one(item)
        return len(work)
    import concurrent.futures
    import multiprocessing
    # spawn, not fork: the pytest host process carries multithreaded
    # JAX — a forked child can inherit a held allocator lock and wedge
    # inside _warm_one forever
    with concurrent.futures.ProcessPoolExecutor(
            max_workers=jobs,
            mp_context=multiprocessing.get_context("spawn")) as ex:
        for path, key, ctx in ex.map(_warm_one, work, chunksize=8):
            if key is not None:
                _CTX_CACHE[path] = (key, ctx)
    return len(work)


def iter_python_files(root, paths=DEFAULT_PATHS):
    for rel in paths:
        top = os.path.join(root, rel)
        if os.path.isfile(top):
            if top.endswith(".py"):
                yield top
            continue
        for dirpath, dirnames, filenames in os.walk(top):
            dirnames[:] = sorted(d for d in dirnames
                                 if d not in _SKIP_PARTS)
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    yield os.path.join(dirpath, fn)


def changed_files(root, base="HEAD"):
    """Repo-relative ``.py`` paths inside the acceptance scope that are
    modified vs ``base`` or untracked (the ``--changed-only``
    pre-commit/CI fast path). Returns a sorted list; empty when git is
    unavailable or nothing changed."""
    import subprocess
    seen = set()
    for cmd in (["git", "diff", "--name-only", base, "--"],
                ["git", "ls-files", "--others", "--exclude-standard"]):
        try:
            proc = subprocess.run(cmd, cwd=root, capture_output=True,
                                  text=True, timeout=30)
        except OSError:
            continue
        if proc.returncode == 0:
            seen.update(ln.strip() for ln in proc.stdout.splitlines()
                        if ln.strip())
    out = []
    for rel in sorted(seen):
        if not rel.endswith(".py"):
            continue
        if any(part in _SKIP_PARTS for part in rel.split("/")):
            continue
        for scope in DEFAULT_PATHS:
            if rel == scope or rel.startswith(scope.rstrip("/") + "/"):
                if os.path.exists(os.path.join(root, rel)):
                    out.append(rel)
                break
    return out


def run(root=None, paths=None, passes=None):
    """Lint ``paths`` (default: the acceptance scope) under ``root``.
    Returns the finalized :class:`Project`."""
    project = Project(root=root, passes=passes)
    if paths is None:
        paths = DEFAULT_PATHS
        project.full_scan = True
    for path in iter_python_files(project.root, paths):
        project.lint_path(path)
    project.finalize()
    return project


def lint_file(path, root=None, passes=None):
    """Lint ONE file (fixture tests); returns (project, findings)."""
    project = Project(root=root, passes=passes)
    project.lint_path(path)
    project.finalize()
    return project


# -- baseline ---------------------------------------------------------------

def baseline_path(root=None):
    return os.path.join(root or repo_root(), "tools", "mxlint",
                        "baseline.json")


def load_baseline(root=None):
    try:
        with open(baseline_path(root), encoding="utf-8") as fh:
            data = json.load(fh)
    except (OSError, ValueError):
        return set()
    return set(data.get("findings", []))


def save_baseline(project, root=None):
    data = {"comment": "accepted pre-existing mxlint findings; keep "
                       "EMPTY — fix or inline-suppress instead",
            "findings": sorted(f.key() for f in project.findings)}
    with open(baseline_path(root), "w", encoding="utf-8") as fh:
        json.dump(data, fh, indent=2)
        fh.write("\n")
