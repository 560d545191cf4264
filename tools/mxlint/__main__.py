"""mxlint CLI.

::

    python -m tools.mxlint                  # lint the acceptance scope
    python -m tools.mxlint mxnet_tpu/serving
    python -m tools.mxlint --changed-only   # git-diff-scoped (pre-commit)
    python -m tools.mxlint --jobs 4         # parallel parse/tokenize
    python -m tools.mxlint --list-rules
    python -m tools.mxlint --write-baseline # accept current findings
    python -m tools.mxlint --write-envdoc   # regenerate README env table

Exit codes: 0 clean (or fully baselined), 1 unbaselined findings,
2 usage error. The tier-1 gate (``tests/test_mxlint.py``) runs the
default scope and asserts exit 0 with an EMPTY baseline.

``--changed-only`` lints only files modified vs HEAD (plus untracked)
so the pre-commit path is sub-second on a small diff; whole-repo
ABSENCE checks (dashboard families, README env rows, the repo-wide
lock graph) need the full scan and are skipped — CI still runs the
default scope.
"""
from __future__ import annotations

import argparse
import os
import sys

# runnable both as ``python -m tools.mxlint`` from the repo root and as
# a checkout-relative script
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

from tools.mxlint import core  # noqa: E402
from tools.mxlint import passes as pass_registry  # noqa: E402
from tools.mxlint.passes.env_registry import load_envvar_registry  # noqa: E402

ENVDOC_BEGIN = "<!-- mxlint:envdoc:begin (generated; edit " \
               "mxnet_tpu/envvars.py, then python -m tools.mxlint " \
               "--write-envdoc) -->"
ENVDOC_END = "<!-- mxlint:envdoc:end -->"


def write_envdoc(root):
    """Regenerate the README "Configuration reference" between the
    envdoc markers from the typed registry."""
    mod = load_envvar_registry(root)
    readme = os.path.join(root, "README.md")
    with open(readme, encoding="utf-8") as fh:
        text = fh.read()
    if ENVDOC_BEGIN not in text or ENVDOC_END not in text:
        print(f"mxlint: README.md lacks the envdoc markers "
              f"({ENVDOC_BEGIN!r} ... {ENVDOC_END!r})", file=sys.stderr)
        return 2
    head, rest = text.split(ENVDOC_BEGIN, 1)
    _, tail = rest.split(ENVDOC_END, 1)
    body = mod.markdown_table()
    out = head + ENVDOC_BEGIN + "\n\n" + body + "\n" + ENVDOC_END + tail
    if out != text:
        with open(readme, "w", encoding="utf-8") as fh:
            fh.write(out)
        print(f"mxlint: wrote configuration reference "
              f"({len(mod.ENVVARS)} variables) into README.md")
    else:
        print("mxlint: README configuration reference already current")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(prog="mxlint", description=__doc__)
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to lint (default: the acceptance "
                         "scope: mxnet_tpu/ tools/)")
    ap.add_argument("--root", default=None,
                    help="repo root (default: auto-detected)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="accept current findings into baseline.json")
    ap.add_argument("--write-envdoc", action="store_true",
                    help="regenerate the README configuration "
                         "reference from mxnet_tpu/envvars.py")
    ap.add_argument("--changed-only", action="store_true",
                    help="lint only files changed vs git HEAD (plus "
                         "untracked); skips whole-repo cross-checks")
    ap.add_argument("--jobs", type=int, default=1, metavar="N",
                    help="parse/tokenize files with N worker processes "
                         "(pass checks stay serial)")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("-q", "--quiet", action="store_true")
    args = ap.parse_args(argv)
    root = os.path.abspath(args.root) if args.root else core.repo_root()

    if args.list_rules:
        for cls in pass_registry.PASS_CLASSES:
            print(f"{cls.name}:")
            for rule in cls.rules:
                print(f"  {rule}")
        return 0
    if args.write_envdoc:
        return write_envdoc(root)

    paths = args.paths or None
    if args.changed_only:
        if paths:
            print("mxlint: --changed-only and explicit paths are "
                  "mutually exclusive", file=sys.stderr)
            return 2
        if args.write_baseline:
            print("mxlint: --write-baseline needs the full scan — "
                  "a --changed-only subset would truncate the "
                  "committed baseline to the diff's findings",
                  file=sys.stderr)
            return 2
        paths = core.changed_files(root)
        if not paths:
            print("mxlint: 0 changed files in scope")
            return 0
    if args.jobs > 1:
        core.warm_cache(root, paths or core.DEFAULT_PATHS,
                        jobs=args.jobs)
    project = core.run(root=root, paths=paths)
    baseline = core.load_baseline(root)
    new = [f for f in project.findings if f.key() not in baseline]
    stale = baseline - {f.key() for f in project.findings}

    if args.write_baseline:
        core.save_baseline(project, root)
        print(f"mxlint: baselined {len(project.findings)} findings")
        return 0

    if not args.quiet:
        for f in new:
            print(f)
    n_files = len(project.contexts)
    print(f"mxlint: {n_files} files, {len(new)} unbaselined findings "
          f"({len(project.findings) - len(new)} baselined, "
          f"{len(project.suppressed)} inline-suppressed"
          + (f", {len(stale)} stale baseline entries" if stale else "")
          + ")")
    return 1 if new else 0


if __name__ == "__main__":
    raise SystemExit(main())
