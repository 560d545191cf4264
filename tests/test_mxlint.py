"""mxlint: per-rule fixture goldens + the tier-1 repo gate.

Two halves:

1. FIXTURES — every pass has a fixture under ``tools/mxlint/fixtures/``
   with positive, inline-suppressed and clean snippets; the goldens
   here pin the exact rule multiset (and spot-check anchor lines) so a
   pass that goes blind or trigger-happy fails loudly.
2. THE GATE — the real passes run over the acceptance scope
   (``mxnet_tpu/``, ``tools/``) and must report ZERO
   unbaselined findings with an EMPTY committed baseline; the README
   configuration reference must be regeneration-stable against
   ``mxnet_tpu/envvars.py``; the Grafana dashboard families must all
   exist. This is the CI contract from ISSUE 6.

No jax / device work anywhere here — the linter is pure stdlib AST.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tools.mxlint import core  # noqa: E402
from tools.mxlint.passes import all_passes  # noqa: E402
from tools.mxlint.passes.env_registry import (  # noqa: E402
    EnvRegistryPass, load_envvar_registry)
from tools.mxlint.passes.telemetry_consistency import (  # noqa: E402
    TelemetryConsistencyPass)

FIXTURES = os.path.join(ROOT, "tools", "mxlint", "fixtures")


def _lint_fixture(fname, relpath=None):
    with open(os.path.join(FIXTURES, fname), encoding="utf-8") as fh:
        source = fh.read()
    project = core.Project(root=ROOT)
    project.lint_source(source, relpath or f"fixtures/{fname}")
    project.finalize()
    return project, source


def _rules(project):
    return sorted(f.rule for f in project.findings)


def _line_mentions_rule(source, finding):
    """The fixture convention: every positive finding's anchor line
    carries a comment naming its rule (or the line right after, for
    findings anchored on multi-line statements)."""
    lines = source.splitlines()
    window = " ".join(lines[finding.line - 1:finding.line + 1])
    return finding.rule in window


# ---------------------------------------------------------------------------
# fixture goldens, one per pass
# ---------------------------------------------------------------------------

def test_fixture_lock_order():
    project, source = _lint_fixture("lock_order_fixture.py")
    assert _rules(project) == [
        "lock-blocking-call",       # time.sleep under lock
        "lock-blocking-call",       # urlopen under lock
        "lock-blocking-call",       # foreign Event.wait under lock
        "lock-blocking-call",       # thread join under lock
        "lock-callback",            # cb() under lock
        "lock-nested",              # via same-class method call
        "lock-nested",              # direct re-acquire
        "lock-order",               # the ABBA pair
    ]
    for f in project.findings:
        if f.rule in ("lock-blocking-call", "lock-callback"):
            assert _line_mentions_rule(source, f), f
    # the suppressed time.sleep was seen but silenced inline
    assert [f.rule for f in project.suppressed] == ["lock-blocking-call"]


def test_fixture_thread_hygiene():
    project, source = _lint_fixture("thread_hygiene_fixture.py")
    assert _rules(project) == [
        "executor-unnamed",         # ThreadPoolExecutor, no prefix
        "silent-except",
        "socketserver-daemon",      # UndecidedServer class
        "socketserver-daemon",      # bare ThreadingHTTPServer(...)
        "thread-daemon",            # unnamed_and_implicit
        "thread-daemon",            # named_but_undecided
        "thread-unjoined",
        "thread-unnamed",
    ]
    assert sorted(f.rule for f in project.suppressed) == [
        "executor-unnamed", "thread-daemon", "thread-unnamed"]
    silent = [f for f in project.findings if f.rule == "silent-except"]
    assert _line_mentions_rule(source, silent[0])
    for f in project.findings:
        if f.rule in ("executor-unnamed", "socketserver-daemon"):
            assert _line_mentions_rule(source, f), f


def test_fixture_telemetry_consistency():
    project, source = _lint_fixture("telemetry_fixture.py")
    assert _rules(project) == [
        "metric-engine-label",
        "metric-labels",
        "metric-tenant-label",
        "span-leak",
        "stage-name-registry",      # .labels(stage="warmupp")
        "stage-name-registry",      # match={"stage": "prefil"}
    ]
    leak = [f for f in project.findings if f.rule == "span-leak"]
    assert _line_mentions_rule(source, leak[0])
    tenant = [f for f in project.findings
              if f.rule == "metric-tenant-label"]
    assert "model" in tenant[0].message
    stages = [f for f in project.findings
              if f.rule == "stage-name-registry"]
    assert {"'warmupp'" in f.message or "'prefil'" in f.message
            for f in stages} == {True}
    for f in stages:
        assert _line_mentions_rule(source, f), f


def test_fixture_env_registry():
    project, source = _lint_fixture("env_registry_fixture.py")
    assert _rules(project) == [
        "env-raw-read",             # os.environ.get
        "env-raw-read",             # os.environ[...]
        "env-raw-read",             # os.getenv
        "env-raw-read",             # aliased env = os.environ.get
        "env-unregistered",
    ]
    assert [f.rule for f in project.suppressed] == ["env-raw-read"]
    unreg = [f for f in project.findings if f.rule == "env-unregistered"]
    assert "MXNET_TPU_NOT_A_REAL_KNOB" in unreg[0].message


def test_fixture_wire_safety():
    # the pass is scoped to the wire path: linted under a PRETEND
    # serving relpath it fires, under the fixture's real path it doesn't
    project, source = _lint_fixture("wire_safety_fixture.py",
                                    relpath="mxnet_tpu/serving/_fx.py")
    assert _rules(project) == [
        "wire-unsafe",              # import pickle
        "wire-unsafe",              # pickle.loads
        "wire-unsafe",              # eval
        "wire-unsafe",              # yaml.load
    ]
    assert [f.rule for f in project.suppressed] == ["wire-unsafe"]
    unscoped, _ = _lint_fixture("wire_safety_fixture.py")
    assert "wire-unsafe" not in _rules(unscoped)


def test_wire_safety_covers_loadgen_and_dump_tools():
    # ISSUE 11 satellite: the two tools that parse wire payloads off
    # live fleets are in scope now — the same fixture fires under
    # their relpaths
    for relpath in ("tools/serve_loadgen.py", "tools/telemetry_dump.py"):
        project, _ = _lint_fixture("wire_safety_fixture.py",
                                   relpath=relpath)
        assert "wire-unsafe" in _rules(project), relpath


def _lint_lock_graph_pair():
    project = core.Project(root=ROOT)
    # the whole-program pass only reports on full scans (a partial
    # graph would mis-resolve); the fixture pair stands in for one
    project.full_scan = True
    for fname in ("lock_graph_fixture_b.py", "lock_graph_fixture_a.py"):
        with open(os.path.join(FIXTURES, fname), encoding="utf-8") as fh:
            project.lint_source(fh.read(), f"fixtures/{fname}")
    project.finalize()
    return project


def test_fixture_lock_graph_cycle_via_callback():
    """The tentpole golden: router holds its lock entering the engine;
    the engine completes futures under ITS lock, firing the router's
    done-callback — a cycle NEITHER per-class pass can see. The
    finding must carry the full witness path."""
    project = _lint_lock_graph_pair()
    cycles = [f for f in project.findings if f.rule == "lock-graph-cycle"]
    assert len(cycles) == 1, project.findings
    msg = cycles[0].message
    # both legs of the witness, with the method chain spelled out
    assert "FixtureRouter._lock" in msg and "FixtureEngine._elock" in msg
    assert "FixtureRouter.submit" in msg          # leg 1: router->engine
    assert "FixtureEngine.submit" in msg          # leg 2: engine->callback
    assert "FixtureRouter._on_done" in msg        # the re-entry
    # the negative control participates in no cycle
    assert "CleanRouter" not in msg and "CleanEngine" not in msg


def test_fixture_lock_graph_blocking_escalation():
    project = _lint_lock_graph_pair()
    blocking = [f for f in project.findings
                if f.rule == "lock-graph-blocking"]
    assert len(blocking) == 1, project.findings
    assert "time.sleep()" in blocking[0].message
    assert "FixtureEngine.flush" in blocking[0].message
    # flush_quietly's identical shape was inline-suppressed
    assert "lock-graph-blocking" in [f.rule for f in project.suppressed]


def test_lock_graph_negative_control_alone_is_clean():
    """The clean pair linted WITHOUT the seeded classes: zero lock-graph
    findings (guards against the pass going trigger-happy on the
    snapshot-outside idiom itself)."""
    import re as _re
    project = core.Project(root=ROOT)
    project.full_scan = True
    for fname in ("lock_graph_fixture_b.py", "lock_graph_fixture_a.py"):
        with open(os.path.join(FIXTURES, fname), encoding="utf-8") as fh:
            src = fh.read()
        # keep only the Clean* halves of each fixture
        kept = _re.split(r"(?m)^class ", src)
        body = kept[0] + "".join("class " + part for part in kept[1:]
                                 if part.startswith("Clean")
                                 or part.startswith("FixtureFuture"))
        project.lint_source(body, f"fixtures/_clean_{fname}")
    project.finalize()
    assert [f for f in project.findings
            if f.rule.startswith("lock-graph")] == []


def test_lock_graph_blocking_survives_call_graph_cycle():
    """Review regression: mutually-recursive helpers must not freeze
    an incomplete transitive summary — the blocking call reachable
    only through the A<->B call cycle is still reported, regardless of
    method visit order."""
    src = '''
import threading
import time


class Pump:
    def __init__(self):
        self._lock = threading.Lock()

    def run(self):
        with self._lock:
            self.step_a()

    def step_a(self):
        self.step_b()

    def step_b(self):
        self.step_a()          # the cycle
        time.sleep(0.5)        # reachable only through it
'''
    project = core.Project(root=ROOT)
    project.full_scan = True
    project.lint_source(src, "fixtures/_cycle_pump.py")
    project.finalize()
    blocking = [f for f in project.findings
                if f.rule == "lock-graph-blocking"]
    assert len(blocking) == 1, project.findings
    assert "time.sleep()" in blocking[0].message


def test_lock_graph_silent_on_partial_scans():
    """Whole-program findings need the whole program: the same seeded
    pair linted WITHOUT full_scan (the --changed-only / explicit-path
    shape) reports nothing, so a pre-commit subset can never flag a
    finding the full CI graph disclaims."""
    project = core.Project(root=ROOT)
    for fname in ("lock_graph_fixture_b.py", "lock_graph_fixture_a.py"):
        with open(os.path.join(FIXTURES, fname), encoding="utf-8") as fh:
            project.lint_source(fh.read(), f"fixtures/{fname}")
    project.finalize()
    assert [f for f in project.findings
            if f.rule.startswith("lock-graph")] == []


def test_executor_positional_prefix_satisfies_rule():
    src = ("from concurrent.futures import ThreadPoolExecutor\n"
           "pool = ThreadPoolExecutor(4, 'mxnet_tpu_pool')\n")
    project = core.Project(root=ROOT)
    project.lint_source(src, "fixtures/_positional_prefix.py")
    project.finalize()
    assert "executor-unnamed" not in _rules(project)


def test_cli_write_baseline_rejects_changed_only():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.mxlint", "--changed-only",
         "--write-baseline"],
        cwd=ROOT, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 2
    assert "truncate" in proc.stderr


def test_fixture_clock_discipline():
    project, source = _lint_fixture("clocks_fixture.py")
    assert _rules(project) == [
        "wall-clock-delta",         # direct time.time() - t0
        "wall-clock-delta",         # tainted local
        "wall-clock-delta",         # tainted self attr
    ]
    assert [f.rule for f in project.suppressed] == ["wall-clock-delta"]
    for f in project.findings:
        assert _line_mentions_rule(source, f), f


def test_suppression_mechanics():
    project = core.Project(root=ROOT)
    project.lint_source(
        "import time\n"
        "# mxlint: disable-file=thread-unnamed\n"
        "import threading\n"
        "def f(t0):\n"
        "    # mxlint: disable=wall-clock-delta\n"
        "    d = time.time() - t0\n"
        "    t = threading.Thread(target=print, daemon=True)\n"
        "    return d, t\n",
        "fixtures/_inline.py")
    project.finalize()
    assert _rules(project) == []            # both silenced
    assert sorted(f.rule for f in project.suppressed) == [
        "thread-unnamed", "wall-clock-delta"]


def test_alert_rule_family_cross_check():
    # the SLO/alert layer's family references resolve like dashboard
    # queries: a rule over a renamed family must fail lint. Checked in
    # finalize under full_scan (declarations span the whole repo scan).
    p = TelemetryConsistencyPass()
    project = core.Project(root=ROOT, passes=[p])
    with open(os.path.join(FIXTURES, "telemetry_fixture.py"),
              encoding="utf-8") as fh:
        source = fh.read()
    project.lint_source(source, "fixtures/telemetry_fixture.py")
    project.full_scan = True
    findings = [f for f in project.finalize()
                if f.rule == "alert-rule-family"]
    fams = sorted(f.message.split("family ")[1].split()[0]
                  for f in findings)
    # the kwarg ref AND the signature default fire; the rule over the
    # fixture-declared family does not
    assert fams == ["mxnet_tpu_fixture_default_gone_ms",
                    "mxnet_tpu_fixture_gone_total"], findings
    for f in findings:
        assert _line_mentions_rule(source, f), f


def test_history_rule_family_cross_check():
    # the history config's recording rules cross-check the same way:
    # capturing a renamed family stores nothing and every retro query
    # over it comes back empty — that must fail lint, while a rule
    # over a declared family stays clean
    p = TelemetryConsistencyPass()
    project = core.Project(root=ROOT, passes=[p])
    with open(os.path.join(FIXTURES, "telemetry_fixture.py"),
              encoding="utf-8") as fh:
        source = fh.read()
    project.lint_source(source, "fixtures/telemetry_fixture.py")
    project.full_scan = True
    findings = [f for f in project.finalize()
                if f.rule == "history-rule-family"]
    fams = sorted(f.message.split("family ")[1].split()[0]
                  for f in findings)
    assert fams == ["mxnet_tpu_fixture_history_gone_total"], findings
    for f in findings:
        assert _line_mentions_rule(source, f), f


def test_dashboard_cross_check_fires_when_family_missing():
    # a full-scan project that declared NO families must flag every
    # family the committed Grafana dashboard queries
    p = TelemetryConsistencyPass()
    project = core.Project(root=ROOT, passes=[p])
    project.lint_source("x = 1\n", "fixtures/_empty.py")
    project.full_scan = True
    findings = project.finalize()
    dash = [f for f in findings if f.rule == "dashboard-family"]
    assert dash, "dashboard cross-check never fired"
    assert any("mxnet_tpu_serving_requests_total" in f.message
               for f in dash)


# ---------------------------------------------------------------------------
# the env registry itself
# ---------------------------------------------------------------------------

def test_envvar_registry_typing(monkeypatch):
    mod = load_envvar_registry(ROOT)
    monkeypatch.delenv("MXNET_TPU_SPANS", raising=False)
    assert mod.get("MXNET_TPU_SPANS") is True
    monkeypatch.setenv("MXNET_TPU_SPANS", "0")
    assert mod.get("MXNET_TPU_SPANS") is False
    monkeypatch.setenv("MXNET_TPU_TRACE_BUFFER", "128")
    assert mod.get("MXNET_TPU_TRACE_BUFFER") == 128
    monkeypatch.setenv("MXNET_TPU_TRACE_BUFFER", "not-an-int")
    assert mod.get("MXNET_TPU_TRACE_BUFFER") == 64      # typo -> default
    monkeypatch.setenv("MXNET_TPU_WATCHDOG_STALL_S", "2.5")
    assert mod.get("MXNET_TPU_WATCHDOG_STALL_S") == 2.5
    monkeypatch.delenv("MXNET_TPU_EVENT_LOG_MAX_MB", raising=False)
    assert mod.get("MXNET_TPU_EVENT_LOG_MAX_MB") is None
    with pytest.raises(KeyError):
        mod.get("MXNET_TPU_NOT_A_REAL_KNOB")
    assert mod.get_raw("MXNET_TPU_SPANS") == "0"
    # every declared name is a real MXNET_TPU_* name with a doc
    for var in mod.all_vars():
        assert var.name.startswith("MXNET_TPU_")
        assert var.doc


def test_every_registered_variable_has_a_reader():
    """A registered variable that nothing reads is a knob that outlived
    its reader: delete the entry with the code that read it. The reads
    are the linter's own (what ``env-unregistered`` checks); the
    ``tests``-scope names are read raw, before the package may be
    imported, by the harness."""
    reads = EnvRegistryPass()
    core.run(root=ROOT, passes=[reads])         # mxnet_tpu/ and tools/
    read = {key for key, _, _ in reads.envvar_calls}
    harness = ""
    for path in core.iter_python_files(
            ROOT, ("tests", "__graft_entry__.py", "chip_smoke.py")):
        with open(path, encoding="utf-8") as fh:
            harness += fh.read()
    unread = [v.name for v in load_envvar_registry(ROOT).all_vars()
              if v.name not in read
              and not (v.scope == "tests" and v.name in harness)]
    assert unread == []


# ---------------------------------------------------------------------------
# the tier-1 gate
# ---------------------------------------------------------------------------

def test_repo_gate_zero_unbaselined_findings():
    project = core.run(root=ROOT)
    baseline = core.load_baseline(ROOT)
    new = [f for f in project.findings if f.key() not in baseline]
    assert not new, (
        "unbaselined mxlint findings (fix them or inline-suppress "
        "with justification):\n" + "\n".join(map(repr, new)))


def test_baseline_is_empty():
    """The acceptance bar: the committed baseline carries ZERO debt —
    in particular nothing from the lock-order, wire-safety or
    telemetry-consistency passes may ever be baselined away."""
    with open(core.baseline_path(ROOT), encoding="utf-8") as fh:
        data = json.load(fh)
    assert data["findings"] == []


def test_envdoc_is_regeneration_stable():
    """README's generated configuration reference matches the registry
    exactly (i.e. --write-envdoc would be a no-op)."""
    from tools.mxlint.__main__ import ENVDOC_BEGIN, ENVDOC_END
    mod = load_envvar_registry(ROOT)
    with open(os.path.join(ROOT, "README.md"), encoding="utf-8") as fh:
        text = fh.read()
    assert ENVDOC_BEGIN in text and ENVDOC_END in text
    body = text.split(ENVDOC_BEGIN, 1)[1].split(ENVDOC_END, 1)[0]
    assert body.strip() == mod.markdown_table().strip()
    for var in mod.ENVVARS.values():
        assert f"`{var.name}`" in body, f"{var.name} missing from table"


def test_ast_cache_shared_across_runs():
    """ISSUE 11 satellite: one parse per (file, mtime, size) per
    process — the repo gate, the alert cross-check and every fixture
    test share contexts instead of re-parsing the scope."""
    p = os.path.join(ROOT, "tools", "mxlint", "core.py")
    c1 = core.cached_context(p, "tools/mxlint/core.py")
    c2 = core.cached_context(p, "tools/mxlint/core.py")
    assert c1 is c2
    assert c1.tree is c2.tree
    # the shared preorder node list is computed once too
    assert c1.nodes is c2.nodes
    # a run() consumes the cached context rather than re-parsing
    project = core.run(root=ROOT, paths=("tools/mxlint/core.py",))
    assert any(ctx is c1 for ctx in project.contexts)


def test_warm_cache_parallel_jobs_matches_serial():
    from tools.mxlint.core import _CTX_CACHE
    paths = ("tools/mxlint",)
    serial = core.run(root=ROOT, paths=paths)
    serial_keys = sorted(f.key() for f in serial.findings)
    _CTX_CACHE.clear()
    n = core.warm_cache(ROOT, paths, jobs=2)
    assert n >= 5
    warm = core.run(root=ROOT, paths=paths)
    assert sorted(f.key() for f in warm.findings) == serial_keys


def test_changed_files_scope_filtered():
    rels = core.changed_files(ROOT)
    for rel in rels:
        assert rel.endswith(".py"), rel
        assert rel.split("/")[0] in ("mxnet_tpu", "tools"), rel
        assert "fixtures" not in rel.split("/"), rel


def test_cli_changed_only_exits_zero():
    # the repo gate holds zero unbaselined findings on the FULL scope,
    # so any changed-only subset must be clean too
    proc = subprocess.run(
        [sys.executable, "-m", "tools.mxlint", "--changed-only", "-q"],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_smoke_exits_zero():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.mxlint", "-q"],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "unbaselined" in proc.stdout


def test_cli_list_rules():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.mxlint", "--list-rules"],
        cwd=ROOT, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0
    for rule in ("lock-blocking-call", "thread-unnamed", "metric-labels",
                 "env-raw-read", "wire-unsafe", "wall-clock-delta",
                 "lock-graph-cycle", "lock-graph-blocking",
                 "executor-unnamed", "socketserver-daemon"):
        assert rule in proc.stdout
