"""pdtt-analyze (tools/analyze/): per-pass seeded-violation fixtures +
clean fixtures, baseline add/expire semantics, runner exit codes and
JSON output, the checker shims, the pass-catalog doc contract, and the
acceptance gate — the full analyzer over the repo with zero
unsuppressed findings."""

import io
import json
import os
import re
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

from tools.analyze import baseline as baseline_lib  # noqa: E402
from tools.analyze import cli, core  # noqa: E402
from tools.analyze.passes import (  # noqa: E402
    alert_catalog,
    event_catalog,
    fault_catalog,
    jit_purity,
    lock_order,
    lock_scope,
    metric_catalog,
    monotonic_clock,
    raw_store,
    thread_lifecycle,
    thread_shared,
)

FIXTURES = "tools/analyze/fixtures"


def run_pass(pass_cls, paths, repo_root=REPO, include=("**",)):
    p = pass_cls()
    p.include = include
    return p.run(core.build_context(repo_root, paths))


# ------------------------------------------------------------ framework
def test_registry_has_all_passes():
    assert set(core.all_passes()) == {
        "lock-scope", "monotonic-clock", "jit-purity", "fault-catalog",
        "event-catalog", "metric-catalog", "thread-shared-state",
        "trace-hygiene", "alert-catalog", "slo-catalog", "lock-order",
        "thread-lifecycle", "action-catalog", "raw-store"}


def test_pass_catalog_doc_is_the_registry_contract():
    """docs/static_analysis.md's '## Pass catalog' rows == registered
    ids — the same stance the fault/event/metric catalogs get (the
    doc now has OTHER tables, e.g. sanitizer finding kinds, so the
    parse is section-scoped through the shared helper)."""
    rows = core.doc_table_names(
        os.path.join(REPO, "docs", "static_analysis.md"),
        "## pass catalog", re.compile(r"^\|\s*`([a-z-]+)`\s*\|"))
    assert rows == set(core.all_passes())


def test_discovery_excludes_tests_and_fixtures():
    rels = core.discover(REPO)
    assert not any(r.startswith("tests/") for r in rels)
    assert not any(r.startswith(f"{FIXTURES}/") for r in rels)
    assert "pytorch_distributed_train_tpu/trainer.py" in rels
    assert "tools/serve_http.py" in rels


def test_finding_fingerprint_is_line_text_not_number():
    sf = core.SourceFile(REPO, os.path.join(FIXTURES, "monotonic_bad.py"))
    p = monotonic_clock.MonotonicClockPass()
    f = [x for x in run_pass(monotonic_clock.MonotonicClockPass,
                             [f"{FIXTURES}/monotonic_bad.py"])
         if x.line == 6][0]
    assert f.key == sf.line_text(6)
    assert f.fingerprint == (p.id, f"{FIXTURES}/monotonic_bad.py", f.key)


# ------------------------------------------------- per-pass fixtures
def test_lock_scope_catches_seeded_violations():
    findings = run_pass(lock_scope.LockScopePass,
                        [f"{FIXTURES}/lock_scope_bad.py"])
    msgs = "\n".join(f.message for f in findings)
    assert len(findings) == 7
    assert "time.sleep" in msgs and "subprocess.run" in msgs
    assert "_q.get" in msgs and "_done.wait" in msgs
    assert "`open(...)` (file I/O)" in msgs
    assert any("`_LOCK`" in f.message for f in findings)  # module lock
    # `with self._lock, open(...)`: the later withitem runs locked
    assert any("with self._lock, open" in f.key for f in findings)


def test_lock_scope_passes_clean_patterns():
    assert run_pass(lock_scope.LockScopePass,
                    [f"{FIXTURES}/lock_scope_clean.py"]) == []


def test_monotonic_clock_catches_seeded_violations():
    findings = run_pass(monotonic_clock.MonotonicClockPass,
                        [f"{FIXTURES}/monotonic_bad.py"])
    lines = {f.line for f in findings}
    assert lines == {6, 7, 13, 20, 28}  # deadline assign, while-compare,
    # tainted compare, timeout kwarg, self-attr taint across methods


def test_monotonic_clock_passes_clean_patterns():
    assert run_pass(monotonic_clock.MonotonicClockPass,
                    [f"{FIXTURES}/monotonic_clean.py"]) == []


def test_jit_purity_catches_seeded_violations():
    findings = run_pass(jit_purity.JitPurityPass,
                        [f"{FIXTURES}/jit_purity_bad.py"])
    msgs = "\n".join(f.message for f in findings)
    assert len(findings) == 7
    for needle in ("print()", "float()", "time.time()", "np.asarray()",
                   ".item()", "traced parameter"):
        assert needle in msgs
    # the wrapped (not decorated) function is found via jax.jit(f, ...)
    assert any("wrapped_step" in f.message for f in findings)


def test_jit_purity_passes_clean_patterns():
    assert run_pass(jit_purity.JitPurityPass,
                    [f"{FIXTURES}/jit_purity_clean.py"]) == []


def test_thread_shared_catches_seeded_violations():
    findings = run_pass(thread_shared.ThreadSharedStatePass,
                        [f"{FIXTURES}/thread_shared_bad.py"])
    attrs = {f.key for f in findings}
    # `result` is written by a TRANSITIVE thread callee (_run -> _finish)
    assert attrs == {"Worker.progress", "Worker.result"}
    assert all(f.severity == "warning" for f in findings)


def test_thread_shared_passes_clean_patterns():
    assert run_pass(thread_shared.ThreadSharedStatePass,
                    [f"{FIXTURES}/thread_shared_clean.py"]) == []


def test_lock_order_catches_seeded_cycles():
    findings = run_pass(lock_order.LockOrderPass,
                        [f"{FIXTURES}/lock_order_bad.py"])
    assert len(findings) == 2    # Pool AB/BA + Mixer vs module lock
    msgs = "\n".join(f.message for f in findings)
    assert "deadlock hazard" in msgs
    # both directions' acquisition paths are named, inter-procedurally:
    # reclaim -> _count closes the Pool cycle through a CALL
    assert "Pool.reclaim" in msgs and "Pool._count" in msgs
    assert "_MOD_LOCK" in msgs
    # keys are stable cycle identities (baselinable)
    assert all(f.key.startswith("cycle:") for f in findings)


def test_lock_order_passes_clean_patterns():
    assert run_pass(lock_order.LockOrderPass,
                    [f"{FIXTURES}/lock_order_clean.py"]) == []


def test_lock_order_graph_is_interprocedural_on_the_repo():
    """The repo graph must actually SEE the cross-subsystem chains the
    pass exists for (scheduler lock -> slo/tracer/registry locks) —
    an empty graph would make the cycle gate vacuously green."""
    graph = lock_order.build_graph(core.build_context(REPO))
    assert len(graph.nodes) >= 15
    svc = "tools/serve_http.py::BatcherService._lock"
    slo = "pytorch_distributed_train_tpu/serving_plane/slo.py::" \
        "SloTracker._lock"
    assert (svc, slo) in graph.edges
    # and the repo itself has no cycle (the acceptance state)
    assert graph.sccs() == []


def test_thread_lifecycle_catches_seeded_violations():
    findings = run_pass(thread_lifecycle.ThreadLifecyclePass,
                        [f"{FIXTURES}/thread_lifecycle_bad.py"])
    msgs = [f.message for f in findings]
    assert len(findings) == 4
    assert any("never joined" in m for m in msgs)
    assert any("constructed and dropped" in m for m in msgs)
    assert any("`.join()` while holding" in m for m in msgs)
    # the module-scope spawn (no enclosing def) is checked too
    assert any("module-scope thread" in m for m in msgs)


def test_thread_lifecycle_passes_clean_patterns():
    assert run_pass(thread_lifecycle.ThreadLifecyclePass,
                    [f"{FIXTURES}/thread_lifecycle_clean.py"]) == []


def test_raw_store_catches_seeded_violations():
    findings = run_pass(raw_store.RawStorePass,
                        [f"{FIXTURES}/raw_store_bad.py"])
    assert len(findings) == 5
    msgs = "\n".join(f.message for f in findings)
    # local name, attr taint across methods, and the unbound inline call
    assert "`store.get(...)`" in msgs
    assert "`self._store.set(...)`" in msgs
    assert "`StoreClient.get(...)`" in msgs
    assert all("ResilientStore" in f.message for f in findings)


def test_raw_store_passes_clean_patterns():
    # resilient wrapper handles + parameter-taking helpers are sanctioned
    assert run_pass(raw_store.RawStorePass,
                    [f"{FIXTURES}/raw_store_clean.py"]) == []


def test_raw_store_repo_surface_is_clean():
    """The production surface routes every store op through the
    resilience plane — the whole point of the wrapper PR; a new raw
    call site must fail here, not land in the baseline."""
    findings = raw_store.RawStorePass().run(core.build_context(REPO))
    assert findings == []


def _seed_live_copy(tmp_path, rel, extra):
    """Copy a LIVE repo file into a tmp tree at the same relative path
    and append a seeded violation — detection proven against real
    code, not just fixtures."""
    dst = tmp_path / rel
    dst.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(os.path.join(REPO, rel), dst)
    with open(dst, "a") as f:
        f.write(extra)
    return str(tmp_path), rel


def test_seeded_cycle_in_live_router_flips_gate(tmp_path):
    """Acceptance: a lock-order cycle seeded into the REAL
    serving_plane/router.py flips `python -m tools.analyze` to exit 1."""
    root, rel = _seed_live_copy(
        tmp_path, "pytorch_distributed_train_tpu/serving_plane/router.py",
        "\n\nclass _SeededCycle:\n"
        "    def __init__(self):\n"
        "        self._a = threading.Lock()\n"
        "        self._b = threading.Lock()\n"
        "    def one(self):\n"
        "        with self._a:\n"
        "            with self._b:\n"
        "                pass\n"
        "    def two(self):\n"
        "        with self._b:\n"
        "            self._back()\n"
        "    def _back(self):\n"
        "        with self._a:\n"
        "            pass\n")
    out = io.StringIO()
    rc = cli.main(["--no-baseline", "--root", root, "--only",
                   "lock-order", rel], out=out)
    assert rc == 1
    assert "deadlock hazard" in out.getvalue()
    # the live file WITHOUT the seed is clean
    out = io.StringIO()
    assert cli.main(["--no-baseline", "--only", "lock-order",
                     "pytorch_distributed_train_tpu/serving_plane/"
                     "router.py"], out=out) == 0


def test_seeded_unjoined_thread_in_live_collector_flips_gate(tmp_path):
    """Acceptance twin: an unjoined non-daemon thread seeded into the
    REAL obs/collector.py flips the gate to exit 1."""
    root, rel = _seed_live_copy(
        tmp_path, "pytorch_distributed_train_tpu/obs/collector.py",
        "\n\ndef _seeded_spawn():\n"
        "    t = threading.Thread(target=print)\n"
        "    t.start()\n"
        "    return t\n")
    out = io.StringIO()
    rc = cli.main(["--no-baseline", "--root", root, "--only",
                   "thread-lifecycle", rel], out=out)
    assert rc == 1
    assert "never joined" in out.getvalue()
    out = io.StringIO()
    assert cli.main(["--no-baseline", "--only", "thread-lifecycle",
                     "pytorch_distributed_train_tpu/obs/collector.py"],
                    out=out) == 0


# ------------------------------------------------- catalog passes
def _repo_with_docs(tmp_path, mutate=None):
    """Tmp repo root with real docs (optionally mutated) — catalog
    passes resolve docs/ against ctx.repo_root."""
    docs = tmp_path / "docs"
    docs.mkdir()
    for name in ("fault_tolerance.md", "observability.md"):
        shutil.copy(os.path.join(REPO, "docs", name), docs / name)
    if mutate:
        mutate(docs)
    return str(tmp_path)


def test_fault_catalog_clean_on_repo():
    assert fault_catalog.FaultCatalogPass().run(
        core.build_context(REPO, [])) == []


def test_fault_catalog_catches_seeded_doc_drift(tmp_path):
    def drop_row(docs):
        p = docs / "fault_tolerance.md"
        text = p.read_text()
        assert "| `step.crash`" in text
        p.write_text("\n".join(
            line for line in text.splitlines()
            if not line.startswith("| `step.crash`")))

    root = _repo_with_docs(tmp_path, drop_row)
    findings = fault_catalog.FaultCatalogPass().run(
        core.build_context(root, []))
    assert [f.key for f in findings] == ["undocumented:step.crash"]


def test_fault_catalog_catches_phantom_point(tmp_path):
    def add_row(docs):
        p = docs / "fault_tolerance.md"
        text = p.read_text()
        anchor = "| `step.crash`"
        i = text.index(anchor)
        p.write_text(text[:i] + "| `ghost.point` | x | x | x |\n"
                     + text[i:])

    root = _repo_with_docs(tmp_path, add_row)
    findings = fault_catalog.FaultCatalogPass().run(
        core.build_context(root, []))
    assert [f.key for f in findings] == ["phantom:ghost.point"]


def test_event_catalog_clean_on_repo():
    ctx = core.build_context(REPO)
    assert event_catalog.EventCatalogPass().run(ctx) == []


def test_event_catalog_catches_undeclared_emit(tmp_path):
    root = _repo_with_docs(tmp_path)
    src = tmp_path / "pytorch_distributed_train_tpu"
    src.mkdir()
    (src / "rogue.py").write_text(
        'def f(evl):\n    evl.emit("made_up_category", "boom")\n')
    # Full discovery over the tmp tree (not a partial path list): the
    # completeness directions only run on whole-surface contexts.
    findings = event_catalog.EventCatalogPass().run(
        core.build_context(root))
    assert any(f.key == "undeclared:made_up_category" for f in findings)
    assert any(f.key.startswith("unemitted:") for f in findings)


def test_alert_catalog_clean_on_repo():
    assert alert_catalog.AlertCatalogPass().run(
        core.build_context(REPO, [])) == []


def test_alert_catalog_catches_doc_drift_both_ways(tmp_path):
    def mutate(docs):
        p = docs / "observability.md"
        text = p.read_text()
        anchor = "| `loss_spike`"
        i = text.index(anchor)
        # phantom row added + a real rule's row dropped
        text = text[:i] + "| `ghost_rule` | anomaly | x | x | x |\n" \
            + text[i:]
        text = "\n".join(line for line in text.splitlines()
                         if not line.startswith("| `ttft_regression`"))
        p.write_text(text)

    root = _repo_with_docs(tmp_path, mutate)
    keys = {f.key for f in alert_catalog.AlertCatalogPass().run(
        core.build_context(root, []))}
    assert keys == {"phantom:ghost_rule", "undocumented:ttft_regression"}


def test_metric_catalog_clean_on_repo():
    ctx = core.build_context(REPO)
    assert metric_catalog.MetricCatalogPass().run(ctx) == []


def test_metric_catalog_catches_drift_and_unbounded_labels(tmp_path):
    def add_doc(docs):
        p = docs / "observability.md"
        text = p.read_text()
        anchor = "| `span_seconds`"
        i = text.index(anchor)
        # fixture_errors_total IS documented -> only its label fires;
        # a phantom row has no registration site.
        p.write_text(text[:i]
                     + "| `fixture_errors_total` | counter | — | x |\n"
                     + "| `phantom_metric_total` | counter | — | x |\n"
                     + text[i:])

    root = _repo_with_docs(tmp_path, add_doc)
    tools = tmp_path / "tools"
    tools.mkdir()
    shutil.copy(os.path.join(REPO, FIXTURES, "metric_labels_bad.py"),
                tools / "metric_labels_bad.py")
    findings = metric_catalog.MetricCatalogPass().run(
        core.build_context(root))   # full tmp-tree discovery
    keys = {f.key for f in findings}
    assert "undocumented:fixture_requests_total" in keys
    assert "undocumented:fixture_depth" in keys
    assert "phantom:phantom_metric_total" in keys
    assert "label:fixture_requests_total:rid" in keys      # raw id
    assert "label:fixture_errors_total:who" in keys        # f-string
    assert "label:fixture_depth:shard" in keys             # str(...)
    assert "label:fixture_requests_total:uid" in keys      # positional


# ---------------------------------------------------- baseline semantics
def _some_findings():
    return run_pass(monotonic_clock.MonotonicClockPass,
                    [f"{FIXTURES}/monotonic_bad.py"])


def test_baseline_suppresses_and_reports_stale(tmp_path):
    findings = _some_findings()
    keep, drop = findings[0], findings[1:]
    bl = baseline_lib.Baseline([
        {"pass": keep.pass_id, "path": keep.path, "key": keep.key,
         "reason": "intentional"},
        {"pass": "monotonic-clock", "path": "gone.py",
         "key": "x = 1", "reason": "expired long ago"},
    ])
    unsuppressed, suppressed, stale = bl.apply(findings)
    assert suppressed == [keep]
    assert sorted(f.key for f in unsuppressed) == sorted(
        f.key for f in drop)
    assert [e["path"] for e in stale] == ["gone.py"]


def test_baseline_write_then_load_roundtrip_and_expiry(tmp_path):
    findings = _some_findings()
    path = str(tmp_path / "baseline.json")
    n = baseline_lib.Baseline.write(path, findings)
    assert n == len(findings)
    bl = baseline_lib.Baseline.load(path)
    unsuppressed, suppressed, stale = bl.apply(findings)
    assert unsuppressed == [] and len(suppressed) == n and stale == []
    # Expiry: rewriting against FEWER findings drops the rest, but
    # keeps the reason of entries that survive.
    bl.entries[0]["reason"] = "curated why"
    survivor = [f for f in findings
                if (f.pass_id, f.path, f.key) == (bl.entries[0]["pass"],
                                                  bl.entries[0]["path"],
                                                  bl.entries[0]["key"])]
    baseline_lib.Baseline.write(path, survivor, previous=bl)
    bl2 = baseline_lib.Baseline.load(path)
    assert len(bl2.entries) == 1
    assert bl2.entries[0]["reason"] == "curated why"


def test_baseline_load_validates(tmp_path):
    p = tmp_path / "b.json"
    p.write_text(json.dumps({"suppressions": [{"pass": "x"}]}))
    with pytest.raises(ValueError):
        baseline_lib.Baseline.load(str(p))


# ------------------------------------------------------------- runner
def test_runner_exit_1_on_findings_and_text_output():
    out = io.StringIO()
    rc = cli.main(["--no-baseline", "--only", "monotonic-clock",
                   f"{FIXTURES}/monotonic_bad.py"], out=out)
    text = out.getvalue()
    assert rc == 1
    assert f"{FIXTURES}/monotonic_bad.py:6: [monotonic-clock]" in text
    assert re.search(r"analyze: \d+ finding", text)


def test_runner_exit_0_on_clean_paths():
    out = io.StringIO()
    rc = cli.main(["--no-baseline", "--only", "monotonic-clock",
                   f"{FIXTURES}/monotonic_clean.py"], out=out)
    assert rc == 0
    assert "0 finding(s)" in out.getvalue()


def test_runner_exit_2_on_unknown_pass():
    assert cli.main(["--only", "no-such-pass"], out=io.StringIO()) == 2


def test_runner_exit_2_on_nonexistent_path():
    """A typo'd explicit path is a usage error, not a green run over
    zero files."""
    assert cli.main(["--no-baseline", "no/such/file.py"],
                    out=io.StringIO()) == 2


def test_syntax_error_file_fails_the_gate(tmp_path):
    """An unparseable file is unenforced, not clean — the run reports
    a parse-error finding and exits 1."""
    src = tmp_path / "tools"
    src.mkdir()
    (src / "broken.py").write_text("def f(:\n")
    out = io.StringIO()
    rc = cli.main(["--no-baseline", "--root", str(tmp_path),
                   "--only", "monotonic-clock", "tools/broken.py"],
                  out=out)
    assert rc == 1
    assert "[parse-error]" in out.getvalue()


def test_runner_only_selects_passes():
    out = io.StringIO()
    rc = cli.main(["--no-baseline", "--only", "lock-scope",
                   f"{FIXTURES}/monotonic_bad.py"], out=out)
    assert rc == 0  # the monotonic violations are invisible to lock-scope


def test_runner_json_format():
    out = io.StringIO()
    rc = cli.main(["--no-baseline", "--format", "json", "--only",
                   "monotonic-clock,lock-scope",
                   f"{FIXTURES}/monotonic_bad.py",
                   f"{FIXTURES}/lock_scope_bad.py"], out=out)
    assert rc == 1
    data = json.loads(out.getvalue())
    assert data["counts"]["findings"] == len(data["findings"]) > 0
    byp = {f["pass"] for f in data["findings"]}
    # lock-scope's include scope (the concurrency planes) excludes the
    # fixtures dir when run through the real runner — scope is part of
    # the pass contract, so only monotonic-clock (scope **) fires here.
    assert byp == {"monotonic-clock"}
    f0 = data["findings"][0]
    assert {"pass", "path", "line", "severity", "message", "key"} <= set(f0)


def test_runner_baseline_flow(tmp_path):
    """--write-baseline then a suppressed run then stale reporting."""
    bl = str(tmp_path / "bl.json")
    out = io.StringIO()
    rc = cli.main(["--only", "monotonic-clock", "--baseline", bl,
                   "--write-baseline", f"{FIXTURES}/monotonic_bad.py"],
                  out=out)
    assert rc == 0 and "wrote" in out.getvalue()
    out = io.StringIO()
    rc = cli.main(["--only", "monotonic-clock", "--baseline", bl,
                   f"{FIXTURES}/monotonic_bad.py"], out=out)
    assert rc == 0
    assert "suppressed" in out.getvalue()
    # Against the clean fixture every entry is stale; still exit 0.
    out = io.StringIO()
    rc = cli.main(["--only", "monotonic-clock", "--baseline", bl,
                   f"{FIXTURES}/monotonic_clean.py"], out=out)
    assert rc == 0
    assert "stale baseline entry" in out.getvalue()


def test_runner_list_passes():
    out = io.StringIO()
    assert cli.main(["--list-passes"], out=out) == 0
    assert "monotonic-clock" in out.getvalue()


def test_runner_path_scoped_run_is_clean_on_a_clean_file():
    """A single-file run must not drown in false phantom/unemitted
    completeness findings (the catalog passes skip the whole-surface
    direction on partial contexts)."""
    out = io.StringIO()
    rc = cli.main(["--no-baseline", "tools/serve_http.py"], out=out)
    assert rc == 0, out.getvalue()


def test_scoped_write_baseline_preserves_out_of_scope_entries(tmp_path):
    """--only X --write-baseline must not delete justified suppressions
    belonging to other passes/files it never re-evaluated."""
    bl = str(tmp_path / "bl.json")
    foreign = {"pass": "monotonic-clock", "path": "other/file.py",
               "key": "while time.time() < deadline:",
               "reason": "curated: intentional"}
    with open(bl, "w") as f:
        json.dump({"suppressions": [foreign]}, f)
    rc = cli.main(["--only", "lock-scope", "--baseline", bl,
                   "--write-baseline", f"{FIXTURES}/monotonic_bad.py"],
                  out=io.StringIO())
    assert rc == 0
    entries = baseline_lib.Baseline.load(bl).entries
    assert foreign in entries
    # A FULL-scope rewrite still expires it (exact-rewrite semantics).
    rc = cli.main(["--baseline", bl, "--write-baseline"],
                  out=io.StringIO())
    assert rc == 0
    assert foreign not in baseline_lib.Baseline.load(bl).entries


def test_non_utf8_file_does_not_crash_the_run(tmp_path):
    src = tmp_path / "tools"
    src.mkdir()
    (src / "weird.py").write_bytes(b"# caf\xe9 comment, latin-1\nx = 1\n")
    out = io.StringIO()
    # --only: the bare tmp root has no docs/ for the catalog passes.
    rc = cli.main(["--no-baseline", "--root", str(tmp_path),
                   "--only", "monotonic-clock,lock-scope",
                   "tools/weird.py"], out=out)
    assert rc == 0, out.getvalue()


def test_runner_sarif_format():
    out = io.StringIO()
    rc = cli.main(["--no-baseline", "--format", "sarif", "--only",
                   "monotonic-clock", f"{FIXTURES}/monotonic_bad.py"],
                  out=out)
    assert rc == 1
    doc = json.loads(out.getvalue())
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "pdtt-analyze"
    assert [r["id"] for r in run["tool"]["driver"]["rules"]] == \
        ["monotonic-clock"]
    res = run["results"]
    assert res and all(r["ruleId"] == "monotonic-clock" for r in res)
    loc = res[0]["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == f"{FIXTURES}/monotonic_bad.py"
    assert loc["region"]["startLine"] >= 1
    assert res[0]["level"] == "error"
    assert "pdttFingerprint/v1" in res[0]["partialFingerprints"]


def _git(root, *args):
    env = dict(os.environ)
    env.update({"GIT_AUTHOR_NAME": "t", "GIT_AUTHOR_EMAIL": "t@x",
                "GIT_COMMITTER_NAME": "t", "GIT_COMMITTER_EMAIL": "t@x",
                "HOME": root})
    import subprocess

    r = subprocess.run(["git", "-C", root, *args], capture_output=True,
                       text=True, env=env)
    assert r.returncode == 0, r.stderr
    return r.stdout


def test_runner_changed_mode_scopes_to_git_diff(tmp_path):
    """--changed analyzes exactly the git-modified + untracked surface
    files; clean tree = exit 0 without analyzing anything."""
    root = str(tmp_path)
    tools = tmp_path / "tools"
    tools.mkdir()
    clean = 'def f():\n    return 1\n'
    bad = ('import time\n\n\ndef f(deadline_s):\n'
           '    deadline = time.time() + deadline_s\n'
           '    while time.time() < deadline:\n        pass\n')
    (tools / "a.py").write_text(clean)
    _git(root, "init", "-q")
    _git(root, "add", "-A")
    _git(root, "commit", "-qm", "seed")
    out = io.StringIO()
    assert cli.main(["--no-baseline", "--root", root, "--only",
                     "monotonic-clock", "--changed"], out=out) == 0
    assert "no changed files" in out.getvalue()
    # machine formats stay parseable on the clean-tree path (the
    # common case in a SARIF pipeline)
    out = io.StringIO()
    assert cli.main(["--no-baseline", "--root", root, "--format",
                     "sarif", "--changed"], out=out) == 0
    assert json.loads(out.getvalue())["runs"][0]["results"] == []
    # a tracked modification AND an untracked new file are both seen
    (tools / "a.py").write_text(bad)
    (tools / "b.py").write_text(bad)
    out = io.StringIO()
    rc = cli.main(["--no-baseline", "--root", root, "--only",
                   "monotonic-clock", "--changed"], out=out)
    assert rc == 1
    text = out.getvalue()
    assert "tools/a.py" in text and "tools/b.py" in text
    # committed again -> clean again
    _git(root, "add", "-A")
    _git(root, "commit", "-qm", "fix")
    out = io.StringIO()
    assert cli.main(["--no-baseline", "--root", root, "--only",
                     "monotonic-clock", "--changed"], out=out) == 0


def test_changed_and_paths_are_mutually_exclusive():
    assert cli.main(["--changed", "tools/serve_http.py"],
                    out=io.StringIO()) == 2


# ------------------------------------------------------------ shims
def test_checker_shims_still_green():
    import check_events
    import check_fault_points

    assert check_fault_points.main() == 0
    assert check_events.main() == 0
    from pytorch_distributed_train_tpu.faults.registry import POINTS

    assert check_fault_points.documented_points() == set(POINTS)


# ------------------------------------------------------- acceptance gate
@pytest.mark.analysis
def test_repo_is_clean_under_full_analyzer():
    """THE gate: every pass over the whole production surface, default
    baseline — zero unsuppressed findings, exit 0."""
    out = io.StringIO()
    rc = cli.main([], out=out)
    assert rc == 0, f"analyzer found violations:\n{out.getvalue()}"


@pytest.mark.analysis
def test_repo_monotonic_fixes_landed():
    """The satellite true-positive fixes stay fixed: no wall-clock
    deadline math left in elastic.py / serve_http.py."""
    findings = run_pass(
        monotonic_clock.MonotonicClockPass,
        ["pytorch_distributed_train_tpu/elastic.py", "tools/serve_http.py",
         "tools/sustained_drill.py"])
    assert findings == []
    text = open(os.path.join(
        REPO, "pytorch_distributed_train_tpu", "elastic.py")).read()
    assert "time.monotonic() + cfg.rendezvous_timeout_s" in text
