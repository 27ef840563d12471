"""Tier-0 graftlint gate (same spirit as test_collection_gate.py).

PR 1 fixed whole classes of bug by hand — the `from jax import
shard_map` import skew, the `update_paged_kv_cache` OOB block-table
write. graftlint encodes
those hunts as permanent rules; this gate makes a new violation fail CI
loudly.

Skip-proof by design: nothing in here calls pytest.skip, the analyzer
import happens INSIDE a test (so a broken tools/graftlint fails with a
traceback instead of erroring the module out of collection), and the
subprocess runs assert on exit codes with the linter output in the
failure message. graftlint is stdlib-ast-only, so these tests cost
milliseconds, not a jax import.
"""
import json
import os
import shutil
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_lint(*args):
    return subprocess.run(
        [sys.executable, "-m", "tools.graftlint", *args],
        capture_output=True, text=True, timeout=300, cwd=REPO_ROOT)


def test_graftlint_imports():
    # a broken/missing tools/graftlint must FAIL here, never skip
    sys.path.insert(0, REPO_ROOT)
    try:
        import tools.graftlint as gl
    finally:
        sys.path.remove(REPO_ROOT)
    assert len(gl.RULES) >= 32, sorted(gl.RULES)
    families = {r.family for r in gl.RULES.values()}
    assert families >= {"trace-safety", "pallas-bounds",
                        "hygiene", "donation", "concurrency",
                        "locksets"}, families
    # the observability PR's rules: interpret=True literals (GL104),
    # metrics record calls inside jitted functions (GL105); the
    # speculative-decode PR's rule: donated-buffer reuse (GL107); the
    # tracing PR's rule: jitted closures over self./module arrays
    # (GL108, the int4 compile-payload-bloat hazard); the SLO PR's
    # rule: dict/set keying on device arrays (GL110, the hash-forces-
    # a-sync hazard the prefix index's host-bytes block_key avoids);
    # the cost-observability PR's rule: wall-clock interval arithmetic
    # (GL111, time.time() differences as durations — NTP-step hazard);
    # the resilience PR's rule: unbounded metric label cardinality
    # (GL112, .labels() fed from loop variables / request identity —
    # one child series per distinct value, forever); the gateway PR's
    # rule: swallowed cancellation (GL113, a broad except in a
    # serve/step/stream loop that neither re-raises nor records a
    # structured terminal status — an infinite retry with no evidence);
    # the v2 PR's concurrency family, powered by the phase-1 project
    # index: blocking calls in async context incl. interprocedurally
    # reachable ones (GL114 — the gateway dump-read hazard), locks held
    # across blocking ops or compiled dispatch (GL115 — the flight-
    # recorder arm()-adoption hazard), fire-and-forget asyncio tasks
    # (GL116 — the gateway drain-task hazard), and stale/unknown
    # suppression comments (GL117 — suppression rot made visible);
    # the train-health PR's rule: daemon threads a long-lived object's
    # stop()/close() never joins (GL118 — the PsServer handler-thread
    # hazard; the comm watchdog's join-with-timeout is the clean shape);
    # the TP-serving PR's rule: end-of-stream sentinels dropped at
    # producer exit (GL119 — put_nowait in a finally with queue.Full
    # swallowed while a get() loop waits; the PR-14 DataLoader prefetch
    # hang, whose closed-flag retry loop is the clean shape);
    # the autotune PR's rule: inline mesh construction on the serving
    # hot path (GL120 — a fresh Mesh/NamedSharding per step is a new
    # jit cache key, so the dispatch it feeds recompiles every call;
    # build them once at __init__ like inference/__init__.py's
    # self._mesh and close over them);
    # the v3 lockset family, powered by per-object lock identity:
    # inconsistent-guard data races (GL121 — the stepper
    # `running`-reads-`error`-lock-free hazard the tree scan caught),
    # lock-order cycles incl. transitive holds-lock re-acquisition
    # (GL122), guarded collections iterated outside their lock from
    # another thread (GL123), and — hygiene, but born of the same
    # sweep — committed-JSON loads subscripted with no schema check or
    # degrade path (GL124, the serve_bench/step_profile traceEvents
    # shape);
    # the fleet-observability PR's rule: user-supplied callbacks
    # invoked while holding an in-tree lock (GL125 — the re-entrancy
    # deadlock GL122 cannot see until the callback's own lock is
    # in-tree; SparseTable's atomic admit+init is the reasoned
    # suppression, snapshot-then-call the clean shape);
    # the multi-replica router PR's rule: check-then-act splits across
    # two guarded regions of the same lock (GL126 — `if k in d` in one
    # `with`, `del d[k]` in a later one: the lock drops between check
    # and act; merged regions and re-validate-under-the-act's-lock are
    # the clean shapes);
    # the host-fast-path PR's rule: blocking waits under a CONTENDED
    # lock identity (GL127 — untimed Future.result()/IO while holding
    # a lock ≥2 execution contexts acquire; held = lexical ∪ entry
    # fixpoint, so the attribute-held future GL115 cannot track flags
    # too; timed waits, Condition.wait and snapshot-then-resolve are
    # the clean shapes)
    assert {"GL104", "GL105", "GL107", "GL108", "GL110", "GL111",
            "GL112", "GL113", "GL114", "GL115", "GL116",
            "GL117", "GL118", "GL119", "GL120", "GL121", "GL122",
            "GL123", "GL124", "GL125", "GL126", "GL127"} <= set(gl.RULES), \
        sorted(gl.RULES)


def test_tree_is_clean_within_budget_and_reports_phases():
    """ONE full-tree run (it costs ~20 s of the tier-1 window) answers
    three questions. The committed tree has zero non-baselined findings.
    The tier-0 gate stays CHEAP as rules accumulate: parse+index once,
    all rules incl. the lockset fixpoints, inside a hard wall budget —
    180s is the never-flake ceiling that still catches an accidental
    re-parse-per-rule regression (O(rules x files) ~ minutes). And the
    per-phase split is printed, so a regression is attributable."""
    import time
    t0 = time.monotonic()
    proc = _run_lint("paddle_tpu/", "tests/", "tools/")
    wall = time.monotonic() - t0
    assert proc.returncode == 0, (
        "graftlint found new violations — fix them, add a line-level "
        "`# graftlint: disable=CODE` with a reason, or (pre-existing "
        "triaged debt only) regenerate the baseline:\n"
        + proc.stdout + proc.stderr)
    assert wall < 180.0, f"full-tree graftlint took {wall:.1f}s"
    assert "phase1 parse+index" in proc.stdout, proc.stdout
    assert "phase2 rules" in proc.stdout, proc.stdout


def test_selftest_corpus():
    """Every rule family still catches its known-bad corpus."""
    proc = _run_lint("--selftest")
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_baseline_is_wellformed_and_minimal():
    path = os.path.join(REPO_ROOT, "tools", "graftlint_baseline.json")
    data = json.loads(open(path).read())
    assert data["version"] == 1
    # the baseline is a triage ledger, not a dumping ground: the debt it
    # carried (jax-0.4.x partial-auto shard_map sites) is gone, and new
    # findings get fixed instead of baselined
    assert data["findings"] == [], (
        f"baselined codes {sorted({e['code'] for e in data['findings']})}"
        " — fix new findings instead of baselining them")


def test_metrics_selfcheck():
    """The observability core's tier-0 selfcheck (tools/lint.sh runs the
    same command): registry correctness + all three exporters, loadable
    WITHOUT jax (stdlib-only contract)."""
    proc = subprocess.run(
        [sys.executable, os.path.join("tools", "metrics_snapshot.py"),
         "--selfcheck"],
        capture_output=True, text=True, timeout=300, cwd=REPO_ROOT)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "metrics selfcheck: OK" in proc.stdout, proc.stdout


def test_concurrency_corpus_roundtrip():
    """The GL114-GL119 concurrency corpus files plus the GL121-GL127
    lockset/hygiene files each reconstruct a fixed real hazard: caught
    codes fire exactly, clean tripwires stay silent (any unexpected
    code fails), and each file's suppression-honored demo is consumed
    (so GL117 does not flag it)."""
    sys.path.insert(0, REPO_ROOT)
    try:
        from tools.graftlint.core import lint_file
        from tools.graftlint.selftest import corpus_expectations
    finally:
        sys.path.remove(REPO_ROOT)
    from collections import Counter
    corpus = os.path.join(REPO_ROOT, "tools", "graftlint", "corpus")
    expected_files = {
        "blocking_async_handler.py": "GL114",
        "lock_across_blocking.py": "GL115",
        "fire_and_forget_task.py": "GL116",
        "stale_suppression.py": "GL117",
        "unjoined_thread_shutdown.py": "GL118",
        "dropped_queue_sentinel.py": "GL119",
        "lockset_inconsistent_guard.py": "GL121",
        "lock_order_cycle.py": "GL122",
        "guarded_collection_escape.py": "GL123",
        "unvalidated_committed_json.py": "GL124",
        "callback_under_lock.py": "GL125",
        "check_then_act.py": "GL126",
        "blocking_call_under_lock.py": "GL127",
    }
    for name, code in expected_files.items():
        path = os.path.join(corpus, name)
        assert os.path.exists(path), f"missing corpus file {name}"
        expected = Counter(corpus_expectations(path))
        assert expected[code] >= 1, (name, expected)
        findings, suppressed = lint_file(path, in_corpus=True)
        got = Counter(f.code for f in findings)
        assert got == expected, (
            f"{name}: expected {dict(expected)}, got {dict(got)}:\n"
            + "\n".join(f.render() for f in findings))
        # every file carries one honored-suppression demo
        assert suppressed >= 1, f"{name}: suppression demo not consumed"


def test_interprocedural_blocking_call_is_caught():
    """THE v2 capability: a blocking call only reachable through a
    helper — lexically nowhere near an `async def`, so per-function
    matching must miss it — flags via the call-graph color, and the
    finding explains the path. Control: the same helper with an
    additional SYNC caller must NOT flag (not 'reachable only from
    async')."""
    staging = os.path.join(REPO_ROOT, "paddle_tpu", "_graftlint_gate_tmp")
    os.makedirs(staging, exist_ok=True)
    hazard = (
        "import time\n"
        "async def stream_events(w):\n"
        "    for c in _prepare():\n"
        "        w.write(c)\n"
        "def _prepare():\n"
        "    time.sleep(0.2)\n"
        "    return [b'x']\n")
    try:
        dst = os.path.join(staging, "interproc_case.py")
        with open(dst, "w") as f:
            f.write(hazard)
        proc = _run_lint("--no-baseline", dst)
        assert proc.returncode != 0, (
            "helper-only-reachable blocking call NOT caught:\n"
            + proc.stdout)
        assert "GL114" in proc.stdout, proc.stdout
        assert "_prepare" in proc.stdout, proc.stdout
        assert "reachable only from async" in proc.stdout, proc.stdout
        # control: one sync caller breaks the only-from-async property
        with open(dst, "w") as f:
            f.write(hazard + "def sync_user():\n    return _prepare()\n")
        proc = _run_lint("--no-baseline", dst)
        assert proc.returncode == 0, (
            "helper with a sync caller should NOT flag (not reachable "
            "ONLY from async):\n" + proc.stdout)
    finally:
        shutil.rmtree(staging, ignore_errors=True)


def test_jsonl_output_is_parseable():
    """--jsonl emits one JSON object per finding with the documented
    fields — incl. suppressed findings, flagged — and keeps the exit
    code contract."""
    staging = os.path.join(REPO_ROOT, "paddle_tpu", "_graftlint_gate_tmp")
    os.makedirs(staging, exist_ok=True)
    try:
        src = os.path.join(REPO_ROOT, "tools", "graftlint", "corpus",
                           "stale_suppression.py")
        dst = os.path.join(staging, "stale_suppression.py")
        shutil.copyfile(src, dst)
        proc = _run_lint("--jsonl", "--no-baseline", dst)
        assert proc.returncode == 1, proc.stdout + proc.stderr
        rows = [json.loads(ln) for ln in proc.stdout.splitlines() if ln]
        assert rows, proc.stdout
        for r in rows:
            assert {"rule", "path", "line", "col", "message",
                    "suppressed", "baselined"} <= set(r), r
        codes = {r["rule"] for r in rows if not r["suppressed"]}
        assert "GL117" in codes, rows
        # the honored GL401 demo surfaces as a suppressed=true row
        assert any(r["rule"] == "GL401" and r["suppressed"]
                   for r in rows), rows
    finally:
        shutil.rmtree(staging, ignore_errors=True)


def test_lock_identity_model():
    """The v3 foundation, unit-pinned: two classes each binding
    `self._lock` yield two DISTINCT lock identities (pooled attr-name
    coloring cannot tell them apart), and a local alias
    (`l = self._lock; with l:`) resolves to the SAME identity as the
    attribute it aliases — the acquisition is attributed to A._lock,
    not dropped as unknown."""
    sys.path.insert(0, REPO_ROOT)
    try:
        from tools.graftlint.core import FileContext
        from tools.graftlint.project import ProjectIndex
    finally:
        sys.path.remove(REPO_ROOT)
    src = (
        "import threading\n"
        "class A:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.x = 0\n"
        "    def use(self):\n"
        "        l = self._lock\n"
        "        with l:\n"
        "            self.x = 1\n"
        "class B:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.RLock()\n")
    ctx = FileContext("paddle_tpu/_idmodel_case.py", src)
    idx = ProjectIndex([ctx])
    a_id = "paddle_tpu/_idmodel_case.py::A._lock"
    b_id = "paddle_tpu/_idmodel_case.py::B._lock"
    assert a_id in idx.locks and b_id in idx.locks, sorted(idx.locks)
    assert idx.locks[a_id].kind == "Lock"
    assert idx.locks[b_id].kind == "RLock"
    assert idx.locks[a_id].short == "A._lock"
    # the alias-taken acquisition resolves to A's lock, specifically
    ls = idx.locksets()
    acqs = [a for a in ls.acquisitions if a.fn.name == "use"]
    assert [a.ident for a in acqs] == [a_id], acqs
    # and the write under the alias carries the identity in its lockset
    writes = [a for a in ls.accesses
              if a.attr == "x" and a.fn.name == "use"]
    assert writes and all(a_id in ls.effective(w) for w in writes), writes


def test_sarif_output_is_parseable():
    """--sarif emits a valid-enough SARIF 2.1.0 document: version,
    driver name, one result per finding with ruleId/level/message/
    physical location — and keeps --jsonl's exit-code contract.
    Suppressed findings ride along greyed (suppressions property), not
    dropped."""
    staging = os.path.join(REPO_ROOT, "paddle_tpu", "_graftlint_gate_tmp")
    os.makedirs(staging, exist_ok=True)
    try:
        src = os.path.join(REPO_ROOT, "tools", "graftlint", "corpus",
                           "stale_suppression.py")
        dst = os.path.join(staging, "stale_suppression.py")
        shutil.copyfile(src, dst)
        proc = _run_lint("--sarif", "--no-baseline", dst)
        assert proc.returncode == 1, proc.stdout + proc.stderr
        doc = json.loads(proc.stdout)
        assert doc["version"] == "2.1.0", doc
        assert "sarif-2.1.0" in doc["$schema"], doc["$schema"]
        run0 = doc["runs"][0]
        driver = run0["tool"]["driver"]
        assert driver["name"] == "graftlint"
        results = run0["results"]
        assert results, proc.stdout
        for r in results:
            assert r["level"] in ("error", "note"), r
            assert r["message"]["text"], r
            loc = r["locations"][0]["physicalLocation"]
            assert loc["artifactLocation"]["uri"].endswith(".py"), r
            assert loc["region"]["startLine"] >= 1, r
        new_codes = {r["ruleId"] for r in results if r["level"] == "error"}
        assert "GL117" in new_codes, sorted(new_codes)
        # the honored GL401 demo is present but marked suppressed
        assert any(r["ruleId"] == "GL401" and r.get("suppressions")
                   for r in results), results
        # every reported code is described in the driver's rule table
        rule_ids = {r["id"] for r in driver["rules"]}
        assert new_codes <= rule_ids, (new_codes, rule_ids)
    finally:
        shutil.rmtree(staging, ignore_errors=True)


def test_changed_scope_does_not_stale_crossfile_suppressions():
    """The GL117 --changed fix, pinned end-to-end: a GL122 lock-order
    cycle spans two files, anchored in order_a with the reasoned
    suppression comment at the OTHER chain in order_b. A full run
    consumes that suppression cross-file (clean). A diff-scoped run
    over order_b alone never collects the cycle (its anchor file is
    out of scope), so GL117 must NOT cry stale over the comment —
    before the fix it did, flip-flopping between full and --changed
    runs."""
    sys.path.insert(0, REPO_ROOT)
    try:
        from tools.graftlint.core import run
    finally:
        sys.path.remove(REPO_ROOT)
    staging = os.path.join(REPO_ROOT, "paddle_tpu", "_graftlint_gate_tmp")
    os.makedirs(staging, exist_ok=True)
    mod = "paddle_tpu._graftlint_gate_tmp.order_a"
    try:
        a = os.path.join(staging, "order_a.py")
        b = os.path.join(staging, "order_b.py")
        with open(a, "w") as f:
            f.write(
                "import threading\n"
                "g_sched = threading.Lock()\n"
                "g_stats = threading.Lock()\n"
                "def fwd():\n"
                "    with g_sched:\n"
                "        with g_stats:\n"
                "            pass\n")
        with open(b, "w") as f:
            f.write(
                f"from {mod} import g_sched, g_stats\n"
                "def rev():\n"
                "    with g_stats:\n"
                "        with g_sched:  "
                "# graftlint: disable=GL122 - gate fixture: rev() runs "
                "only before the sched threads start\n"
                "            pass\n")
        full = run([staging], use_baseline=False)
        assert not full.new, [f.render() for f in full.new]
        assert any(f.code == "GL122" for f in full.suppressed_findings), (
            "cross-file GL122 cycle was not found/suppressed at all:"
            + str([f.render() for f in full.suppressed_findings]))
        scoped = run([staging], use_baseline=False, rule_paths=[b])
        stale = [f for f in scoped.new if f.code == "GL117"]
        assert not stale, [f.render() for f in stale]
    finally:
        shutil.rmtree(staging, ignore_errors=True)


def test_changed_mode_runs():
    """--changed (the pre-commit fast path) must work in any git
    state: exit 0 on a clean diff of a clean tree, and never crash."""
    proc = _run_lint("--changed")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "graftlint: OK" in proc.stdout, proc.stdout


def test_introduced_corpus_snippet_fails():
    """Dropping any known-bad snippet into the package tree turns the run
    red; the clean corpus file stays green (false-positive tripwire)."""
    corpus = os.path.join(REPO_ROOT, "tools", "graftlint", "corpus")
    staging = os.path.join(REPO_ROOT, "paddle_tpu", "_graftlint_gate_tmp")
    os.makedirs(staging, exist_ok=True)
    try:
        for name in sorted(os.listdir(corpus)):
            if not name.endswith(".py"):
                continue
            dst = os.path.join(staging, name)
            shutil.copyfile(os.path.join(corpus, name), dst)
            proc = _run_lint(dst)
            if name == "clean_ok.py":
                assert proc.returncode == 0, (
                    f"{name} should lint clean outside the corpus:\n"
                    + proc.stdout)
            else:
                assert proc.returncode != 0, (
                    f"introducing corpus snippet {name} into paddle_tpu/ "
                    "did NOT fail the lint run:\n" + proc.stdout)
            os.remove(dst)
    finally:
        shutil.rmtree(staging, ignore_errors=True)
