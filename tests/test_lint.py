"""ocvf-lint framework tests: per-rule fixture snippets (positive, negative,
suppressed), suppression hygiene, CLI exit-code contract, and the tier-1
gate that the real tree is clean.

The fixture tests assert exact (rule, line) pairs — the acceptance bar is
that a deliberately seeded violation of every rule is detected at the
correct file:line, not merely that "something" fires."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import threading

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

from tools.ocvf_lint import core  # noqa: E402


def lint_tree(tmp_path, files, rules=None):
    """Write {relpath: source} under tmp_path and lint the tree."""
    for rel, src in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(src))
    return core.run([str(tmp_path)], rules=rules).findings


def lint_source(tmp_path, source, rules=None):
    return lint_tree(tmp_path, {"mod.py": source}, rules=rules)


def rules_and_lines(findings):
    return [(f.rule, f.line) for f in findings]


# ---------------- blocking-under-lock ----------------


def test_blocking_under_lock_positive(tmp_path):
    findings = lint_source(tmp_path, """\
        import time

        class S:
            def bad(self):
                with self._lock:
                    time.sleep(0.1)
        """, rules=["blocking-under-lock"])
    assert rules_and_lines(findings) == [("blocking-under-lock", 6)]


def test_blocking_under_lock_negatives(tmp_path):
    findings = lint_source(tmp_path, """\
        import time

        class S:
            def sleep_outside(self):
                with self._lock:
                    x = 1
                time.sleep(0.1)

            def nested_def_resets(self):
                with self._lock:
                    def later():
                        time.sleep(0.1)  # runs outside the lock
                    self.hook = later

            def str_join_is_not_io(self):
                with self._lock:
                    return ", ".join(["a"])
        """, rules=["blocking-under-lock"])
    assert findings == []


def test_blocking_under_lock_io_and_suppression(tmp_path):
    findings = lint_source(tmp_path, """\
        import os

        class S:
            def fsyncs(self, fh):
                with self._lock:
                    os.fsync(fh.fileno())

            def justified(self, fh):
                with self._lock:  # ocvf-lint: disable-block=blocking-under-lock -- this lock exists to serialize these writes
                    fh.write(b"x")
                    fh.flush()
        """, rules=["blocking-under-lock"])
    assert rules_and_lines(findings) == [("blocking-under-lock", 6)]


# ---------------- lock-order ----------------


def test_lock_order_inversion_detected(tmp_path):
    findings = lint_source(tmp_path, """\
        class S:
            def ab(self):
                with self._a_lock:
                    with self._b_lock:
                        pass

            def ba(self):
                with self._b_lock:
                    with self._a_lock:
                        pass
        """, rules=["lock-order"])
    assert len(findings) == 1
    assert findings[0].rule == "lock-order"
    assert findings[0].line == 4  # the first edge site
    assert "inversion" in findings[0].message


def test_lock_order_consistent_is_clean(tmp_path):
    findings = lint_source(tmp_path, """\
        class S:
            def one(self):
                with self._a_lock:
                    with self._b_lock:
                        pass

            def two(self):
                with self._a_lock:
                    with self._b_lock:
                        pass
        """, rules=["lock-order"])
    assert findings == []


def test_lock_order_re_entry_detected(tmp_path):
    findings = lint_source(tmp_path, """\
        class S:
            def re_enter(self):
                with self._lock:
                    with self._lock:
                        pass
        """, rules=["lock-order"])
    assert rules_and_lines(findings) == [("lock-order", 4)]
    assert "re-acquired" in findings[0].message


def test_lock_order_call_propagation(tmp_path):
    """An inversion only visible through a method call: ab() nests
    lexically, ba() holds b and CALLS a helper that takes a."""
    findings = lint_source(tmp_path, """\
        class S:
            def ab(self):
                with self._a_lock:
                    with self._b_lock:
                        pass

            def take_a(self):
                with self._a_lock:
                    pass

            def ba(self):
                with self._b_lock:
                    self.take_a()
        """, rules=["lock-order"])
    assert len(findings) == 1
    assert "inversion" in findings[0].message


def test_lock_order_suppression_at_any_edge(tmp_path):
    findings = lint_source(tmp_path, """\
        class S:
            def ab(self):
                with self._a_lock:
                    with self._b_lock:  # ocvf-lint: disable=lock-order -- ordered handoff proven safe by construction here
                        pass

            def ba(self):
                with self._b_lock:
                    with self._a_lock:
                        pass
        """, rules=["lock-order"])
    assert findings == []


# ---------------- non-atomic-write ----------------


def test_non_atomic_write_positive(tmp_path):
    findings = lint_source(tmp_path, """\
        import json

        def save(path, obj):
            with open(path, "w") as fh:
                json.dump(obj, fh)
        """, rules=["non-atomic-write"])
    assert rules_and_lines(findings) == [("non-atomic-write", 4)]


def test_non_atomic_write_negatives(tmp_path):
    findings = lint_source(tmp_path, """\
        def fine(path):
            with open(path) as fh:
                data = fh.read()
            with open(path, "rb") as fh:
                blob = fh.read()
            with open(path, "a") as fh:  # append = journal-style, exempt
                fh.write("x")
            return data, blob
        """, rules=["non-atomic-write"])
    assert findings == []


def test_non_atomic_write_exempt_layers_and_suppression(tmp_path):
    findings = lint_tree(tmp_path, {
        "utils/serialization.py": """\
            def atomic_write_bytes(path, blob):
                with open(path + ".tmp", "wb") as fh:  # the helper itself
                    fh.write(blob)
            """,
        "app.py": """\
            def dump(path, text):
                # ocvf-lint: disable=non-atomic-write -- throwaway debug artifact, torn file is harmless
                with open(path, "w") as fh:
                    fh.write(text)
            """,
        "pathlib_user.py": """\
            def bad(p):
                p.write_text("hello")
            """,
    }, rules=["non-atomic-write"])
    assert [(f.rule, os.path.basename(f.path), f.line) for f in findings] == [
        ("non-atomic-write", "pathlib_user.py", 2)]


# ---------------- metrics-registry ----------------

METRIC_FIXTURE_REGISTRY = """\
    GOOD = "good_metric"
    OTHER = "other_metric"
    FAMILY_PREFIX = "fam_"
    """


def test_metrics_registry_literals(tmp_path):
    findings = lint_tree(tmp_path, {
        "utils/metric_names.py": METRIC_FIXTURE_REGISTRY,
        "app.py": """\
            def f(metrics, reason):
                metrics.incr("good_metric")
                metrics.incr("bad_typo_metric")
                metrics.observe("other_metric", 1.0)
                metrics.incr(f"fam_{reason}")
                metrics.incr(f"unregistered_{reason}")
            """,
    }, rules=["metrics-registry"])
    assert rules_and_lines(findings) == [("metrics-registry", 3),
                                         ("metrics-registry", 6)]


def test_metrics_registry_constants_and_prefix_concat(tmp_path):
    findings = lint_tree(tmp_path, {
        "utils/metric_names.py": METRIC_FIXTURE_REGISTRY,
        "app.py": """\
            import utils.metric_names as mn
            from utils.metric_names import GOOD

            def f(metrics, reason, name):
                metrics.incr(mn.GOOD)
                metrics.incr(GOOD)
                metrics.incr(mn.FAMILY_PREFIX + reason)
                metrics.incr(mn.DOES_NOT_EXIST)
                metrics.incr(name)
            """,
    }, rules=["metrics-registry"])
    assert rules_and_lines(findings) == [("metrics-registry", 8),
                                         ("metrics-registry", 9)]


def test_metrics_registry_checks_every_pair_of_incr_many(tmp_path):
    """``Metrics.incr_many((name, value), ...)``: each pair's name is held
    to the registry like ``incr``'s; an argument that is no literal pair
    hides its names, and is flagged once."""
    findings = lint_tree(tmp_path, {
        "utils/metric_names.py": METRIC_FIXTURE_REGISTRY,
        "app.py": """\
            import utils.metric_names as mn

            def f(metrics, reason, pairs):
                metrics.incr_many((mn.GOOD, 1.0), ("other_metric", 2.0),
                                  (mn.FAMILY_PREFIX + reason, 3.0))
                metrics.incr_many((mn.GOOD, 1.0), ("bad_typo_metric", 2.0))
                metrics.incr_many((mn.GOOD, 1.0), (mn.DOES_NOT_EXIST, 2.0))
                metrics.incr_many(*pairs)
            """,
    }, rules=["metrics-registry"])
    assert rules_and_lines(findings) == [("metrics-registry", 6),
                                         ("metrics-registry", 7),
                                         ("metrics-registry", 8)]


def test_metrics_registry_prefix_strictness(tmp_path):
    """Prefix/name pools stay disjoint: a bare prefix is not a counter
    name, a full name is not a prefix, and concatenation requires a
    *_PREFIX constant (or its literal value) on the left."""
    findings = lint_tree(tmp_path, {
        "utils/metric_names.py": METRIC_FIXTURE_REGISTRY,
        "app.py": """\
            import utils.metric_names as mn

            def f(metrics, reason):
                metrics.incr("fam_" + reason)          # literal prefix: ok
                metrics.incr(mn.FAMILY_PREFIX + reason)
                metrics.incr(mn.GOOD + reason)          # full name + x: drift
                metrics.incr("fam_")                    # bare prefix as name
                metrics.counters_with_prefix("good_metric")  # name as prefix
            """,
    }, rules=["metrics-registry"])
    assert rules_and_lines(findings) == [("metrics-registry", 6),
                                         ("metrics-registry", 7),
                                         ("metrics-registry", 8)]


def test_metrics_registry_checks_count_shim_sites(tmp_path):
    findings = lint_tree(tmp_path, {
        "utils/metric_names.py": METRIC_FIXTURE_REGISTRY,
        "app.py": """\
            def f(conn):
                conn._count("good_metric")
                conn._count("conector_reconects")  # the typo class
            """,
    }, rules=["metrics-registry"])
    assert rules_and_lines(findings) == [("metrics-registry", 3)]


def test_metrics_registry_read_sites_and_np_percentile(tmp_path):
    findings = lint_tree(tmp_path, {
        "utils/metric_names.py": METRIC_FIXTURE_REGISTRY,
        "app.py": """\
            import numpy as np

            def f(metrics, ts):
                metrics.counter("good_metric")
                metrics.counter("typo_metric")
                metrics.counters_with_prefix("fam_")
                return np.percentile(ts, 50)  # not a Metrics read
            """,
    }, rules=["metrics-registry"])
    assert rules_and_lines(findings) == [("metrics-registry", 5)]


# ---------------- swallowed-exception ----------------


def test_swallowed_exception_positive(tmp_path):
    findings = lint_source(tmp_path, """\
        def f():
            try:
                work()
            except Exception:
                pass
            try:
                work()
            except:
                return None
        """, rules=["swallowed-exception"])
    assert rules_and_lines(findings) == [("swallowed-exception", 4),
                                         ("swallowed-exception", 8)]


def test_swallowed_exception_accounted_forms_pass(tmp_path):
    findings = lint_source(tmp_path, """\
        def f(metrics, log, q):
            try:
                work()
            except Exception:
                metrics.incr("errors")
            try:
                work()
            except Exception:
                raise RuntimeError("wrapped")
            try:
                work()
            except Exception as e:
                q["error"] = repr(e)  # exception is read -> recorded
            try:
                work()
            except ValueError:
                pass  # narrow except is out of scope for this rule
        """, rules=["swallowed-exception"])
    assert findings == []


def test_swallowed_exception_suppression(tmp_path):
    findings = lint_source(tmp_path, """\
        def f():
            try:
                work()
            except Exception:  # ocvf-lint: disable=swallowed-exception -- teardown is best-effort by contract
                pass
        """, rules=["swallowed-exception"])
    assert findings == []


# ---------------- suppression hygiene ----------------


def test_bare_suppression_is_inert_and_flagged(tmp_path):
    findings = lint_source(tmp_path, """\
        import time

        class S:
            def bad(self):
                with self._lock:
                    time.sleep(0.1)  # ocvf-lint: disable=blocking-under-lock
        """, rules=["blocking-under-lock"])
    got = rules_and_lines(findings)
    assert ("suppression", 6) in got          # the bare disable is a finding
    assert ("blocking-under-lock", 6) in got  # and it suppressed NOTHING


def test_short_justification_counts_as_bare(tmp_path):
    findings = lint_source(tmp_path, """\
        import time

        class S:
            def bad(self):
                with self._lock:
                    time.sleep(0.1)  # ocvf-lint: disable=blocking-under-lock -- ok
        """, rules=["blocking-under-lock"])
    assert ("suppression", 6) in rules_and_lines(findings)


def test_unknown_rule_in_suppression_flagged(tmp_path):
    findings = lint_source(tmp_path, """\
        x = 1  # ocvf-lint: disable=no-such-rule -- justification text here
        """)
    assert [(f.rule, f.line) for f in findings] == [("suppression", 1)]
    assert "unknown rule" in findings[0].message


def test_disable_file_covers_everything(tmp_path):
    findings = lint_source(tmp_path, """\
        # ocvf-lint: disable-file=non-atomic-write -- scratch artifact writer, torn output is harmless
        def a(p):
            open(p, "w").write("x")

        def b(p):
            open(p, "w").write("y")
        """, rules=["non-atomic-write"])
    assert findings == []


def test_disable_block_covers_whole_statement(tmp_path):
    findings = lint_source(tmp_path, """\
        import os

        class S:
            def f(self, fh):
                with self._lock:  # ocvf-lint: disable-block=blocking-under-lock -- serializing these writes is the purpose of this lock
                    fh.write(b"a")
                    fh.flush()
                    os.fsync(fh.fileno())
                with self._lock:
                    fh.write(b"b")
        """, rules=["blocking-under-lock"])
    assert rules_and_lines(findings) == [("blocking-under-lock", 10)]


def test_suppression_meta_rule_cannot_be_suppressed(tmp_path):
    findings = lint_source(tmp_path, """\
        x = 1  # ocvf-lint: disable=unknown-thing -- long enough justification ; ocvf-lint: disable=suppression -- nice try
        """)
    assert any(f.rule == "suppression" for f in findings)


def test_parse_error_is_a_finding_not_a_crash(tmp_path):
    findings = lint_source(tmp_path, "def broken(:\n")
    assert [f.rule for f in findings] == ["parse-error"]


# ---------------- CLI contract ----------------


def _cli(*args, cwd=REPO_ROOT):
    return subprocess.run(
        [sys.executable, "-m", "tools.ocvf_lint", *args],
        cwd=cwd, capture_output=True, text=True, timeout=120,
        env={**os.environ, "PYTHONPATH": REPO_ROOT
             + os.pathsep + os.environ.get("PYTHONPATH", "")})


def test_cli_exit_0_on_clean(tmp_path):
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    proc = _cli(str(clean))
    assert proc.returncode == 0, proc.stderr


def test_cli_exit_1_on_findings_and_json(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text('def f(p):\n    open(p, "w").write("x")\n')
    proc = _cli("--json", str(bad))
    assert proc.returncode == 1
    doc = json.loads(proc.stdout)
    assert doc["findings"][0]["rule"] == "non-atomic-write"
    assert doc["findings"][0]["line"] == 2


def test_cli_exit_2_on_internal_error(tmp_path):
    proc = _cli(str(tmp_path / "does-not-exist"))
    assert proc.returncode == 2


ALL_RULES = ("lock-order", "blocking-under-lock", "non-atomic-write",
             "metrics-registry", "swallowed-exception",
             "jit-recompile-hazard", "host-sync", "prng-discipline",
             "epoch-pairing", "wal-before-mutate",
             "settle-once", "resource-pairing", "fence-ordering",
             "ledger-registry-coherence")


def test_cli_list_rules_names_all_fourteen(tmp_path):
    proc = _cli("--list-rules")
    assert proc.returncode == 0
    for rule in ALL_RULES:
        assert rule in proc.stdout


# ---------------- the tier-1 gate: the real tree is clean ----------------


def test_real_tree_has_zero_findings():
    """The acceptance bar: ``python -m tools.ocvf_lint
    opencv_facerecognizer_tpu scripts`` exits 0 at head, with all
    FOURTEEN rules active (v2 added jit-recompile-hazard / host-sync /
    prng-discipline / epoch-pairing / wal-before-mutate; v3 added
    settle-once / resource-pairing / fence-ordering /
    ledger-registry-coherence) and every suppression/boundary
    justified."""
    proc = _cli("opencv_facerecognizer_tpu", "scripts", "--json",
                "--no-cache")
    assert proc.returncode == 0, f"lint found issues:\n{proc.stdout}\n{proc.stderr}"
    doc = json.loads(proc.stdout)
    assert doc["findings"] == []
    assert set(doc["rules"]) >= set(ALL_RULES)
    assert doc["files_scanned"] > 40
    # the v2 hot-path rules are live, not vacuous: the designed boundary
    # sites (sacrificial blocker, prewarm, the one per-batch materialize,
    # offline gallery builders) are annotated and honored
    assert doc["boundaries_used"] >= 20


def test_baseline_ratchet_enforced_at_head():
    """LINT_BASELINE.json is the checked-in ratchet: the gate run passes
    against it, it covers every v2 rule, and at head every frozen count is
    already zero (counts may only shrink — never edit them upward; new
    findings must be fixed or suppressed with justification)."""
    baseline_path = os.path.join(REPO_ROOT, "LINT_BASELINE.json")
    with open(baseline_path) as fh:
        doc = json.load(fh)
    assert set(doc["rules"]) >= set(ALL_RULES)
    assert all(v == 0 for v in doc["rules"].values()), doc["rules"]
    proc = _cli("opencv_facerecognizer_tpu", "scripts", "--no-cache",
                "--baseline", baseline_path)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_wiring_names_live_code():
    """ROADMAP Design 13, "every deletion also deletes its hints": each
    class ``wiring.ATTR_HINTS`` dispatches to is defined in the package,
    and each module a ``*_SUFFIXES`` scope names exists. A stale entry
    makes a checker silently skip what it was pointed at."""
    import ast

    from tools.ocvf_lint import wiring

    package = os.path.join(REPO_ROOT, "opencv_facerecognizer_tpu")
    defined = set()
    for folder, _dirs, files in os.walk(package):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(folder, name)) as fh:
                    defined |= {node.name for node in ast.walk(ast.parse(fh.read()))
                                if isinstance(node, ast.ClassDef)}
    stale = {attr: cls for attr, cls in wiring.ATTR_HINTS.items()
             if cls not in defined}
    assert not stale, stale
    scopes = [name for name in dir(wiring) if name.endswith("_SUFFIXES")]
    assert "HOT_PATH_SUFFIXES" in scopes
    missing = [(scope, suffix) for scope in scopes
               for suffix in getattr(wiring, scope)
               if not os.path.isfile(os.path.join(package, suffix))]
    assert not missing, missing


def test_real_lock_graph_is_nonempty_and_acyclic():
    """The static inter-module lock graph over the real runtime must keep
    seeing the known edges (StateLifecycle -> WAL/journal/gallery/metrics)
    — if this goes empty the lock-order rule has silently gone blind."""
    from tools.ocvf_lint.checkers.lock_order import build_lock_graph

    edges = set(build_lock_graph(
        [os.path.join(REPO_ROOT, "opencv_facerecognizer_tpu")]))
    assert any(a.endswith("StateLifecycle._enroll_lock") for a, _ in edges)
    assert any(b.endswith("Metrics._lock") for _, b in edges)
    inverted = [(a, b) for (a, b) in edges if a != b and (b, a) in edges]
    assert not inverted


# ---------------- metric_names registry sanity ----------------


def test_metric_names_registry_no_duplicates():
    from opencv_facerecognizer_tpu.utils import metric_names as mn

    names = mn.all_names()
    assert len(names) == len(set(names)), "duplicate metric name values"
    assert len(names) > 50
    prefixes = mn.all_prefixes()
    assert all(p.endswith("_") for p in prefixes)
    # no full name may collide into a prefix family ambiguously with itself
    assert len(prefixes) == len(set(prefixes))


# ---------------- DebugLock dynamic backstop unit tests ----------------


def test_debug_lock_records_edges_and_detects_inversion():
    from opencv_facerecognizer_tpu.utils.debug_lock import (
        DebugLock, LockOrderError, LockOrderMonitor)

    monitor = LockOrderMonitor()
    a = monitor.debug_lock("A")
    b = monitor.debug_lock("B")
    with a:
        with b:
            pass
    assert monitor.edges() == {("A", "B")}
    monitor.check()  # consistent so far
    with b:
        with a:
            pass
    assert monitor.inversions() == [("A", "B")]
    with pytest.raises(LockOrderError):
        monitor.check()


def test_debug_lock_re_entry_raises_immediately():
    from opencv_facerecognizer_tpu.utils.debug_lock import (
        LockOrderError, LockOrderMonitor)

    monitor = LockOrderMonitor()
    a = monitor.debug_lock("A")
    with a:
        with pytest.raises(LockOrderError):
            a.acquire()


def test_debug_lock_backs_a_condition_variable():
    from opencv_facerecognizer_tpu.utils.debug_lock import LockOrderMonitor

    monitor = LockOrderMonitor()
    inner = monitor.debug_lock("CV")
    cv = threading.Condition(inner)
    hits = []

    def waiter():
        with cv:
            while not hits:
                cv.wait(timeout=5.0)

    t = threading.Thread(target=waiter)
    t.start()
    with cv:
        hits.append(1)
        cv.notify()
    t.join(timeout=5.0)
    assert not t.is_alive()
    monitor.check()


# ===================== v2: JAX-aware dataflow rules =====================

# ---------------- jit-recompile-hazard ----------------


def test_jit_hazard_branch_and_interprocedural_materialize(tmp_path):
    findings = lint_source(tmp_path, """\
        import jax
        import functools

        @jax.jit
        def bad(x):
            if x > 0:
                return x
            return -x

        def helper(y):
            return float(y)

        @jax.jit
        def bad2(x):
            return helper(x)

        @functools.partial(jax.jit, static_argnames=("flag",))
        def ok_static(x, flag):
            if flag:
                return x
            return -x

        @jax.jit
        def ok_shape(x):
            if x.shape[0] > 8:
                return x
            return x.reshape((-1,))
        """, rules=["jit-recompile-hazard"])
    assert rules_and_lines(findings) == [("jit-recompile-hazard", 6),
                                         ("jit-recompile-hazard", 11)]
    assert "branch" in findings[0].message
    assert "float()" in findings[1].message  # found INSIDE the callee


def test_jit_hazard_call_form_and_nested_step(tmp_path):
    """The pipeline idiom: a nested ``step`` wrapped by jax.jit(step)."""
    findings = lint_source(tmp_path, """\
        import jax
        import numpy as np

        def build():
            def step(params, frames):
                frames = frames.astype("float32")
                n = np.asarray(frames)
                return frames

            return jax.jit(step)
        """, rules=["jit-recompile-hazard"])
    assert rules_and_lines(findings) == [("jit-recompile-hazard", 7)]
    assert "np.asarray" in findings[0].message


def test_jit_hazard_hot_path_construction_needs_boundary(tmp_path):
    findings = lint_tree(tmp_path, {
        "parallel/pipeline.py": """\
            import jax

            def build(step):
                return jax.jit(step)

            def build_ok(step):
                return jax.jit(step)  # ocvf-lint: boundary=jit-recompile-hazard -- cache-keyed builder, warmed for every ladder bucket before serving
            """,
        "models/other.py": """\
            import jax

            def build(step):
                return jax.jit(step)  # not a hot-path module: fine
            """,
    }, rules=["jit-recompile-hazard"])
    assert [(f.rule, os.path.basename(f.path), f.line) for f in findings] == [
        ("jit-recompile-hazard", "pipeline.py", 4)]


# ---------------- host-sync ----------------

HOT_SYNC_FIXTURE = """\
    import numpy as np

    class S:
        def serve(self, frames):
            frames = np.asarray(frames)
            packed = self.pipeline.recognize_batch_packed(frames)
            self._inflight.append((packed, 1))

        def drain(self):
            packed, n = self._inflight[0]
            arr = np.asarray(packed)
            return arr

        def probe(self, packed):
            return packed.item()
    """


def test_host_sync_taint_through_inflight_deque(tmp_path):
    findings = lint_tree(tmp_path, {"runtime/recognizer.py": HOT_SYNC_FIXTURE},
                         rules=["host-sync"])
    # np.asarray(frames) at line 5 is a HOST value — no finding; the
    # dispatched batch popped back out of self._inflight IS device-tainted,
    # and .item() is unconditionally a sync in hot-path modules.
    assert rules_and_lines(findings) == [("host-sync", 11), ("host-sync", 15)]


def test_host_sync_scope_and_boundary_annotation(tmp_path):
    findings = lint_tree(tmp_path, {
        "runtime/other.py": HOT_SYNC_FIXTURE,  # not a hot-path module
        "runtime/batcher.py": """\
            import numpy as np

            class B:
                def put(self, frame):
                    frame = np.asarray(frame)  # host frame: clean
                    return frame

                def wait(self, out):
                    out.block_until_ready()  # ocvf-lint: boundary=host-sync -- fixture: designed sync point for this test
            """,
    }, rules=["host-sync"])
    assert findings == []


def test_host_sync_block_until_ready_flagged(tmp_path):
    findings = lint_tree(tmp_path, {
        "parallel/pipeline.py": """\
            def prewarm(out):
                out.block_until_ready()
            """,
    }, rules=["host-sync"])
    assert rules_and_lines(findings) == [("host-sync", 2)]


# ---------------- prng-discipline ----------------


def test_prng_reuse_loop_and_nondet_seed(tmp_path):
    findings = lint_source(tmp_path, """\
        import jax
        import time

        def bad(seed):
            key = jax.random.PRNGKey(seed)
            a = jax.random.normal(key, (3,))
            b = jax.random.uniform(key, (3,))
            return a, b

        def loop_bad(seed):
            key = jax.random.PRNGKey(seed)
            out = []
            for i in range(3):
                out.append(jax.random.normal(key, (3,)))
            return out

        def nondet():
            return jax.random.PRNGKey(int(time.time()))
        """, rules=["prng-discipline"])
    assert rules_and_lines(findings) == [("prng-discipline", 7),
                                         ("prng-discipline", 14),
                                         ("prng-discipline", 18)]
    assert "reused" in findings[0].message or "consumed again" in findings[0].message
    assert "loop" in findings[1].message
    assert "time.time" in findings[2].message


def test_prng_split_fold_in_and_loop_resplit_are_clean(tmp_path):
    findings = lint_source(tmp_path, """\
        import jax
        import numpy as np

        def ok(seed):
            key = jax.random.PRNGKey(seed)
            k1, k2 = jax.random.split(key)
            return jax.random.normal(k1, (3,)), jax.random.uniform(k2, (3,))

        def ok_fold(seed):
            rng = jax.random.PRNGKey(seed)
            a = jax.random.normal(jax.random.fold_in(rng, 1), (3,))
            b = jax.random.normal(jax.random.fold_in(rng, 2), (3,))
            return a, b

        def ok_loop(seed):
            key = jax.random.PRNGKey(seed)
            out = []
            for i in range(3):
                key, sub = jax.random.split(key)
                out.append(jax.random.normal(sub, (3,)))
            return out

        def np_random_is_not_jax(rng):
            return np.random.normal(0.0, 1.0, (3,))
        """, rules=["prng-discipline"])
    assert findings == []


def test_prng_nondet_seed_exempt_in_tests(tmp_path):
    findings = lint_tree(tmp_path, {
        "tests/test_something.py": """\
            import jax
            import time

            def make_key():
                return jax.random.PRNGKey(int(time.time()))
            """,
    }, rules=["prng-discipline"])
    assert findings == []


# ---------------- epoch-pairing ----------------


def test_epoch_pairing_guarded_fields_and_raw_quantizer(tmp_path):
    findings = lint_tree(tmp_path, {
        "mod.py": """\
            def bad(gallery):
                return gallery._epoch

            def bad2(self):
                return self.gallery.quantizer.data

            def bad3(gallery):
                emb = gallery.embeddings
                lab = gallery.labels
                return emb, lab

            def ok(gallery):
                data = gallery.data
                return data.embeddings, data.labels

            class Unrelated:
                def own_private_data_is_fine(self):
                    return self._data
            """,
        "parallel/gallery.py": """\
            class ShardedGallery:
                def bump(self):
                    self._epoch += 1
            """,
        "parallel/quantizer.py": """\
            class CoarseQuantizer:
                def publish(self, data):
                    self._data = data
            """,
    }, rules=["epoch-pairing"])
    assert [(f.rule, os.path.basename(f.path), f.line) for f in findings] == [
        ("epoch-pairing", "mod.py", 2),
        ("epoch-pairing", "mod.py", 5),
        ("epoch-pairing", "mod.py", 9)]
    assert "_ivf_data" in findings[1].message
    assert "snapshot" in findings[2].message


def test_epoch_pairing_suppression(tmp_path):
    findings = lint_source(tmp_path, """\
        def debug_dump(gallery):
            return gallery._epoch  # ocvf-lint: disable=epoch-pairing -- offline debug dump, no serving thread can race this tool
        """, rules=["epoch-pairing"])
    assert findings == []


# ---------------- wal-before-mutate ----------------


def test_wal_before_mutate_positive_and_apply_fn_route(tmp_path):
    findings = lint_tree(tmp_path, {
        "mod.py": """\
            class S:
                def bad(self, emb, labels):
                    self.gallery.add(emb, labels)

                def good(self, emb, labels):
                    self.state.append_enrollment(
                        emb, labels,
                        apply_fn=lambda: self.gallery.add(emb, labels))

                def bad_wal(self, rec):
                    self.wal.append(rec)

                def reads_are_fine(self):
                    return self.wal.replay()
            """,
        "runtime/state_store.py": """\
            class StateLifecycle:
                def replay(self, gallery, rec):
                    gallery.add(rec["emb"], rec["labels"])
            """,
    }, rules=["wal-before-mutate"])
    assert [(f.rule, os.path.basename(f.path), f.line) for f in findings] == [
        ("wal-before-mutate", "mod.py", 3),
        ("wal-before-mutate", "mod.py", 11)]


def test_wal_before_mutate_boundary_for_nondurable_gallery(tmp_path):
    findings = lint_source(tmp_path, """\
        def bench(gallery, rows, labs):
            gallery.add(rows, labs)  # ocvf-lint: boundary=wal-before-mutate -- synthetic bench gallery, no state dir, nothing durable at stake
        """, rules=["wal-before-mutate"])
    assert findings == []


# ---------------- boundary annotation hygiene ----------------


def test_boundary_requires_justification_and_capability(tmp_path):
    findings = lint_source(tmp_path, """\
        def f(gallery, rows, labs):
            gallery.add(rows, labs)  # ocvf-lint: boundary=wal-before-mutate
        """, rules=["wal-before-mutate"])
    got = rules_and_lines(findings)
    assert ("suppression", 2) in got           # bare boundary is a finding
    assert ("wal-before-mutate", 2) in got     # and it sanctioned NOTHING

    findings = lint_source(tmp_path, """\
        def f():
            try:
                work()
            except Exception:  # ocvf-lint: boundary=swallowed-exception -- boundaries are not defined for this rule
                pass
        """, rules=["swallowed-exception"])
    got = rules_and_lines(findings)
    assert ("suppression", 4) in got           # rule defines no boundaries
    assert ("swallowed-exception", 4) in got   # so nothing was sanctioned


def test_boundary_counts_reported_separately(tmp_path):
    path = tmp_path / "parallel" / "pipeline.py"
    path.parent.mkdir(parents=True)
    path.write_text(textwrap.dedent("""\
        def prewarm(out):
            out.block_until_ready()  # ocvf-lint: boundary=host-sync -- prewarm thread blocks by design in this fixture
        """))
    result = core.run([str(tmp_path)], rules=["host-sync"])
    assert result.findings == []
    assert result.boundaries_used == 1
    assert result.suppressions_used == 0


# ---------------- settle-once (v3) ----------------

#: minimal ledger registry shared by the settle-once fixtures: the rule
#: resolves terminal statuses through these tables, not hard-coded names.
_MN_FIXTURE = """\
    FRAMES_COMPLETED = "frames_completed"
    FRAMES_FAILED = "frames_failed"
    BATCHER_DROPPED_PREFIX = "batcher_dropped_"
    FRAMES_ADMITTED = "frames_admitted"
    LEDGER_COMPLETION_COUNTERS = (FRAMES_COMPLETED,)
    LEDGER_DROP_COUNTERS = (FRAMES_FAILED,)
    PROM_FOLDED_PREFIXES = ()
    """


def test_settle_once_unsettled_incr_on_exit_path(tmp_path):
    findings = lint_tree(tmp_path, {
        "utils/metric_names.py": _MN_FIXTURE,
        "runtime/service.py": """\
            from utils import metric_names as mn

            class RecognizerService:
                def fail_path(self, tids, count):
                    self.metrics.incr(mn.FRAMES_FAILED, count)
                    return count
            """,
    }, rules=["settle-once"])
    assert rules_and_lines(findings) == [("settle-once", 5)]
    assert "without a matching settle sink" in findings[0].message


def test_settle_once_double_settlement_on_crash_path(tmp_path):
    findings = lint_tree(tmp_path, {
        "utils/metric_names.py": _MN_FIXTURE,
        "runtime/service.py": """\
            from utils import metric_names as mn

            class RecognizerService:
                def crash(self, tid):
                    self.metrics.incr(mn.FRAMES_FAILED)
                    self._trace_settle([tid], mn.FRAMES_FAILED, "a")
                    self._trace_settle([tid], mn.FRAMES_FAILED, "b")
                    raise RuntimeError("boom")
            """,
    }, rules=["settle-once"])
    # the raising path skips balance (crash handlers settle elsewhere)
    # but a double settlement of the same basis+status still fires.
    assert rules_and_lines(findings) == [("settle-once", 7)]
    assert "settles the same frame run twice" in findings[0].message


def test_settle_once_balanced_paths_and_prefix_family_clean(tmp_path):
    findings = lint_tree(tmp_path, {
        "utils/metric_names.py": _MN_FIXTURE,
        "runtime/service.py": """\
            from utils import metric_names as mn

            class FrameBatcher:
                def drop(self, entry, reason):
                    self.metrics.incr(mn.BATCHER_DROPPED_PREFIX + reason)
                    self._emit_settle(entry[3],
                                      mn.BATCHER_DROPPED_PREFIX + reason,
                                      "batcher")
                    return False

            class RecognizerService:
                def publish(self, tids, published, rejected):
                    self.metrics.incr(mn.FRAMES_ADMITTED)
                    try:
                        self.emit(tids)
                    finally:
                        self.metrics.incr(mn.FRAMES_COMPLETED, published)
                        self._trace_settle(tids, mn.FRAMES_COMPLETED, "ok")
                    if published < len(rejected):
                        self.metrics.incr(mn.FRAMES_FAILED)
                        self._trace_settle(tids, mn.FRAMES_FAILED, "fail")
            """,
    }, rules=["settle-once"])
    # FRAMES_ADMITTED is not terminal; both terminal incrs pair exactly.
    assert findings == []


def test_settle_once_literal_status_is_flagged(tmp_path):
    findings = lint_tree(tmp_path, {
        "utils/metric_names.py": _MN_FIXTURE,
        "runtime/service.py": """\
            from utils import metric_names as mn

            class RecognizerService:
                def fail_path(self, tid):
                    self.metrics.incr(mn.FRAMES_FAILED)
                    self._trace_settle([tid], "frames_failed", "x")
            """,
    }, rules=["settle-once"])
    # balance holds (the literal still pairs) — only hygiene fires.
    assert rules_and_lines(findings) == [("settle-once", 6)]
    assert "string literal" in findings[0].message


def test_settle_once_suppression(tmp_path):
    findings = lint_tree(tmp_path, {
        "utils/metric_names.py": _MN_FIXTURE,
        "runtime/service.py": """\
            from utils import metric_names as mn

            class RecognizerService:
                def fail_path(self, tids, count):
                    self.metrics.incr(mn.FRAMES_FAILED, count)  # ocvf-lint: disable=settle-once -- settled by the caller's crash handler in this fixture
                    return count
            """,
    }, rules=["settle-once"])
    assert findings == []


# ---------------- resource-pairing (v3) ----------------


def test_resource_pairing_custody_leak_and_boundary(tmp_path):
    source = """\
        class FrameBatcher:
            def pop(self, count):
                buf = self._ring.acquire(count)
                data = self.fill(count)
                if data is None:
                    return None
                self.out.append((data, buf))
                return data
        """
    findings = lint_tree(tmp_path, {"runtime/batcher.py": source},
                         rules=["resource-pairing"])
    # anchored at the acquire, with the leaking exit as an also-site.
    assert rules_and_lines(findings) == [("resource-pairing", 3)]
    assert findings[0].also == ((str(tmp_path / "runtime" / "batcher.py"), 6),)
    # a boundary annotation on the leaking EXIT line sanctions the path.
    suppressed = source.replace(
        "return None",
        "return None  # ocvf-lint: boundary=resource-pairing -- fixture: caller inherits the buffer through self.pending on this path")
    findings = lint_tree(tmp_path / "b", {"runtime/batcher.py": suppressed},
                         rules=["resource-pairing"])
    assert findings == []


def test_resource_pairing_release_forfeit_and_handoff_clean(tmp_path):
    findings = lint_tree(tmp_path, {
        "runtime/batcher.py": """\
            class FrameBatcher:
                def pop(self, count):
                    buf = self._ring.acquire(count)
                    try:
                        data = self.fill(count)
                    except Exception:
                        self._ring.forfeit(buf)
                        raise
                    self._ring.recycle(buf)
                    return data

                def pop_handoff(self, count):
                    buf = self._ring.acquire(count)
                    return self.pack(buf)
            """,
    }, rules=["resource-pairing"])
    assert findings == []


def test_resource_pairing_forfeit_missing_on_crash_path(tmp_path):
    findings = lint_tree(tmp_path, {
        "runtime/batcher.py": """\
            class FrameBatcher:
                def pop(self, count):
                    buf = self._ring.acquire(count)
                    try:
                        data = self.fill(count)
                    except Exception:
                        self.log("fill failed")
                        raise
                    self._ring.recycle(buf)
                    return data
            """,
    }, rules=["resource-pairing"])
    # the normal path releases; the crash path leaks the buffer.
    assert rules_and_lines(findings) == [("resource-pairing", 3)]
    assert "crash paths" in findings[0].message


def test_resource_pairing_discarded_acquire(tmp_path):
    findings = lint_tree(tmp_path, {
        "runtime/batcher.py": """\
            class FrameBatcher:
                def warm(self, count):
                    self._ring.acquire(count)
            """,
    }, rules=["resource-pairing"])
    assert rules_and_lines(findings) == [("resource-pairing", 3)]
    assert "discarded" in findings[0].message


def test_resource_pairing_seq_burn_and_watermark(tmp_path):
    findings = lint_tree(tmp_path, {
        "runtime/state_store.py": """\
            class StateLifecycle:
                def enroll(self, rows):
                    seq = self._wal_seq = self._wal_seq + 1
                    if not rows:
                        raise ValueError("empty enrollment")
                    self.wal.append_enroll(seq, rows)
                    return seq

                def adopt(self, highest):
                    self._wal_seq = max(self._wal_seq, int(highest))
                    return self._wal_seq
            """,
    }, rules=["resource-pairing"])
    # the early raise leaks the burned seq; watermark seeding is NOT a
    # burn (max(), not the increment idiom) and stays silent.
    assert rules_and_lines(findings) == [("resource-pairing", 3)]
    assert "append_*" in findings[0].message


def test_resource_pairing_seq_burn_abort_path_clean(tmp_path):
    findings = lint_tree(tmp_path, {
        "runtime/state_store.py": """\
            class StateLifecycle:
                def enroll(self, rows):
                    seq = self._wal_seq = self._wal_seq + 1
                    try:
                        self.wal.append_enroll(seq, rows)
                    except BaseException:
                        self.wal.append_abort(seq)
                        raise
                    return seq
            """,
    }, rules=["resource-pairing"])
    assert findings == []


@pytest.mark.parametrize("method", ["lifecycle", "span"])
def test_resource_pairing_tracer_contexts_need_with(tmp_path, method):
    findings = lint_tree(tmp_path, {
        "mod.py": f"""\
            class Worker:
                def bad(self):
                    span = self._tracer.{method}("swap")
                    return span

                def good(self):
                    with self._tracer.{method}("swap"):
                        return 1

                def good_when_sampled(self, tid):
                    with (self._tracer.{method}("swap") if tid else NULL):
                        return 1
            """,
    }, rules=["resource-pairing"])
    assert rules_and_lines(findings) == [("resource-pairing", 3)]
    assert f"Tracer.{method} is a contextmanager" in findings[0].message


# ---------------- fence-ordering (v3) ----------------


def test_fence_ordering_install_before_fence(tmp_path):
    findings = lint_tree(tmp_path, {
        "runtime/state_store.py": """\
            class StateLifecycle:
                def perform_cutover(self, to_version, emb):
                    seq = self.alloc()
                    self.gallery.load_snapshot(emb, to_version)
                    self.wal.append_cutover(seq, to_version)
                    return seq
            """,
    }, rules=["fence-ordering"])
    assert rules_and_lines(findings) == [("fence-ordering", 4)]
    assert "before the WAL fence append" in findings[0].message


def test_fence_ordering_fence_first_with_faults_clean(tmp_path):
    findings = lint_tree(tmp_path, {
        "runtime/state_store.py": """\
            class StateLifecycle:
                def perform_cutover(self, to_version, emb, fault):
                    seq = self.alloc()
                    if fault == "before":
                        raise RuntimeError("crash before record")
                    self.wal.append_cutover(seq, to_version)
                    if fault == "after":
                        raise RuntimeError("crash after record")
                    self.gallery.load_snapshot(emb, to_version)
                    return seq

                def perform_registry_cutover(self, role, install_fn):
                    seq = self.alloc()
                    self.wal.append_registry_cutover(seq, role)
                    self.registry.install(role)
                    install_fn()
                    return seq
            """,
    }, rules=["fence-ordering"])
    assert findings == []


def test_fence_ordering_installer_callback_before_fence(tmp_path):
    findings = lint_tree(tmp_path, {
        "runtime/state_store.py": """\
            class StateLifecycle:
                def perform_registry_cutover(self, role, install_fn):
                    seq = self.alloc()
                    install_fn()
                    self.wal.append_registry_cutover(seq, role)
                    return seq
            """,
    }, rules=["fence-ordering"])
    assert rules_and_lines(findings) == [("fence-ordering", 4)]


def test_fence_ordering_durable_writer_needs_atomic_helper(tmp_path):
    findings = lint_tree(tmp_path, {
        "runtime/registry.py": """\
            class ModelRegistry:
                def _save_locked(self):
                    with open(self.path, "w") as fh:
                        fh.write(self.blob)
            """,
    }, rules=["fence-ordering"])
    got = rules_and_lines(findings)
    # the bare write-mode open AND the missing atomic_write_* both fire.
    assert ("fence-ordering", 3) in got
    assert ("fence-ordering", 2) in got
    clean = lint_tree(tmp_path / "b", {
        "runtime/registry.py": """\
            class ModelRegistry:
                def _save_locked(self):
                    atomic_write_json(self.path, self.blob)
            """,
    }, rules=["fence-ordering"])
    assert clean == []


# ---------------- ledger-registry-coherence (v3) ----------------

_COHERENT_TREE = {
    "utils/metric_names.py": """\
        FRAMES_COMPLETED = "frames_completed"
        FRAMES_COMPLETED_EMPTY = "frames_completed_empty"
        FRAMES_FAILED = "frames_failed"
        REJ_PREFIX = "frames_rejected_"
        LEDGER_COMPLETION_COUNTERS = (FRAMES_COMPLETED,
                                      FRAMES_COMPLETED_EMPTY)
        LEDGER_DROP_COUNTERS = (FRAMES_FAILED,)
        PROM_FOLDED_PREFIXES = (REJ_PREFIX,)
        """,
    "utils/tracing.py": """\
        OUTCOME_COMPLETED = "completed"
        OUTCOME_COMPLETED_EMPTY = "completed_empty"

        def account_spans(spans):
            return {OUTCOME_COMPLETED: 0, OUTCOME_COMPLETED_EMPTY: 0}
        """,
    "runtime/recognizer.py": """\
        from utils import metric_names as mn

        class RecognizerService:
            LEDGER_DROP_COUNTERS = mn.LEDGER_DROP_COUNTERS

            def ledger(self):
                return (mn.FRAMES_COMPLETED, mn.FRAMES_COMPLETED_EMPTY,
                        self.LEDGER_DROP_COUNTERS)

            def frames_in_system(self):
                return (mn.FRAMES_COMPLETED, mn.FRAMES_COMPLETED_EMPTY,
                        self.LEDGER_DROP_COUNTERS)
        """,
    "runtime/promtext.py": """\
        from utils import metric_names as mn

        _LABEL_FAMILIES = ((mn.REJ_PREFIX, "frames_rejected", "reason"),)
        """,
    "scripts/chaos_soak.py": """\
        def _check_span_accounting(acct):
            assert acct["completed"] >= 0
            assert acct["completed_empty"] >= 0
        """,
}


def test_coherence_full_tree_is_clean(tmp_path):
    findings = lint_tree(tmp_path, dict(_COHERENT_TREE),
                         rules=["ledger-registry-coherence"])
    assert findings == []


def test_coherence_missing_tracing_mirror_and_reducer_ref(tmp_path):
    tree = dict(_COHERENT_TREE)
    tree["utils/tracing.py"] = """\
        OUTCOME_COMPLETED = "completed"

        def account_spans(spans):
            return {OUTCOME_COMPLETED: 0}
        """
    findings = lint_tree(tmp_path, tree,
                         rules=["ledger-registry-coherence"])
    assert [f.rule for f in findings] == ["ledger-registry-coherence"]
    assert "no OUTCOME_* mirror" in findings[0].message
    assert "completed_empty" in findings[0].message


def test_coherence_recognizer_drop_tuple_drift(tmp_path):
    tree = dict(_COHERENT_TREE)
    tree["runtime/recognizer.py"] = """\
        from utils import metric_names as mn

        class RecognizerService:
            LEDGER_DROP_COUNTERS = (mn.FRAMES_FAILED, mn.FRAMES_BOGUS)

            def ledger(self):
                return (mn.FRAMES_COMPLETED, mn.FRAMES_COMPLETED_EMPTY,
                        self.LEDGER_DROP_COUNTERS)

            def frames_in_system(self):
                return (mn.FRAMES_COMPLETED, mn.FRAMES_COMPLETED_EMPTY,
                        self.LEDGER_DROP_COUNTERS)
        """
    findings = lint_tree(tmp_path, tree,
                         rules=["ledger-registry-coherence"])
    assert rules_and_lines(findings) == [("ledger-registry-coherence", 4)]
    assert "drifted from the registry table" in findings[0].message


def test_coherence_missing_completion_in_ledger_surface(tmp_path):
    tree = dict(_COHERENT_TREE)
    tree["runtime/recognizer.py"] = """\
        from utils import metric_names as mn

        class RecognizerService:
            LEDGER_DROP_COUNTERS = mn.LEDGER_DROP_COUNTERS

            def ledger(self):
                return (mn.FRAMES_COMPLETED, self.LEDGER_DROP_COUNTERS)

            def frames_in_system(self):
                return (mn.FRAMES_COMPLETED, mn.FRAMES_COMPLETED_EMPTY,
                        self.LEDGER_DROP_COUNTERS)
        """
    findings = lint_tree(tmp_path, tree,
                         rules=["ledger-registry-coherence"])
    assert rules_and_lines(findings) == [("ledger-registry-coherence", 6)]
    assert "FRAMES_COMPLETED_EMPTY" in findings[0].message


def test_coherence_promtext_family_drift(tmp_path):
    tree = dict(_COHERENT_TREE)
    tree["runtime/promtext.py"] = """\
        from utils import metric_names as mn

        _LABEL_FAMILIES = ()
        """
    findings = lint_tree(tmp_path, tree,
                         rules=["ledger-registry-coherence"])
    assert rules_and_lines(findings) == [("ledger-registry-coherence", 3)]
    assert "REJ_PREFIX" in findings[0].message


def test_coherence_chaos_soak_missing_outcome(tmp_path):
    tree = dict(_COHERENT_TREE)
    tree["scripts/chaos_soak.py"] = """\
        def _check_span_accounting(acct):
            assert acct["completed"] >= 0
        """
    findings = lint_tree(tmp_path, tree,
                         rules=["ledger-registry-coherence"])
    assert rules_and_lines(findings) == [("ledger-registry-coherence", 1)]
    assert "completed_empty" in findings[0].message


def test_coherence_sites_absent_from_subset_lint_are_skipped(tmp_path):
    tree = {k: v for k, v in _COHERENT_TREE.items()
            if k in ("utils/metric_names.py", "scripts/chaos_soak.py")}
    findings = lint_tree(tmp_path, tree,
                         rules=["ledger-registry-coherence"])
    assert findings == []


# ---------------- v3 scratch-copy deletion gates ----------------
# The acceptance demonstration: delete ONE settlement call / custody
# overwrite / fence append from a copy of the REAL tree and the matching
# rule must fire at the mutated site.


def _real_source(rel):
    with open(os.path.join(REPO_ROOT, rel), "r", encoding="utf-8") as fh:
        return fh.read()


def test_scratch_delete_settlement_call_fires_settle_once(tmp_path):
    src = _real_source("opencv_facerecognizer_tpu/runtime/recognizer.py")
    needle = ('self._trace_settle(trace_ids[:count], mn.FRAMES_FAILED,\n'
              '                                   "dispatch.abandoned", '
              'batch=batch_tid)\n                ')
    assert needle in src, "recognizer settle site moved; update the fixture"
    mutated = src.replace(needle, "", 1)
    path = tmp_path / "runtime" / "recognizer.py"
    path.parent.mkdir(parents=True)
    path.write_text(mutated)
    findings = core.run([str(tmp_path)], rules=["settle-once"]).findings
    incr_line = mutated.splitlines().index(
        "                self.metrics.incr(mn.FRAMES_FAILED, count)") + 1
    assert ("settle-once", incr_line) in rules_and_lines(findings)


def test_scratch_break_custody_overwrite_fires_resource_pairing(tmp_path):
    src = _real_source("opencv_facerecognizer_tpu/runtime/batcher.py")
    assert "buf = _EXHAUSTED" in src, "batcher custody site moved"
    # the exhausted-branch overwrite is what ENDS custody of the acquired
    # buffer on the retry path; renaming it leaks custody to `return None`
    mutated = src.replace("buf = _EXHAUSTED", "buf_retry = _EXHAUSTED", 1)
    path = tmp_path / "runtime" / "batcher.py"
    path.parent.mkdir(parents=True)
    path.write_text(mutated)
    findings = core.run([str(tmp_path)],
                        rules=["resource-pairing"]).findings
    acquire_line = next(i for i, line in enumerate(mutated.splitlines(), 1)
                        if "self._ring.acquire(" in line)
    assert ("resource-pairing", acquire_line) in rules_and_lines(findings)


def test_scratch_delete_fence_append_fires_fence_ordering(tmp_path):
    src = _real_source("opencv_facerecognizer_tpu/runtime/state_store.py")
    needle = ("""self.wal.append_cutover(seq, from_version, int(to_version),
                                    rows=int(size), dim=int(emb.shape[1]))""")
    assert needle in src, "cutover fence site moved; update the fixture"
    mutated = src.replace(needle, "_ = seq", 1)
    path = tmp_path / "runtime" / "state_store.py"
    path.parent.mkdir(parents=True)
    path.write_text(mutated)
    findings = core.run([str(tmp_path)],
                        rules=["fence-ordering"]).findings
    lines = mutated.splitlines()
    mark = next(i for i, line in enumerate(lines, 1)
                if line.strip() == "_ = seq")
    install_line = next(i for i, line in enumerate(lines, 1)
                        if i > mark and "load_snapshot(" in line)
    assert ("fence-ordering", install_line) in rules_and_lines(findings)


# ---------------- incremental cache ----------------


def _cache_tree(tmp_path):
    tree = tmp_path / "tree"
    files = {
        "a.py": 'def f(p):\n    open(p, "w").write("x")\n',
        "b.py": "def g():\n    try:\n        work()\n    except Exception:\n"
                "        pass\n",
        "c.py": "x = 1\n",
    }
    for rel, src in files.items():
        p = tree / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
    return tree


def test_cache_returns_identical_findings_to_cold_run(tmp_path):
    from tools.ocvf_lint.cache import LintCache

    tree = _cache_tree(tmp_path)
    cold = core.run([str(tree)])
    cache = LintCache(str(tmp_path / "cache"))
    warm1 = core.run([str(tree)], cache=cache)     # populates
    cache2 = LintCache(str(tmp_path / "cache"))    # reload from disk
    warm2 = core.run([str(tree)], cache=cache2)    # full run-layer hit
    as_dicts = lambda r: [f.to_dict() for f in r.findings]  # noqa: E731
    assert as_dicts(cold) == as_dicts(warm1) == as_dicts(warm2)
    assert cold.rule_counts() == warm2.rule_counts()
    assert warm2.cache.get("run_hit") is True
    assert warm2.suppressions_used == cold.suppressions_used


def test_cache_file_layer_replays_unchanged_files(tmp_path):
    from tools.ocvf_lint.cache import LintCache

    tree = _cache_tree(tmp_path)
    cache = LintCache(str(tmp_path / "cache"))
    core.run([str(tree)], cache=cache)
    # edit ONE file: its findings refresh, the others replay by hash
    (tree / "c.py").write_text('def h(p):\n    open(p, "w").write("y")\n')
    cache2 = LintCache(str(tmp_path / "cache"))
    warm = core.run([str(tree)], cache=cache2)
    assert warm.cache["run_hit"] is False
    assert warm.cache["file_hits"] == 2
    assert warm.cache["file_misses"] == 1
    cold = core.run([str(tree)])
    assert [f.to_dict() for f in warm.findings] == \
        [f.to_dict() for f in cold.findings]
    assert any(f.path.endswith("c.py") for f in warm.findings)


def test_cache_invalidated_by_tool_fingerprint(tmp_path):
    from tools.ocvf_lint import cache as cache_mod

    tree = _cache_tree(tmp_path)
    cache = cache_mod.LintCache(str(tmp_path / "cache"))
    core.run([str(tree)], cache=cache)
    # simulate a linter edit: a different fingerprint must see an EMPTY cache
    stale = cache_mod.LintCache(str(tmp_path / "cache"))
    stale.fingerprint = "not-the-real-fingerprint"
    stale.data = {"tool": stale.fingerprint, "files": {}, "runs": {}}
    warm = core.run([str(tree)], cache=stale)
    assert warm.cache["run_hit"] is False
    assert warm.cache["file_misses"] == 3


def test_cached_rerun_meets_runtime_budget():
    """The tier-1 gate must stay fast as rules multiply: an unchanged-tree
    re-run rides the run-layer cache.  Budget is wall-clock generous (this
    box has one CPU core and the subprocess pays interpreter startup) but
    far below a cold run with ten rules over 60+ files."""
    import shutil
    import time

    cache_dir = os.path.join(REPO_ROOT, ".ocvf_lint_cache_test")
    shutil.rmtree(cache_dir, ignore_errors=True)
    try:
        warm = _cli("opencv_facerecognizer_tpu", "scripts", "--json",
                    "--cache-dir", cache_dir)
        assert warm.returncode == 0, warm.stdout + warm.stderr
        t0 = time.perf_counter()
        hit = _cli("opencv_facerecognizer_tpu", "scripts", "--json",
                   "--cache-dir", cache_dir)
        elapsed = time.perf_counter() - t0
        assert hit.returncode == 0
        doc = json.loads(hit.stdout)
        assert doc["cache"]["run_hit"] is True
        assert doc["findings"] == []
        assert elapsed < 15.0, f"cached lint re-run took {elapsed:.1f}s"
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)


# ---------------- baseline / ratchet ----------------


def test_baseline_regression_fails_and_update_refuses_growth(tmp_path):
    from tools.ocvf_lint import baseline as baseline_mod

    bad = tmp_path / "bad.py"
    bad.write_text('def f(p):\n    open(p, "w").write("x")\n')
    base = tmp_path / "base.json"

    # frozen at the current count: rc 0 even though findings exist
    proc = _cli(str(bad), "--baseline", str(base), "--update-baseline",
                "--baseline-allow-growth")
    assert proc.returncode == 0, proc.stderr
    allowed = baseline_mod.load(str(base))
    assert allowed["non-atomic-write"] == 1
    proc = _cli(str(bad), "--baseline", str(base))
    assert proc.returncode == 0, proc.stdout + proc.stderr

    # a SECOND finding regresses past the frozen count: rc 1
    bad.write_text('def f(p):\n    open(p, "w").write("x")\n'
                   'def g(p):\n    open(p, "w").write("y")\n')
    proc = _cli(str(bad), "--baseline", str(base), "--no-cache")
    assert proc.returncode == 1
    assert "REGRESSION" in proc.stderr

    # and --update-baseline refuses to freeze the regression in
    proc = _cli(str(bad), "--baseline", str(base), "--update-baseline",
                "--no-cache")
    assert proc.returncode == 1
    assert "refusing to grow" in proc.stderr
    assert baseline_mod.load(str(base))["non-atomic-write"] == 1

    # fixing back down passes, and the ratchet can tighten
    bad.write_text("x = 1\n")
    proc = _cli(str(bad), "--baseline", str(base), "--no-cache")
    assert proc.returncode == 0
    proc = _cli(str(bad), "--baseline", str(base), "--update-baseline",
                "--no-cache")
    assert proc.returncode == 0
    assert baseline_mod.load(str(base))["non-atomic-write"] == 0


# ---------------- SARIF output ----------------


def test_sarif_output_structure(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text('def f(p):\n    open(p, "w").write("x")\n')
    proc = _cli("--sarif", str(bad), "--no-cache")
    assert proc.returncode == 1
    doc = json.loads(proc.stdout)
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "ocvf-lint"
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert "non-atomic-write" in rule_ids
    result = run["results"][0]
    assert result["ruleId"] == "non-atomic-write"
    loc = result["locations"][0]["physicalLocation"]
    assert loc["region"]["startLine"] == 2


def test_cache_is_path_sensitive_for_location_dependent_rules(tmp_path):
    """Identical bytes mean different things at different paths (tests/
    exemption, owner-module suffixes) — the file layer must key on BOTH,
    or moving a file across an exemption boundary replays a stale clean
    verdict."""
    from tools.ocvf_lint.cache import LintCache

    src = ("import jax\nimport time\n\n"
           "def make_key():\n"
           "    return jax.random.PRNGKey(int(time.time()))\n")
    tree = tmp_path / "tree"
    exempt = tree / "tests" / "test_x.py"
    exempt.parent.mkdir(parents=True)
    exempt.write_text(src)
    cache = LintCache(str(tmp_path / "cache"))
    clean = core.run([str(tree)], rules=["prng-discipline"], cache=cache)
    assert clean.findings == []  # tests/ is exempt from the seed rule
    # same BYTES promoted out of tests/: must be a finding on a warm cache
    promoted = tree / "keys.py"
    promoted.write_text(src)
    exempt.unlink()
    cache2 = LintCache(str(tmp_path / "cache"))
    warm = core.run([str(tree)], rules=["prng-discipline"], cache=cache2)
    assert [(f.rule, f.line) for f in warm.findings] == \
        [("prng-discipline", 5)]


def test_update_baseline_with_rules_subset_preserves_other_counts(tmp_path):
    from tools.ocvf_lint import baseline as baseline_mod

    base = tmp_path / "base.json"
    err = baseline_mod.update(str(base), {"lock-order": 2, "host-sync": 1},
                              ["lock-order", "host-sync"])
    assert err is None
    # a subset run measuring only host-sync must not wipe lock-order's
    # frozen reserve
    err = baseline_mod.update(str(base), {"host-sync": 0}, ["host-sync"])
    assert err is None
    allowed = baseline_mod.load(str(base))
    assert allowed == {"lock-order": 2, "host-sync": 0}


def test_run_cache_key_covers_fallback_metric_registry(tmp_path):
    """metrics-registry reads utils/metric_names.py from disk when it is
    not among the linted files — that out-of-tree input must be folded
    into the run-cache key, or editing the registry replays a stale clean
    verdict for subset lints (run_lint.sh --changed)."""
    from tools.ocvf_lint.cache import LintCache
    from tools.ocvf_lint.checkers.metrics_registry import MetricsRegistryChecker

    checker = MetricsRegistryChecker()
    fp = checker.extra_cache_fingerprint(["scripts/chaos_soak.py"])
    assert fp.startswith("metrics-registry:")
    assert len(fp) > len("metrics-registry:")
    # registry in the linted set: its hash is already a key input
    assert checker.extra_cache_fingerprint(
        ["opencv_facerecognizer_tpu/utils/metric_names.py"]) == ""
    cache = LintCache(str(tmp_path / "cache"))
    k1 = cache.run_key(["metrics-registry"], [("a.py", "h")], extra=fp)
    k2 = cache.run_key(["metrics-registry"], [("a.py", "h")],
                       extra="metrics-registry:different")
    assert k1 != k2


def test_jit_hazard_partial_decorator_reported_once(tmp_path):
    findings = lint_tree(tmp_path, {
        "parallel/pipeline.py": """\
            import functools

            import jax

            @functools.partial(jax.jit, static_argnames=("flag",))
            def step(x, flag):
                return x
            """,
    }, rules=["jit-recompile-hazard"])
    assert len(findings) == 1, rules_and_lines(findings)
    assert "@jit-decorated" in findings[0].message


def test_update_baseline_refuses_corrupt_existing(tmp_path):
    from tools.ocvf_lint import baseline as baseline_mod

    base = tmp_path / "base.json"
    base.write_text("{this is not json")
    err = baseline_mod.update(str(base), {"host-sync": 3}, ["host-sync"])
    assert err is not None and "unreadable" in err
    assert base.read_text() == "{this is not json"  # nothing rewritten
    # the explicit override path may rebuild from scratch
    err = baseline_mod.update(str(base), {"host-sync": 3}, ["host-sync"],
                              allow_growth=True)
    assert err is None
    assert baseline_mod.load(str(base)) == {"host-sync": 3}
