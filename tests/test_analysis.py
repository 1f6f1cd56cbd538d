"""Static concurrency & convention analyzer (PR 10): every rule family
proven on in-test source fixtures, waiver/baseline mechanics, the
shipped-tree + baseline self-check, regression tests for the real
concurrency bugs the analyzer surfaced (WireClient sendall under the
state lock, ResultCache.oversize_puts lost updates, HealthRegistry
breaker-dict races), and the knob-coverage constructions that pin
every engine knob's non-default path."""
import json
import pathlib
import socket
import sys
import textwrap
import threading
import time

import numpy as np

from repro.analysis import run_analysis
from repro.analysis.runner import (check_baseline, load_baseline,
                                   write_baseline)
from repro.core.engine import VDMSAsyncEngine
from repro.core.remote import TransportModel
from repro.core.result_cache import ResultCache
from repro.cluster.engine import ShardedEngine
from repro.query.health import HealthRegistry
from repro.serving.frontend import WireClient

REPO = pathlib.Path(__file__).resolve().parent.parent
FAST = TransportModel(network_latency_s=0.001, service_time_s=0.002)


def _analyze(tmp_path, source, *, name="mod_under_test.py",
             ref_dirs=(), knob_classes=()):
    p = tmp_path / name
    p.write_text(textwrap.dedent(source))
    return run_analysis([str(p)], ref_dirs=[str(d) for d in ref_dirs],
                        knob_classes=knob_classes)


def _rules(result):
    return sorted({f.rule for f in result.findings})


# ===================================================== lock-order rules
def test_lock_order_cycle_detected(tmp_path):
    res = _analyze(tmp_path, """\
        import threading

        class A:
            def __init__(self):
                self._lock = threading.Lock()
                self._other = threading.Lock()

            def fwd(self):
                with self._lock:
                    with self._other:
                        pass

            def rev(self):
                with self._other:
                    with self._lock:
                        pass
        """)
    cycles = [f for f in res.findings if f.rule == "lock-order"]
    assert cycles, _rules(res)
    assert "A._lock" in cycles[0].subject and "A._other" in cycles[0].subject


def test_consistent_order_is_clean(tmp_path):
    res = _analyze(tmp_path, """\
        import threading

        class A:
            def __init__(self):
                self._lock = threading.Lock()
                self._other = threading.Lock()

            def one(self):
                with self._lock:
                    with self._other:
                        pass

            def two(self):
                with self._lock:
                    with self._other:
                        pass
        """)
    assert not [f for f in res.findings if f.rule == "lock-order"]
    # the nesting still shows up as a graph edge (the DOT artifact)
    assert any(e.src == "A._lock" and e.dst == "A._other"
               for e in res.graph.edges.values())


def test_interprocedural_cycle_through_call(tmp_path):
    res = _analyze(tmp_path, """\
        import threading

        class A:
            def __init__(self):
                self._lock = threading.Lock()
                self._other = threading.Lock()

            def helper(self):
                with self._other:
                    pass

            def fwd(self):
                with self._lock:
                    self.helper()

            def rev(self):
                with self._other:
                    with self._lock:
                        pass
        """)
    assert [f for f in res.findings if f.rule == "lock-order"]


def test_reentrant_lock_acquisition(tmp_path):
    res = _analyze(tmp_path, """\
        import threading

        class B:
            def __init__(self):
                self._lock = threading.Lock()

            def boom(self):
                with self._lock:
                    with self._lock:
                        pass
        """)
    reent = [f for f in res.findings if f.rule == "lock-reentrant"]
    assert reent and reent[0].scope == "B.boom"


def test_rlock_reentry_is_exempt(tmp_path):
    res = _analyze(tmp_path, """\
        import threading

        class B:
            def __init__(self):
                self._lock = threading.RLock()

            def fine(self):
                with self._lock:
                    with self._lock:
                        pass
        """)
    assert not [f for f in res.findings if f.rule == "lock-reentrant"]


def test_reentry_through_self_call(tmp_path):
    res = _analyze(tmp_path, """\
        import threading

        class B:
            def __init__(self):
                self._lock = threading.Lock()

            def inner(self):
                with self._lock:
                    pass

            def outer(self):
                with self._lock:
                    self.inner()
        """)
    reent = [f for f in res.findings if f.rule == "lock-reentrant"]
    assert reent and "inner" in reent[0].subject


# ==================================================== guarded-by rules
GUARDED = """\
    import threading

    class C:
        def __init__(self):
            self._lock = threading.Lock()
            self._n = 0  # guarded-by: _lock

        def good(self):
            with self._lock:
                self._n += 1

        def bad(self):
            return self._n
    """


def test_guarded_by_escape(tmp_path):
    res = _analyze(tmp_path, GUARDED)
    hits = [f for f in res.findings if f.rule == "guarded-by"]
    assert len(hits) == 1
    assert hits[0].scope == "C.bad" and "C._n" in hits[0].subject


def test_locked_suffix_convention(tmp_path):
    res = _analyze(tmp_path, """\
        import threading

        class D:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0  # guarded-by: _lock

            def _bump_locked(self):
                self._n += 1

            def good(self):
                with self._lock:
                    self._bump_locked()

            def bad(self):
                self._bump_locked()
        """)
    hits = [f for f in res.findings if f.rule == "guarded-by"]
    # _bump_locked itself is exempt (callers hold the lock); the
    # unlocked call site is the violation
    assert len(hits) == 1
    assert hits[0].scope == "D.bad" and "call-unlocked" in hits[0].subject


def test_blocking_call_under_lock(tmp_path):
    res = _analyze(tmp_path, """\
        import threading
        import time

        class E:
            def __init__(self):
                self._lock = threading.Lock()
                self._cv = threading.Condition()

            def bad_sleep(self):
                with self._lock:
                    time.sleep(0.1)

            def ok_wait(self):
                with self._cv:
                    self._cv.wait()

            def bad_wait(self):
                with self._lock:
                    with self._cv:
                        self._cv.wait()
        """)
    hits = {f.scope for f in res.findings
            if f.rule == "blocking-under-lock"}
    # cv.wait releases the (sole) held cv — the idiom is fine; waiting
    # while ALSO holding an unrelated lock carries that lock into the
    # sleep and is flagged, as is a plain sleep
    assert "E.bad_sleep" in hits and "E.bad_wait" in hits
    assert "E.ok_wait" not in hits


def test_transitive_blocking_through_self_call(tmp_path):
    res = _analyze(tmp_path, """\
        import threading
        import time

        class F:
            def __init__(self):
                self._lock = threading.Lock()

            def slow(self):
                time.sleep(0.5)

            def bad(self):
                with self._lock:
                    self.slow()
        """)
    hits = [f for f in res.findings if f.rule == "blocking-under-lock"]
    assert any(f.scope == "F.bad" for f in hits)


# ========================================================= knob-inert
def test_knob_inertness(tmp_path):
    ref = tmp_path / "refs"
    ref.mkdir()
    (ref / "test_knobs.py").write_text(
        "def test():\n    Eng(covered=3)\n")
    res = _analyze(tmp_path, """\
        class Eng:
            def __init__(self, *, covered: int = 0, enabling: bool = True,
                         orphan: int = 0):
                pass
        """, ref_dirs=[ref], knob_classes=("Eng",))
    subjects = {f.subject for f in res.findings if f.rule == "knob-inert"}
    assert "Eng.enabling:enabling-default" in subjects
    assert "Eng.orphan:unreferenced" in subjects
    assert not any(s.startswith("Eng.covered:") for s in subjects)


def test_knob_without_default(tmp_path):
    res = _analyze(tmp_path, """\
        class Eng:
            def __init__(self, *, mandatory):
                pass
        """, knob_classes=("Eng",))
    subjects = {f.subject for f in res.findings if f.rule == "knob-inert"}
    assert "Eng.mandatory:no-default" in subjects


# ==================================================== backend-protocol
def test_backend_missing_protocol_methods(tmp_path):
    res = _analyze(tmp_path, """\
        class BadBackend:
            name = "bad"

            def can_run(self, op):
                return True
        """)
    msgs = [f.message for f in res.findings if f.rule == "backend-protocol"]
    assert any("estimate" in m for m in msgs)
    assert any("queue_depth" in m for m in msgs)


def test_offload_mixin_shutdown_contract(tmp_path):
    res = _analyze(tmp_path, """\
        import threading

        class OffloadInboxMixin:
            def _init_inbox(self):
                pass

        class SlackBackend(OffloadInboxMixin):
            name = "slack"

            def __init__(self):
                pass

            def can_run(self, op):
                return True

            def estimate(self, op):
                return 0.0

            def queue_depth(self):
                return 0
        """)
    subjects = {f.subject for f in res.findings
                if f.rule == "backend-protocol"
                and f.scope == "SlackBackend"}
    assert "SlackBackend:offload:init-inbox" in subjects
    assert "SlackBackend:offload:run-groups" in subjects
    assert "SlackBackend:offload:pill-drain" in subjects


# ============================================================ waivers
def test_waiver_suppresses_and_is_load_bearing(tmp_path):
    res = _analyze(tmp_path, """\
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0  # guarded-by: _lock

            def snapshot(self):
                # analysis: ok(guarded-by) -- monotonic probe, staleness fine
                return self._n
        """)
    assert not [f for f in res.findings if f.rule == "guarded-by"]
    assert not [f for f in res.findings if f.rule == "useless-waiver"]
    assert len(res.suppressed) == 1
    f, w = res.suppressed[0]
    assert f.rule == "guarded-by" and "staleness fine" in w.reason


def test_unused_waiver_is_an_error(tmp_path):
    res = _analyze(tmp_path, """\
        # analysis: ok(guarded-by) -- nothing here needs this
        x = 1
        """)
    hits = [f for f in res.findings if f.rule == "useless-waiver"]
    assert len(hits) == 1 and hits[0].subject.startswith("guarded-by:")


def test_unknown_rule_waiver_is_an_error(tmp_path):
    res = _analyze(tmp_path, """\
        # analysis: ok(bogus-rule) -- typo
        x = 1
        """)
    hits = [f for f in res.findings if f.rule == "useless-waiver"]
    assert len(hits) == 1 and hits[0].subject.startswith("unknown-rule:")


def test_docstring_waiver_text_is_inert(tmp_path):
    res = _analyze(tmp_path, '''\
        """Docs quoting the grammar:

            # analysis: ok(guarded-by) -- example only
        """
        x = 1
        ''')
    assert not res.findings


# ================================================ fingerprints/baseline
def test_fingerprint_survives_line_drift(tmp_path):
    p = tmp_path / "drift.py"
    p.write_text(textwrap.dedent(GUARDED))
    before = run_analysis([str(p)]).findings
    p.write_text("# a comment\n# another\n\n" + textwrap.dedent(GUARDED))
    after = run_analysis([str(p)]).findings
    assert len(before) == len(after) == 1
    assert before[0].fingerprint == after[0].fingerprint
    assert before[0].line != after[0].line


def test_baseline_gates_only_new_findings(tmp_path):
    p = tmp_path / "base.py"
    p.write_text(textwrap.dedent(GUARDED))
    first = run_analysis([str(p)])
    bl_path = tmp_path / "baseline.json"
    write_baseline(str(bl_path), first)
    baseline = load_baseline(str(bl_path))

    new, stale = check_baseline(run_analysis([str(p)]), baseline)
    assert new == [] and stale == []

    # a second violation is NEW even though the first is baselined
    p.write_text(textwrap.dedent(GUARDED)
                 + "\n    def worse(self):\n        self._n = 9\n")
    new, stale = check_baseline(run_analysis([str(p)]), baseline)
    assert len(new) == 1 and stale == []

    # fixing the original finding leaves its entry stale
    p.write_text("x = 1\n")
    new, stale = check_baseline(run_analysis([str(p)]), baseline)
    assert new == [] and len(stale) == 1


def test_baseline_file_round_trips(tmp_path):
    p = tmp_path / "rt.py"
    p.write_text(textwrap.dedent(GUARDED))
    res = run_analysis([str(p)])
    bl_path = tmp_path / "baseline.json"
    write_baseline(str(bl_path), res)
    data = json.loads(bl_path.read_text())
    assert data["version"] == 1
    assert data["findings"][0]["rule"] == "guarded-by"


# =============================================== shipped-tree self-check
def test_shipped_tree_is_clean_against_baseline(monkeypatch):
    """The tree we ship plus its checked-in baseline must pass the same
    gate CI runs — and the baseline must be EMPTY: every true positive
    was fixed or waived, not baselined."""
    monkeypatch.chdir(REPO)
    res = run_analysis(["src"], ref_dirs=["tests", "bench"])
    baseline = load_baseline("analysis_baseline.json")
    new, stale = check_baseline(res, baseline)
    assert [f.render() for f in new] == []
    assert stale == []
    assert baseline["findings"] == []
    # the analyzer actually saw the tree: the lock-order graph must
    # carry the known hierarchy (session/gather above store locks)
    edges = {(e.src, e.dst) for e in res.graph.edges.values()}
    assert ("QuerySession._cv", "MetadataStore._lock") in edges
    assert res.graph.sccs() == []


def test_cli_check_baseline_and_dot(tmp_path, monkeypatch):
    import subprocess
    dot = tmp_path / "locks.dot"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "src",
         "--check-baseline", "--dot", str(dot)],
        cwd=REPO, capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    text = dot.read_text()
    assert text.startswith("digraph lock_order") and "->" in text


def test_cli_fails_on_fresh_violation(tmp_path):
    import subprocess
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent(GUARDED))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", str(bad)],
        cwd=REPO, capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"})
    assert proc.returncode == 1
    assert "guarded-by" in proc.stdout


# ================================================ real-bug regressions
def test_wireclient_close_not_wedged_by_stalled_send():
    """Regression: _send/send_raw held the state lock across
    ``sendall``; a peer that stopped reading left the send blocked on a
    full buffer and close() deadlocked behind it.  Writes now serialize
    on a dedicated IO lock, so close() can shut the socket down and
    unblock the writer."""
    lst = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    lst.bind(("127.0.0.1", 0))
    lst.listen(1)
    accepted = []
    t_acc = threading.Thread(
        target=lambda: accepted.append(lst.accept()[0]), daemon=True)
    t_acc.start()
    client = WireClient(lst.getsockname())
    t_acc.join(timeout=5)
    try:
        client._sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF,
                                4096)
        # peer never reads: this send fills both buffers and blocks
        sender = threading.Thread(
            target=lambda: _swallow_oserror(
                client.send_raw, b"x" * (64 << 20)),
            daemon=True)
        sender.start()
        time.sleep(0.3)          # let the send wedge in the kernel
        closer = threading.Thread(target=client.close, daemon=True)
        closer.start()
        closer.join(timeout=10)
        assert not closer.is_alive(), \
            "close() deadlocked behind a stalled send"
        sender.join(timeout=10)
        assert not sender.is_alive()
    finally:
        for s in accepted:
            s.close()
        lst.close()


def _swallow_oserror(fn, *args):
    try:
        fn(*args)
    except OSError:
        pass


def test_result_cache_oversize_counter_is_atomic():
    """Regression: ``oversize_puts += 1`` ran outside the cache lock;
    concurrent oversize puts (native workers + Thread_3) lost updates."""
    cache = ResultCache(capacity=8, capacity_bytes=64)
    big = np.zeros(1024, dtype=np.float32)       # nbytes >> 64
    n_threads, per_thread = 8, 400
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def hammer(t):
            for i in range(per_thread):
                cache.put(f"e{t}-{i}", "sig", big)
        threads = [threading.Thread(target=hammer, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        sys.setswitchinterval(old)
    assert cache.stats()["oversize_puts"] == n_threads * per_thread


def test_health_registry_mutation_races_iteration():
    """Regression: HealthRegistry._breakers was a bare dict; cluster
    shard join/leave (register/remove on user threads) raced stats()
    iteration on router threads — dict-changed-during-iteration."""
    reg = HealthRegistry(["a", "b"])
    stop = threading.Event()
    errors = []

    def churn():
        i = 0
        while not stop.is_set():
            name = f"shard:{i % 7}"
            try:
                reg.register(name)
                reg.record_failure(name)
                reg.remove(name)
            except Exception as e:  # noqa: BLE001 — the race under test
                errors.append(e)
                return
            i += 1

    def read():
        while not stop.is_set():
            try:
                reg.stats()
                reg.routable("a")
                reg.penalty("shard:3")
            except Exception as e:  # noqa: BLE001 — the race under test
                errors.append(e)
                return

    threads = [threading.Thread(target=churn) for _ in range(3)] \
        + [threading.Thread(target=read) for _ in range(3)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        time.sleep(0.5)
        stop.set()
        for t in threads:
            t.join(timeout=5)
    finally:
        sys.setswitchinterval(old)
    assert errors == []
    assert set(reg.stats()) >= {"a", "b"}


# ================================================== knob coverage pins
def test_engine_nondefault_knobs_are_live():
    """Pins the non-default path of every knob the analyzer found
    unreferenced: breaker parameterization, byte-bounded caching, and
    per-tenant admission weights must construct AND take effect."""
    eng = VDMSAsyncEngine(
        num_remote_servers=1, transport=FAST,
        dispatch="cost", breaker_enabled=True,
        breaker_failure_threshold=0.6, breaker_probes=3,
        cache_capacity=4, cache_capacity_bytes=1 << 20,
        admission="shed", max_inflight_entities=8,
        admission_tenants={"gold": 3.0},
        admission_tenant_default_weight=2.0)
    try:
        b = eng.health.get("native")
        assert b.failure_threshold == 0.6
        assert b.half_open_probes == 3
        assert eng.result_cache.capacity_bytes == 1 << 20
    finally:
        eng.shutdown()


def test_cluster_nondefault_breaker_knobs_are_live():
    sh = ShardedEngine(num_shards=1,
                       breaker_failure_threshold=0.6,
                       breaker_min_samples=2,
                       num_remote_servers=1, transport=FAST)
    try:
        b = sh.health.get("shard:0")
        assert b.failure_threshold == 0.6
        assert b.min_samples == 2
    finally:
        sh.shutdown()
