"""Runtime lock sanitizer gates (ISSUE 7, Pass 3b): SanitizedLock
order/cycle/self-deadlock/hold-budget semantics, the make_lock env
switch, an in-process 16-thread hammer over the real batcher + cache +
registry + service code under sanitized locks, and the subprocess
hammer that drives the FULL serving stack (engine -> index -> /metrics)
with ``MILNCE_LOCK_SANITIZE=1`` set before import so even the
module-level DEVICE_DISPATCH_LOCK is sanitized.

The ABBA test is the acceptance pin: a deliberately inverted ordering
MUST raise LockOrderError at the inversion site, without needing the
actual deadlock interleaving.
"""

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from milnce_tpu.analysis import lockrt
from milnce_tpu.analysis.lockrt import (LockHoldBudgetExceeded,
                                        LockOrderError, LockOrderGraph,
                                        SanitizedLock, SanitizedRLock)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CHILD = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "lockrt_hammer_child.py")


def _pair(graph=None):
    g = graph if graph is not None else LockOrderGraph()
    return SanitizedLock("A", graph=g), SanitizedLock("B", graph=g)


class TestOrderDetection:
    def test_abba_inversion_raises_across_threads(self):
        """The acceptance pin: thread 1 establishes A -> B; thread 2
        taking B then A raises at the inversion — no deadlock needed."""
        a, b = _pair()
        established = threading.Event()
        caught = []

        def t1():
            with a:
                with b:
                    pass
            established.set()

        def t2():
            established.wait(timeout=10)
            try:
                with b:
                    with a:
                        pass
            except LockOrderError as exc:
                caught.append(exc)

        threads = [threading.Thread(target=t1), threading.Thread(target=t2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert len(caught) == 1
        assert "cycle" in str(caught[0])
        # both edges' first sites are recorded for the post-mortem
        assert "A" in str(caught[0]) and "B" in str(caught[0])

    def test_consistent_order_never_raises(self):
        a, b = _pair()

        def worker():
            for _ in range(200):
                with a:
                    with b:
                        pass

        threads = [threading.Thread(target=worker) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        # graph holds exactly the one established edge
        (edge,) = [e[:2] for e in a._graph.snapshot()["edges"]]
        assert edge == ["A", "B"]

    def test_three_lock_cycle_detected(self):
        g = LockOrderGraph()
        a, b = _pair(g)
        c = SanitizedLock("C", graph=g)
        with a:
            with b:
                pass
        with b:
            with c:
                pass
        with pytest.raises(LockOrderError, match="cycle"):
            with c:
                with a:
                    pass

    def test_self_deadlock_detected(self):
        a = SanitizedLock("A", graph=LockOrderGraph())
        with a:
            with pytest.raises(LockOrderError, match="self-deadlock"):
                a.acquire()
        # the held stack unwound correctly: re-acquire after release works
        with a:
            pass

    def test_trylock_is_exempt_from_ordering(self):
        """Lockdep parity: a failed (or successful) non-blocking acquire
        can never deadlock, so it must neither record edges nor be
        judged against the order graph — the avoid-deadlock-by-trylock
        pattern stays legal."""
        g = LockOrderGraph()
        a, b = _pair(g)
        with a:
            with b:
                pass                        # establishes A -> B
        with b:
            assert a.acquire(blocking=False)   # would be B -> A if judged
            a.release()
        assert [e[:2] for e in g.snapshot()["edges"]] == [["A", "B"]]
        # ...and a trylock on a self-held lock returns False, not a
        # self-deadlock report (stdlib semantics)
        with a:
            assert a.acquire(blocking=False) is False

    def test_rlock_reacquire_is_legal(self):
        r = SanitizedRLock("R", graph=LockOrderGraph())
        with r:
            with r:
                pass
        with r:
            pass

    def test_lock_classes_share_discipline_by_name(self):
        """Two INSTANCES with one name are one order class (lockdep
        semantics): AB on instance pair 1, BA on pair 2 still raises."""
        g = LockOrderGraph()
        a1, b1 = SanitizedLock("A", graph=g), SanitizedLock("B", graph=g)
        a2, b2 = SanitizedLock("A", graph=g), SanitizedLock("B", graph=g)
        with a1:
            with b1:
                pass
        with pytest.raises(LockOrderError):
            with b2:
                with a2:
                    pass


class TestHoldBudget:
    def test_budget_exceeded_raises_after_release(self):
        a = SanitizedLock("A", hold_budget_s=0.01, graph=LockOrderGraph())
        with pytest.raises(LockHoldBudgetExceeded, match="budget"):
            with a:
                time.sleep(0.05)
        # the lock was RELEASED before raising — nobody is wedged
        assert a.acquire(blocking=False)
        a.release()

    def test_within_budget_is_silent(self):
        a = SanitizedLock("A", hold_budget_s=5.0, graph=LockOrderGraph())
        with a:
            pass

    def test_budget_report_never_masks_the_body_exception(self):
        """An exception unwinding through the with-block is the root
        cause; the budget overrun must not replace its traceback."""
        a = SanitizedLock("A", hold_budget_s=0.01, graph=LockOrderGraph())
        with pytest.raises(ValueError, match="root cause"):
            with a:
                time.sleep(0.05)
                raise ValueError("root cause")
        assert a.acquire(blocking=False)    # still released cleanly
        a.release()


class TestMakeLock:
    def test_plain_lock_without_env(self, monkeypatch):
        monkeypatch.delenv(lockrt.ENV_SANITIZE, raising=False)
        lk = lockrt.make_lock("x")
        assert not isinstance(lk, SanitizedLock)
        with lk:
            pass

    def test_sanitized_with_env_and_budget(self, monkeypatch):
        monkeypatch.setenv(lockrt.ENV_SANITIZE, "1")
        monkeypatch.setenv(lockrt.ENV_HOLD_BUDGET_MS, "250")
        lk = lockrt.make_lock("serving.test")
        assert isinstance(lk, SanitizedLock)
        assert lk.name == "serving.test"
        assert lk.hold_budget_s == pytest.approx(0.25)

    def test_budget_zero_means_disabled(self, monkeypatch):
        """MILNCE_LOCK_HOLD_BUDGET_MS=0 disables the budget — a literal
        0.0 s budget would raise on essentially every release."""
        monkeypatch.setenv(lockrt.ENV_SANITIZE, "1")
        monkeypatch.setenv(lockrt.ENV_HOLD_BUDGET_MS, "0")
        lk = lockrt.make_lock("serving.test0")
        assert lk.hold_budget_s is None
        with lk:
            pass


# ---------------------------------------------------------------------------
# in-process hammer: real batcher + cache + registry + service code
# under sanitized locks (a fake engine keeps it jax-free and fast)
# ---------------------------------------------------------------------------

class _FakeEngine:
    """Engine-shaped stand-in: bucket ladder semantics without jax.
    embed_text acquires the dispatch-named sanitized lock so the order
    graph sees the same device-worker -> dispatch shape as production."""

    buckets = (4, 8)
    max_batch = 8
    text_words = 4
    embed_dim = 8

    def __init__(self):
        self._dispatch = lockrt.make_lock("serving.device_dispatch")
        self._calls = 0

    def bucket_for(self, n):
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(n)

    def embed_text(self, rows):
        with self._dispatch:
            self._calls += 1
            return np.tile(rows[:, :1].astype(np.float32), (1, 8))

    def recompiles(self):
        return 0

    def stats(self):
        return {"recompiles": 0, "calls": {"text@8": self._calls}}


def test_in_process_service_hammer_under_sanitizer(monkeypatch):
    """16 threads through RetrievalService.embed_text_ids + health +
    Prometheus scrape, every component lock sanitized: exact final
    counts, zero order violations."""
    monkeypatch.setenv(lockrt.ENV_SANITIZE, "1")
    lockrt.reset_global_graph()
    try:
        from milnce_tpu.obs import metrics as obs_metrics
        from milnce_tpu.serving.cache import EmbeddingLRUCache
        from milnce_tpu.serving.service import RetrievalService

        service = RetrievalService(
            _FakeEngine(), None, cache=EmbeddingLRUCache(256),
            registry=obs_metrics.MetricsRegistry())
        assert isinstance(service.cache._lock, SanitizedLock)
        assert isinstance(service._batcher._children_lock, SanitizedLock)
        errors = []
        n_embed, n_read, k = 12, 4, 10

        def embedder(tid):
            try:
                for i in range(k):
                    rows = np.full((1, 4), tid * 100 + i, np.int32)
                    out = service.embed_text_ids(rows, timeout_ms=30_000)
                    assert out.shape == (1, 8)
            except Exception as exc:  # noqa: BLE001 - the assertion IS the test
                errors.append(exc)

        def reader():
            try:
                for _ in range(k):
                    service.health()
                    service.metrics_text()
            except Exception as exc:  # noqa: BLE001
                errors.append(exc)

        threads = [threading.Thread(target=embedder, args=(t,))
                   for t in range(n_embed)]
        threads += [threading.Thread(target=reader) for _ in range(n_read)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        service.close()
        assert not errors, errors
        # every row was a distinct cache key: exact request accounting
        assert service.health()["batcher"]["requests"] == n_embed * k
        # the sanitizer actually saw the mesh: ordering edges recorded
        assert lockrt.GLOBAL_GRAPH.snapshot()["edges"]
    finally:
        lockrt.reset_global_graph()


# ---------------------------------------------------------------------------
# subprocess hammer: the FULL serving stack (engine -> index -> HTTP)
# with MILNCE_LOCK_SANITIZE=1 set before import
# ---------------------------------------------------------------------------

def test_serving_hammer_subprocess_under_sanitizer():
    """ISSUE 7 acceptance: 16 threads drive batcher -> engine -> index
    and /metrics in a child process whose locks — including the
    module-level DEVICE_DISPATCH_LOCK — are all SanitizedLock, cycle
    detection armed.  Exit 0 == no order violation, no recompiles, all
    requests 200.  (Fast child exemption in test_suite_hygiene.py: tiny
    preset + the shared persistent compile cache, seconds-scale.)"""
    env = dict(os.environ)
    env["MILNCE_LOCK_SANITIZE"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (flags
                            + " --xla_force_host_platform_device_count=8"
                            ).strip()
    proc = subprocess.run([sys.executable, _CHILD], capture_output=True,
                          text=True, timeout=540, env=env)
    assert proc.returncode == 0, (
        f"hammer child failed\nstdout:\n{proc.stdout}\n"
        f"stderr:\n{proc.stderr}")
    assert "HAMMER_OK" in proc.stdout, proc.stdout
