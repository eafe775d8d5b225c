"""Serving-path chaos suite (ISSUE 10): replica-pool failure isolation.

Three layers, mirroring how the pool is built:

- **jax-free unit chaos** over engine-shaped fakes: hedge
  first-result-wins determinism, loser-slot reclaim, requeue masking a
  flaky replica, the consecutive-error quarantine + probe recovery
  round trip, saturation, and the admission controller's shed rules —
  the state machine logic, fast and deterministic;
- **real-engine chaos** on 2 single-device replicas: every new fault
  site (``serve.dispatch_raise`` / ``serve.dispatch_hang`` /
  ``serve.replica_dead``) threaded through ``InferenceEngine._run``,
  surviving exactly as ROBUSTNESS.md's failure matrix promises, with
  recompiles pinned 0 on every surviving replica;
- **closed-loop chaos bench** (subprocess): the ISSUE acceptance pin —
  ``serve.dispatch_raise@%5`` armed and one replica force-killed
  mid-run, zero hung requests, dead replica quarantined and rerouted,
  errors bounded and structured, recompiles=0 on survivors.

All tier-1 (pinned never-slow by the suite_hygiene serving-chaos gate).
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from milnce_tpu.obs import metrics as obs_metrics
from milnce_tpu.resilience import faults
from milnce_tpu.serving.engine import ReplicaDead
from milnce_tpu.serving.pool import (DEGRADED, QUARANTINED, SERVING,
                                     PoolSaturated, PoolUnavailable,
                                     ReplicaPool)
from milnce_tpu.serving.service import (AdmissionController, DegradedError,
                                        RetrievalService, ShedError,
                                        serve_http)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_FRAMES, _SIZE, _WORDS = 4, 32, 6


# ---------------------------------------------------------------------------
# engine-shaped fakes (jax-free: the pool only needs the embed surface)
# ---------------------------------------------------------------------------

class FakeEngine:
    """Deterministic engine stand-in: ``embed_*`` is a pure function of
    the rows (so first-result-wins hedging is CHECKABLE for value
    determinism), with injectable delay / scripted failures / death."""

    buckets = (4, 8)
    max_batch = 8
    text_words = 4
    embed_dim = 8

    def __init__(self, delay_s: float = 0.0):
        self.delay_s = delay_s
        self.calls = 0
        self.fail_next = 0           # raise on the next N calls
        self._dead = False
        self._lock = threading.Lock()

    @property
    def dead(self) -> bool:
        return self._dead

    def kill(self) -> None:
        self._dead = True

    def bucket_for(self, n):
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(n)

    def embed_text(self, rows):
        if self._dead:
            raise ReplicaDead("fake replica is dead")
        with self._lock:
            self.calls += 1
            if self.fail_next > 0:
                self.fail_next -= 1
                raise RuntimeError("scripted dispatch failure")
            delay = self.delay_s
        if delay:
            time.sleep(delay)
        rows = np.asarray(rows)
        return np.tile(rows[:, :1].astype(np.float32), (1, self.embed_dim))

    embed_video = embed_text

    def recompiles(self):
        return 0

    def stats(self):
        return {"buckets": list(self.buckets), "max_batch": self.max_batch,
                "recompiles": 0, "dead": self._dead, "calls": {}}


def _fake_pool(n=2, **kwargs):
    engines = [FakeEngine() for _ in range(n)]
    kwargs.setdefault("probe_interval_s", 0.05)
    kwargs.setdefault("registry", obs_metrics.MetricsRegistry())
    return engines, ReplicaPool(engines, **kwargs)


def _rows(n=2, fill=3):
    return np.full((n, 4), fill, np.int32)


def _expected(rows, dim=8):
    return np.tile(np.asarray(rows)[:, :1].astype(np.float32), (1, dim))


class _TinyIndex:
    """Brute-force top-2 over 16 rows (numpy; counts its passes)."""

    k = 2
    buckets = query_buckets = FakeEngine.buckets
    bucket_for = FakeEngine.bucket_for

    def __init__(self):
        self.corpus = np.random.default_rng(7).normal(
            size=(16, 8)).astype(np.float32)
        self.passes = []

    def topk(self, q):
        self.passes.append(q.shape[0])
        scores = q @ self.corpus.T
        idx = np.argsort(-scores, axis=1)[:, :2].astype(np.int32)
        return np.take_along_axis(scores, idx, axis=1), idx

    def stats(self):
        return {"size": 16}


def test_a_pooled_service_hands_a_flushs_rows_over_from_the_pools_worker():
    """Over a pool the text flush resolves on a replica's worker, and
    what ISSUE 29 took out of the caller's hands falls out of the same
    callback — the pool worker that resolves a flush puts the call in
    the scan queue; the pass runs on the device worker (ISSUE 30: the
    one loop, no worker per queue)."""
    from milnce_tpu.obs import spans as obs_spans

    engines, pool = _fake_pool(2)
    ring = obs_spans.SpanRecorder(ring=256)
    index = _TinyIndex()
    service = RetrievalService(pool, index, recorder=ring,
                               registry=obs_metrics.MetricsRegistry())
    handed_over_on, passes_on = [], []
    real, real_topk = service._scans.submit_block, index.topk

    def spying(rows, timeout_ms=None, future=None):
        handed_over_on.append(threading.current_thread().name)
        return real(rows, timeout_ms, future=future)

    def spying_topk(q):
        passes_on.append(threading.current_thread().name)
        return real_topk(q)

    service._scans.submit_block, index.topk = spying, spying_topk
    try:
        names = {t.name for t in threading.enumerate()}
        assert not {"text-worker", "topk-worker"} & names
        scores, idx = service.query_ids(_rows(3, fill=5))
        assert idx.shape == (3, 2) and index.passes == [3]
        assert handed_over_on and all(
            name.startswith("pool-replica") for name in handed_over_on)
        assert passes_on == ["device-worker"]
        (flush,) = [r for r in ring.tail() if r["name"] == "topk.flush"]
        assert flush["chained_rows"] == 3       # the same counter
        query = [r for r in ring.tail() if r["name"] == "query"][-1]
        assert query["embed_wait_ms"] > 0.0 and query["topk_ms"] >= 0.0
    finally:
        service.close()
        pool.close()


def test_a_pooled_service_has_one_device_worker_and_no_other_thread():
    engines, pool = _fake_pool(2)
    before = set(threading.enumerate())
    service = RetrievalService(pool, _TinyIndex(),
                               registry=obs_metrics.MetricsRegistry())
    try:
        started = set(threading.enumerate()) - before
        assert [t.name for t in started] == ["device-worker"]
        assert service._device_worker in started
    finally:
        service.close()
        pool.close()
    assert not service._device_worker.is_alive()


class _HeldPool(FakeEngine):
    """Pool-shaped stub of two replicas: ``submit_text`` keeps each batch
    in ``sent`` until the test completes it with ``finish(i)`` (on the
    test's thread, where a replica's worker would)."""

    replicas = (0, 1)

    def __init__(self):
        super().__init__()
        self.sent: list[tuple] = []

    def pool_stats(self):
        return {}

    def set_on_latency(self, cb):
        pass

    def submit_text(self, rows):
        from concurrent.futures import Future

        self.sent.append((Future(), np.array(rows, copy=True)))
        return self.sent[-1][0]

    def unresolved(self) -> int:
        return sum(not fut.done() for fut, _ in self.sent)

    def finish(self, i: int) -> None:
        fut, rows = self.sent[i]
        fut.set_result(self.embed_text(rows))


def _until(cond, what, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.002)


def _held_service_with_three_batches_of_misses():
    """-> (pool, service, caller threads, their outcomes): three callers
    of ``max_batch`` fresh rows each against two replicas that finish
    nothing yet — two batches in flight, the rest waiting."""
    pool = _HeldPool()
    service = RetrievalService(pool, None,
                               registry=obs_metrics.MetricsRegistry())
    outcomes: list = []

    def call(fill):
        try:
            outcomes.append(service.embed_text_ids(
                np.arange(fill, fill + pool.max_batch * 4, dtype=np.int32)
                .reshape(pool.max_batch, 4)))
        except Exception as exc:  # noqa: BLE001 - the outcome IS the test
            outcomes.append(exc)

    threads = [threading.Thread(target=call, args=(100 * i,), daemon=True)
               for i in range(3)]
    for t in threads:
        t.start()
    rows = 3 * pool.max_batch
    _until(lambda: service._batcher.stats()["requests"] == rows
           and len(pool.sent) == 2, "two batches in flight")
    return pool, service, threads, outcomes


def test_a_pooled_service_keeps_one_batch_a_replica_in_flight():
    """Three flushes' worth of misses over two replicas: never more than
    two batches in flight, and the third goes out when a completion
    wakes the device worker (no poll, no window)."""
    pool, service, threads, outcomes = \
        _held_service_with_three_batches_of_misses()
    try:
        time.sleep(0.05)                # the worker has nothing to send
        assert len(pool.sent) == 2 and service._batcher.depth() > 0
        pool.finish(0)
        _until(lambda: len(pool.sent) == 3, "the third batch")
        assert pool.unresolved() == 2
        done = 1
        while any(t.is_alive() for t in threads):
            _until(lambda: len(pool.sent) > done
                   or not any(t.is_alive() for t in threads),
                   "the next batch or the last answer")
            if len(pool.sent) > done:
                assert pool.unresolved() <= 2
                pool.finish(done)
                done += 1
        assert [getattr(o, "shape", o) for o in outcomes] == [(8, 8)] * 3
        flushes = service.health()["batcher"]
        assert flushes["flushes"] == done and flushes["requests"] == 24
    finally:
        service.close()


def test_closing_a_pooled_service_with_a_flush_in_flight_resolves_every_caller():
    """What waits behind the busy replicas is failed by ``close`` at
    once; a batch in flight gets its result from the pool, and its
    caller an answer — nobody hangs."""
    pool, service, threads, outcomes = \
        _held_service_with_three_batches_of_misses()
    service.close()
    assert not service._device_worker.is_alive()
    _until(lambda: outcomes, "a caller whose rows were waiting")
    assert pool.unresolved() == 2       # nothing has completed yet
    assert all(isinstance(o, RuntimeError) and "closed" in str(o)
               for o in outcomes)
    for i in range(len(pool.sent)):     # as ReplicaPool.close would
        pool.finish(i)
    for t in threads:
        t.join(10.0)
    assert len(outcomes) == 3 and not any(t.is_alive() for t in threads)


# ---------------------------------------------------------------------------
# unit chaos: routing, requeue, quarantine/recovery, hedge, saturation
# ---------------------------------------------------------------------------

class TestPoolUnit:
    def test_requeue_masks_one_flaky_replica(self):
        engines, pool = _fake_pool(2)
        try:
            engines[0].fail_next = engines[1].fail_next = 0
            # whichever replica routes first fails once; the requeue to
            # the sibling must answer the caller
            engines[0].fail_next = 1
            engines[1].fail_next = 0
            out = pool.embed_text(_rows())
            np.testing.assert_array_equal(out, _expected(_rows()))
            # either the flaky replica was routed (requeue fired) or the
            # healthy one was — in both cases the request succeeded; force
            # the flaky path deterministically for the counter:
            engines[0].fail_next = engines[1].fail_next = 1
            with pytest.raises(RuntimeError, match="scripted"):
                # both replicas fail -> requeue exhausts -> caller sees it
                pool.embed_text(_rows())
            assert pool.counts()["requeued"] >= 1
        finally:
            pool.close()

    def test_consecutive_errors_quarantine_then_probe_recovers(self):
        engines, pool = _fake_pool(2, error_threshold=2, max_requeues=0)
        try:
            for e in engines:
                e.fail_next = 10**6
            for _ in range(4):          # 2 consecutive errors per replica
                with pytest.raises(RuntimeError):
                    pool.embed_text(_rows())
            states = {pool._replica_state(r) for r in pool.replicas}
            assert states == {QUARANTINED}
            with pytest.raises(PoolUnavailable):
                pool.embed_text(_rows())
            assert pool.counts()["quarantines"] == 2
            # heal the fakes; the background probe must recover both
            for e in engines:
                with e._lock:
                    e.fail_next = 0
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                if all(pool._replica_state(r) == SERVING
                       for r in pool.replicas):
                    break
                time.sleep(0.02)
            assert all(pool._replica_state(r) == SERVING
                       for r in pool.replicas), "probe recovery timed out"
            assert pool.counts()["recoveries"] == 2
            assert pool.counts()["probes"] >= 2
            np.testing.assert_array_equal(pool.embed_text(_rows()),
                                          _expected(_rows()))
        finally:
            pool.close()

    def test_replica_dead_quarantines_immediately_and_probes_keep_failing(
            self):
        engines, pool = _fake_pool(2, error_threshold=5)
        try:
            engines[0].kill()
            engines[1].kill()
            with pytest.raises((ReplicaDead, PoolUnavailable)):
                pool.embed_text(_rows())
            # one dispatch error quarantines a DEAD replica (no
            # threshold wait), and probes never revive it
            time.sleep(0.3)
            dead_states = [pool._replica_state(r) for r in pool.replicas
                           if r.engine.dead]
            assert QUARANTINED in dead_states
            assert pool.counts()["probes"] >= 1
            assert pool.counts()["recoveries"] == 0
        finally:
            pool.close()

    def test_hedge_first_result_wins_is_value_deterministic(self):
        engines, pool = _fake_pool(2, hedge_quantile=0.1, hedge_min_ms=4.0,
                                   probe_interval_s=60.0)
        try:
            rows = _rows()
            for _ in range(20):          # prime the latency window
                pool.embed_text(rows)
            engines[0].delay_s = 0.4     # primary goes slow
            with pool._state_lock:       # force routing onto replica 0
                pool.replicas[1].state = DEGRADED
            t0 = time.monotonic()
            out = pool.embed_text(rows)
            dt = time.monotonic() - t0
            # the hedge (replica 1) answered long before the wedged
            # primary could have, and the value is EXACTLY the function
            # of the rows — whichever copy wins, the answer is the same
            np.testing.assert_array_equal(out, _expected(rows))
            assert dt < 0.3, f"hedge did not win ({dt:.3f}s)"
            counts = pool.counts()
            assert counts["hedged"] == 1
            assert counts["hedge_wins"] == 1
        finally:
            pool.close()

    def test_hedged_loser_queue_slot_is_reclaimed_unexecuted(self):
        engines, pool = _fake_pool(2, hedge_quantile=0.1, hedge_min_ms=4.0,
                                   probe_interval_s=60.0, queue_depth=8)
        try:
            rows = _rows()
            for _ in range(20):
                pool.embed_text(rows)
            calls_before = engines[0].calls + engines[1].calls
            engines[0].delay_s = 0.25
            with pool._state_lock:
                pool.replicas[1].state = DEGRADED
            # A executes on replica 0 (slow); B queues BEHIND it, gets
            # hedged to replica 1, and its stale copy on replica 0 must
            # be skipped when the worker finally reaches it
            fut_a = pool.submit_text(rows)
            fut_b = pool.submit_text(rows)
            np.testing.assert_array_equal(fut_b.result(timeout=5),
                                          _expected(rows))
            np.testing.assert_array_equal(fut_a.result(timeout=5),
                                          _expected(rows))
            deadline = time.monotonic() + 5.0
            while (time.monotonic() < deadline
                   and pool.counts()["reclaimed"] < 1):
                time.sleep(0.02)
            assert pool.counts()["reclaimed"] >= 1
            # the reclaimed copy never executed: 2 logical dispatches,
            # at most 3 executions (A on r0, B's hedge on r1, NOT B on r0)
            assert engines[0].calls + engines[1].calls <= calls_before + 3
        finally:
            pool.close()

    def test_all_queues_full_is_saturated_not_a_hang(self):
        engines, pool = _fake_pool(2, queue_depth=1, probe_interval_s=60.0)
        try:
            for e in engines:
                e.delay_s = 0.5
            futs = []
            t0 = time.monotonic()
            with pytest.raises(PoolSaturated) as exc_info:
                for _ in range(16):      # 2 executing + 2 queued, then boom
                    futs.append(pool.submit_text(_rows()))
            assert time.monotonic() - t0 < 2.0, "saturation must be instant"
            assert exc_info.value.retry_after_ms > 0
            assert pool.counts()["saturated"] >= 1
            for f in futs:               # everything admitted still resolves
                f.result(timeout=10)
        finally:
            pool.close()

    def test_inflight_registry_drains_to_empty(self):
        """Every resolved dispatch must leave the hedge monitor's
        in-flight registry — a submit-vs-worker race that re-added a
        resolved dispatch after its discard leaked it (and its padded
        rows) there forever."""
        _engines, pool = _fake_pool(2)
        try:
            for i in range(20):
                pool.embed_text(_rows(fill=i + 1))
            deadline = time.monotonic() + 2.0
            while time.monotonic() < deadline:
                with pool._state_lock:
                    if not pool._inflight:
                        break
                time.sleep(0.01)
            with pool._state_lock:
                assert not pool._inflight, (
                    f"{len(pool._inflight)} resolved dispatches leaked "
                    "in the in-flight registry")
        finally:
            pool.close()

    def test_raising_latency_observer_does_not_kill_the_worker_lane(self):
        """The service-injected on_latency callback runs on the worker
        thread AFTER the dispatch resolves; if it raises, the lane must
        survive (a dead worker would strand every queued dispatch while
        the replica still reads SERVING)."""
        _engines, pool = _fake_pool(1)
        try:
            def bad_observer(dur_ms, rows):
                raise RuntimeError("observer bug")

            pool.set_on_latency(bad_observer)
            np.testing.assert_array_equal(pool.embed_text(_rows()),
                                          _expected(_rows()))
            # the worker survived the observer's exception: still serving
            np.testing.assert_array_equal(
                pool.embed_text(_rows(fill=5)), _expected(_rows(fill=5)))
            assert pool._replica_state(pool.replicas[0]) == SERVING
        finally:
            pool.close()

    def test_pool_stats_shape(self):
        _engines, pool = _fake_pool(2)
        try:
            pool.embed_text(_rows())
            ps = pool.pool_stats()
            assert len(ps["replicas"]) == 2
            for rep in ps["replicas"]:
                for key in ("id", "state", "outstanding",
                            "consecutive_errors", "dispatches", "errors",
                            "last_probe_age_s", "dead", "recompiles"):
                    assert key in rep, f"pool replica stats missing {key}"
            for key in ("requeued", "hedged", "hedge_wins", "saturated",
                        "quarantines", "recoveries", "probes"):
                assert key in ps
        finally:
            pool.close()


# ---------------------------------------------------------------------------
# admission controller: bounded global queue + deadline feasibility
# ---------------------------------------------------------------------------

class TestAdmission:
    def test_overload_sheds_with_retry_hint(self):
        ac = AdmissionController(4, max_batch=4,
                                 registry=obs_metrics.MetricsRegistry())
        with ac.admit(3, None):
            with pytest.raises(ShedError) as exc_info:
                with ac.admit(2, None):
                    pass
            assert exc_info.value.reason == "overload"
            assert exc_info.value.retry_after_ms > 0
        # slots released on exit: admissible again
        with ac.admit(4, None):
            pass
        assert ac.stats()["shed"] == {"overload": 1}

    def test_deadline_infeasibility_needs_samples_and_is_provable(self):
        depth = [0]
        ac = AdmissionController(1000, max_batch=4, lanes=1,
                                 depth_fn=lambda: depth[0],
                                 registry=obs_metrics.MetricsRegistry())
        depth[0] = 40
        with ac.admit(1, 1.0):       # no flush samples yet: never sheds
            pass
        ac.observe_flush(50.0, 4)    # fastest dispatch ever seen: 50 ms
        with pytest.raises(ShedError) as exc_info:
            with ac.admit(1, 100.0):  # 10 batches ahead -> floor 500 ms
                pass
        assert exc_info.value.reason == "deadline_infeasible"
        assert exc_info.value.retry_after_ms >= 100.0
        with ac.admit(1, 1000.0):    # a feasible deadline passes
            pass
        with ac.admit(1, None):      # no deadline: feasibility can't shed
            pass

    def test_unarmed_controller_never_sheds(self):
        """max_inflight=0 disarms BOTH refusal conditions (the config.py
        contract: max_inflight 'arms the admission controller') — an
        unarmed service must not 429 on feasibility either."""
        depth = [40]
        ac = AdmissionController(0, max_batch=4, lanes=1,
                                 depth_fn=lambda: depth[0],
                                 registry=obs_metrics.MetricsRegistry())
        ac.observe_flush(50.0, 4)
        with ac.admit(1, 100.0):     # would shed if armed
            pass

    def test_admission_judges_the_effective_default_deadline(self):
        """Feasibility must see the deadline the batcher will actually
        apply: a client omitting timeout_ms still gets the service's
        default_timeout_ms judged at admission (a raw None would
        silently disable the check for every default-deadline client)."""
        service = RetrievalService(FakeEngine(), None,
                                   default_timeout_ms=123.0,
                                   registry=obs_metrics.MetricsRegistry())
        try:
            seen = []
            real_admit = service._admission.admit

            def spying_admit(rows, timeout_ms, tier=None):
                seen.append(timeout_ms)
                return real_admit(rows, timeout_ms, tier)

            service._admission.admit = spying_admit
            service.embed_text_ids(_rows(1))
            service.embed_text_ids(_rows(1, fill=4), timeout_ms=77.0)
            assert seen == [123.0, 77.0]
        finally:
            service.close()

    def test_pool_saturated_is_a_refusal_not_a_query_error(self):
        """PoolSaturated reaching the query path is a structured 429
        refusal — it must not inflate the unstructured query_errors
        counter (the error-rate gate's input)."""
        class _SaturatingEngine(FakeEngine):
            def embed_text(self, rows):
                raise PoolSaturated("full", retry_after_ms=5.0)

        class _FakeIndex:
            k = 5

            def topk(self, emb):
                n = emb.shape[0]
                return (np.zeros((n, 5), np.float32),
                        np.zeros((n, 5), np.int64))

            def stats(self):
                return {"size": 1}

        service = RetrievalService(_SaturatingEngine(), _FakeIndex(),
                                   registry=obs_metrics.MetricsRegistry())
        try:
            with pytest.raises(PoolSaturated):
                service.query_ids(_rows(1))
            assert service.health()["query_errors"] == 0
        finally:
            service.close()

    def test_shed_never_hangs_through_the_service(self):
        slow = FakeEngine(delay_s=1.0)
        service = RetrievalService(slow, None,
                                   registry=obs_metrics.MetricsRegistry(),
                                   max_inflight=1)
        try:
            started = threading.Event()

            def occupy():
                started.set()
                service.embed_text_ids(_rows(1))

            t = threading.Thread(target=occupy, daemon=True)
            t.start()
            started.wait()
            time.sleep(0.1)          # the occupant is admitted + in flight
            t0 = time.monotonic()
            with pytest.raises(ShedError):
                service.embed_text_ids(_rows(1, fill=9))
            assert time.monotonic() - t0 < 0.5, "shed must be instant"
            t.join(timeout=10)
        finally:
            service.close()


# ---------------------------------------------------------------------------
# HTTP error contract: structured bodies + Retry-After on 429/503/504
# ---------------------------------------------------------------------------

def _post(base, route, payload):
    req = urllib.request.Request(
        base + route, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=30)


class TestHTTPErrorContract:
    def test_shed_is_429_with_structured_body_and_header_healthz_never_sheds(
            self):
        slow = FakeEngine(delay_s=1.0)
        service = RetrievalService(slow, None,
                                   registry=obs_metrics.MetricsRegistry(),
                                   max_inflight=1)
        server = serve_http(service, port=0)
        threading.Thread(target=server.serve_forever, daemon=True).start()
        base = f"http://127.0.0.1:{server.server_address[1]}"
        try:
            started = threading.Event()

            def occupy():
                started.set()
                try:
                    _post(base, "/v1/embed_text",
                          {"token_ids": [[1, 1, 1, 1]]})
                except Exception:
                    pass
            t = threading.Thread(target=occupy, daemon=True)
            t.start()
            started.wait()
            time.sleep(0.15)
            with pytest.raises(urllib.error.HTTPError) as exc_info:
                _post(base, "/v1/embed_text", {"token_ids": [[2, 2, 2, 2]]})
            err = exc_info.value
            assert err.code == 429
            body = json.loads(err.read())
            assert body["kind"] == "shed"
            assert body["reason"] == "overload"
            assert body["retry_after_ms"] > 0
            assert int(err.headers["Retry-After"]) >= 1
            # the observability plane NEVER sheds, even right now
            for route in ("/healthz", "/metrics"):
                with urllib.request.urlopen(base + route, timeout=30) as r:
                    assert r.status == 200
            t.join(timeout=10)
        finally:
            server.shutdown()
            server.server_close()
            service.close()

    def test_deadline_expiry_is_504_with_retry_hint(self):
        # a single engine flushes a lone row at once (no window to age
        # in): the deadline passes behind a flush in flight
        slow = FakeEngine(delay_s=0.3)
        service = RetrievalService(slow, None,
                                   registry=obs_metrics.MetricsRegistry())
        server = serve_http(service, port=0)
        threading.Thread(target=server.serve_forever, daemon=True).start()
        base = f"http://127.0.0.1:{server.server_address[1]}"
        try:
            threading.Thread(
                target=lambda: _post(base, "/v1/embed_text",
                                     {"token_ids": [[2, 2, 2, 2]]}).close(),
                daemon=True).start()
            deadline = time.monotonic() + 10
            while slow.calls < 1:
                assert time.monotonic() < deadline
                time.sleep(0.002)
            with pytest.raises(urllib.error.HTTPError) as exc_info:
                _post(base, "/v1/embed_text",
                      {"token_ids": [[3, 3, 3, 3]], "timeout_ms": 1})
            err = exc_info.value
            assert err.code == 504
            body = json.loads(err.read())
            assert body["kind"] == "deadline_expired"
            assert body["retry_after_ms"] > 0
            assert int(err.headers["Retry-After"]) >= 1
        finally:
            server.shutdown()
            server.server_close()
            service.close()

    def test_degraded_ladder_cache_hits_answered_misses_503_then_full_503(
            self):
        engines, pool = _fake_pool(2, probe_interval_s=60.0)
        from milnce_tpu.serving.cache import EmbeddingLRUCache

        service = RetrievalService(pool, None,
                                   cache=EmbeddingLRUCache(64),
                                   registry=obs_metrics.MetricsRegistry())
        server = serve_http(service, port=0)
        threading.Thread(target=server.serve_forever, daemon=True).start()
        base = f"http://127.0.0.1:{server.server_address[1]}"
        try:
            hot = [[5, 5, 5, 5]]
            with _post(base, "/v1/embed_text", {"token_ids": hot}) as r:
                cached = json.loads(r.read())["embeddings"]
            for e in engines:            # kill the whole pool
                e.kill()
            # drive a dispatch error so both replicas quarantine
            with pytest.raises(urllib.error.HTTPError):
                _post(base, "/v1/embed_text", {"token_ids": [[6, 6, 6, 6]]})
            # cache-only tier: the hot row still answers...
            with _post(base, "/v1/embed_text", {"token_ids": hot}) as r:
                assert json.loads(r.read())["embeddings"] == cached
            # ...a miss is a STRUCTURED 503
            with pytest.raises(urllib.error.HTTPError) as exc_info:
                _post(base, "/v1/embed_text", {"token_ids": [[7, 7, 7, 7]]})
            err = exc_info.value
            assert err.code == 503
            body = json.loads(err.read())
            assert body["kind"] == "degraded"
            assert body["reason"] in ("cache_only", "no_healthy_replicas")
            assert int(err.headers["Retry-After"]) >= 1
            # /healthz stays up and surfaces the pool section
            with urllib.request.urlopen(base + "/healthz", timeout=30) as r:
                h = json.loads(r.read())
            assert "pool" in h and len(h["pool"]["replicas"]) == 2
            assert {rep["state"] for rep in h["pool"]["replicas"]} \
                == {QUARANTINED}
            assert h["admission"]["max_inflight"] == 0
        finally:
            server.shutdown()
            server.server_close()
            service.close()
            pool.close()


# ---------------------------------------------------------------------------
# fault-site grammar
# ---------------------------------------------------------------------------

def test_serving_fault_sites_parse_and_unknown_still_rejected():
    spec = faults.parse_spec(
        "serve.dispatch_raise@%5;serve.dispatch_hang@1:x=0.5;"
        "serve.replica_dead@3")
    assert set(spec) == {"serve.dispatch_raise", "serve.dispatch_hang",
                         "serve.replica_dead"}
    assert spec["serve.dispatch_hang"].x == 0.5
    with pytest.raises(ValueError, match="unknown fault site"):
        faults.parse_spec("serve.typo@*")


# ---------------------------------------------------------------------------
# real-engine chaos: the fault sites through InferenceEngine._run on a
# 2-replica pool (single-device engines, own dispatch locks)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def real_stack():
    import jax
    import jax.numpy as jnp

    from milnce_tpu.models import S3D

    model = S3D(num_classes=16, vocab_size=64, word_embedding_dim=8,
                text_hidden_dim=16, inception_blocks=1)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, _FRAMES, _SIZE, _SIZE, 3)),
                           jnp.zeros((1, _WORDS), jnp.int32))
    pool = ReplicaPool.build(
        model, dict(variables), 2, text_words=_WORDS,
        video_shape=(_FRAMES, _SIZE, _SIZE, 3), max_batch=8, min_bucket=4,
        probe_interval_s=0.2, error_threshold=2,
        registry=obs_metrics.MetricsRegistry())
    yield dict(model=model, variables=variables, pool=pool)
    pool.close()


class TestRealEngineChaos:
    def _ids(self, n=4, seed=0):
        return np.random.default_rng(seed).integers(
            1, 64, (n, _WORDS)).astype(np.int32)

    def test_dispatch_raise_survives_via_requeue(self, real_stack):
        pool = real_stack["pool"]
        clean = pool.embed_text(self._ids())
        before = pool.counts()["requeued"]
        with faults.armed("serve.dispatch_raise@1"):
            out = pool.embed_text(self._ids())
        np.testing.assert_array_equal(out, clean)
        assert pool.counts()["requeued"] == before + 1
        assert all(pool._replica_state(r) != QUARANTINED
                   for r in pool.replicas)

    def test_dispatch_hang_slows_but_survives(self, real_stack):
        pool = real_stack["pool"]
        clean = pool.embed_text(self._ids(seed=1))
        with faults.armed("serve.dispatch_hang@1:x=0.4"):
            t0 = time.monotonic()
            out = pool.embed_text(self._ids(seed=1))
            dt = time.monotonic() - t0
        np.testing.assert_array_equal(out, clean)
        assert dt >= 0.4, "the hang site did not fire"
        assert pool.recompiles() == 0

    def test_quarantine_then_recovery_round_trip(self, real_stack):
        pool = real_stack["pool"]
        rec_before = pool.counts()["recoveries"]
        with faults.armed("serve.dispatch_raise@*"):
            outcomes = []
            for _ in range(6):
                try:
                    pool.embed_text(self._ids(1))
                    outcomes.append("ok")
                except Exception as exc:
                    outcomes.append(type(exc).__name__)
                if "PoolUnavailable" in outcomes:
                    break
            assert "PoolUnavailable" in outcomes, outcomes
            assert all(pool._replica_state(r) == QUARANTINED
                       for r in pool.replicas)
        # disarmed: probes must recover BOTH replicas within a few
        # intervals, and the recovered pool serves with zero recompiles
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if all(pool._replica_state(r) == SERVING
                   for r in pool.replicas):
                break
            time.sleep(0.05)
        assert all(pool._replica_state(r) == SERVING
                   for r in pool.replicas), "probe recovery timed out"
        assert pool.counts()["recoveries"] >= rec_before + 2
        assert pool.embed_text(self._ids()).shape[0] == 4
        assert pool.recompiles() == 0

    def test_replica_dead_reroutes_within_a_probe_interval(self,
                                                          real_stack):
        # fresh pool: this test leaves a permanently dead replica behind
        pool = ReplicaPool.build(
            real_stack["model"], dict(real_stack["variables"]), 2,
            text_words=_WORDS, video_shape=(_FRAMES, _SIZE, _SIZE, 3),
            max_batch=8, min_bucket=4, probe_interval_s=0.2,
            registry=obs_metrics.MetricsRegistry())
        try:
            clean = pool.embed_text(self._ids(seed=2))
            with faults.armed("serve.replica_dead@1"):
                out = pool.embed_text(self._ids(seed=2))
            # the request that KILLED a replica still answered (requeue),
            # bitwise-identical — replicas are exact peers
            np.testing.assert_array_equal(out, clean)
            dead = [r for r in pool.replicas if r.engine.dead]
            alive = [r for r in pool.replicas if not r.engine.dead]
            assert len(dead) == 1 and len(alive) == 1
            assert pool._replica_state(dead[0]) == QUARANTINED
            # traffic immediately reroutes to the survivor...
            for _ in range(3):
                np.testing.assert_array_equal(
                    pool.embed_text(self._ids(seed=2)), clean)
            # ...probes keep failing (death is permanent), and the
            # survivor never recompiled
            time.sleep(0.5)
            assert pool._replica_state(dead[0]) == QUARANTINED
            assert pool.counts()["recoveries"] == 0
            assert pool.recompiles() == 0
        finally:
            pool.close()


# ---------------------------------------------------------------------------
# ISSUE acceptance: closed-loop chaos bench (subprocess)
# ---------------------------------------------------------------------------

def test_chaos_serve_bench_closed_loop_acceptance(tmp_path):
    """``serve.dispatch_raise@%5`` armed + one replica force-killed
    (``serve.replica_dead@25``) mid-run on a 2-replica pool: the
    closed-loop bench completes with zero hung requests (the run
    finishing inside its timeout IS the no-hang pin — every worker
    joins), the dead replica quarantined with traffic rerouted, errors
    bounded and structured (zero UNstructured errors), and recompiles=0
    on the surviving replica.  (Fast-child exemption in
    test_suite_hygiene.py: tiny preset + shared persistent compile
    cache, seconds-scale.)"""
    out = tmp_path / "SB_CHAOS.json"
    env = dict(os.environ)
    env.pop("MILNCE_FAULTS", None)
    # share the suite's persistent compile cache with the child (the
    # script itself doesn't configure one — production benches must
    # measure real compiles): warmup becomes disk hits after the first
    # run, keeping this acceptance child seconds-scale in tier-1
    env.setdefault("JAX_COMPILATION_CACHE_DIR", "/tmp/jax_test_cache")
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.0")
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "scripts", "serve_bench.py"),
         "--backend", "cpu", "--preset", "tiny", "--mode", "closed",
         "--duration", "2", "--concurrency", "4", "--replicas", "2",
         "--max_batch", "8", "--min_bucket", "8",
         "--distinct", "0", "--corpus", "16",
         "--probe_interval_s", "0.2", "--max_requeues", "2",
         "--faults", "serve.dispatch_raise@%5;serve.replica_dead@25",
         "--out", str(out)],
        capture_output=True, text=True, timeout=540, env=env)
    assert proc.returncode == 0, (
        f"chaos bench failed\nstdout:\n{proc.stdout}\n"
        f"stderr:\n{proc.stderr}")
    report = json.loads(out.read_text())
    assert report["requests"] > 20, "the chaos window barely served"
    # unstructured failures bounded at ~zero: a raise-hit request either
    # answers via requeue or refuses STRUCTURED (503 degraded when only
    # the quarantined replica was left to retry on); at most a rare
    # interleaving can exhaust the requeue budget on back-to-back
    # scheduled occurrences
    assert report["errors"] <= 2, (report["errors"], proc.stdout)
    assert report["error_rate"] <= 0.01
    res = report["resilience"]
    assert res["requeued"] >= 1, "dispatch_raise@%5 never requeued"
    assert res["quarantines"] >= 1, "the dead replica never quarantined"
    replicas = report["pool"]["replicas"]
    dead = [r for r in replicas if r["dead"]]
    alive = [r for r in replicas if not r["dead"]]
    assert len(dead) == 1 and dead[0]["state"] == QUARANTINED
    # traffic rerouted: the survivor kept dispatching after the kill
    assert len(alive) == 1 and alive[0]["dispatches"] > dead[0]["dispatches"]
    # recompiles=0 on every surviving replica (pool recompiles sums
    # survivors; the per-replica stats pin it individually)
    assert report["engine"]["recompiles"] == 0
    assert alive[0]["recompiles"] == 0
