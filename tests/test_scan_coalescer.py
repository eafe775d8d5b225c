"""The query path of a single-engine ``RetrievalService``: every caller
that is waiting shares ONE pass over the index (ISSUE 27), and ONE device
worker takes the tower's flush and the pass in turn, so that a flush's
rows ride the very next pass (ISSUE 29).  Stub engine and stub index
(numpy only, no device): each lists what it ran in ``order``, and can
hold a flush or a pass in flight while the test lines callers up behind
it.  Decided by order and counts, never by wall time."""

import threading
import time

import numpy as np
import pytest

from milnce_tpu.obs import metrics as obs_metrics
from milnce_tpu.obs import spans as obs_spans
from milnce_tpu.serving.batcher import DeadlineExpired
from milnce_tpu.serving.cache import EmbeddingLRUCache
from milnce_tpu.serving.service import RetrievalService, ShedError

_WORDS, _DIM, _K = 4, 8, 3
_LADDER = (4, 8)


def _bucket_for(n, ladder=_LADDER):
    for b in ladder:
        if n <= b:
            return b
    raise ValueError(f"{n} queries exceeds the top query bucket "
                     f"{ladder[-1]}")


class _Held:
    """``hold()`` makes the next run wait, inside it, until
    ``release()``; ``step()`` lets exactly that one through and holds
    the one after it."""

    def __init__(self):
        self.entered = threading.Event()
        self._gate = None

    def hold(self):
        self.entered.clear()
        self._gate = threading.Event()

    def release(self):
        gate, self._gate = self._gate, None
        gate.set()

    def step(self):
        gate, self._gate = self._gate, threading.Event()
        self.entered.clear()
        gate.set()

    def _wait_here(self):
        gate = self._gate
        if gate is not None:
            self.entered.set()
            assert gate.wait(10), "the test never released the run"


class _Engine(_Held):
    """``embed_text`` is a fixed linear map of the token ids.  ``order``
    (shared with the index when the fixture makes both) lists every run:
    ``("flush", real rows)`` here — the batcher pads, so the real rows
    are those that are not all zero."""

    text_words = _WORDS
    embed_dim = _DIM

    def __init__(self, order=None, ladder=_LADDER):
        super().__init__()
        self.buckets, self.max_batch = ladder, ladder[-1]
        self.bucket_for = lambda n: _bucket_for(n, ladder)
        self.order = [] if order is None else order
        self._w = np.random.default_rng(0).normal(
            size=(_WORDS, _DIM)).astype(np.float32)

    def embed_text(self, rows):
        self.order.append(("flush", int(rows.any(axis=1).sum())))
        self._wait_here()
        return rows.astype(np.float32) @ self._w

    def recompiles(self):
        return 0

    def stats(self):
        return {}


class _Index(_Held):
    """Brute-force top-k over a small corpus.  ``scans`` lists the rows
    of every pass (``order`` has them as ``("pass", rows)``)."""

    k = _K

    def __init__(self, ladder=_LADDER):
        super().__init__()
        self.query_buckets = ladder
        self.bucket_for = lambda n: _bucket_for(n, ladder)
        self.order = []
        self.corpus = np.random.default_rng(1).normal(
            size=(32, _DIM)).astype(np.float32)
        self.scans = []

    def rank(self, q):
        scores = q @ self.corpus.T
        idx = np.argsort(-scores, axis=1)[:, :_K].astype(np.int32)
        return np.take_along_axis(scores, idx, axis=1), idx

    def topk(self, q):
        self.scans.append(q.shape[0])
        self.order.append(("pass", q.shape[0]))
        self._wait_here()
        return self.rank(q)

    def stats(self):
        return {"size": self.corpus.shape[0]}


class _LiveIndex(_Index):
    """The live index's surface: the generation is read when the scan
    starts, as ``LiveRetrievalIndex.topk_with_gen`` captures it."""

    def __init__(self, ladder=_LADDER):
        super().__init__(ladder)
        self.generation = 1

    def topk_with_gen(self, q):
        gen = self.generation
        return (*self.topk(q), gen)


def _rows(seed, n=1):
    return np.random.default_rng(seed).integers(
        1, 50, (n, _WORDS)).astype(np.int32)


@pytest.fixture
def made():
    """-> make(index=None, **service kwargs) -> (service, index, ring);
    everything made is closed afterwards."""
    services = []

    def make(index=None, **kw):
        index = _Index() if index is None else index
        ring = obs_spans.SpanRecorder(ring=4096)
        kw.setdefault("cache", EmbeddingLRUCache(64))
        svc = RetrievalService(
            _Engine(index.order, index.query_buckets), index, recorder=ring,
            registry=obs_metrics.MetricsRegistry(), **kw)
        services.append((svc, index))
        return svc, index, ring

    yield make
    for svc, index in services:
        for held in (index, svc.engine):
            if held._gate is not None:
                held.release()
        svc.close()


class _Caller(threading.Thread):
    """One ``query_ids_with_gen`` call on a thread of its own."""

    def __init__(self, svc, rows, **kw):
        super().__init__(daemon=True)
        self.svc, self.rows, self.kw = svc, rows, kw
        self.answer = self.error = None
        self.start()

    def run(self):
        try:
            self.answer = self.svc.query_ids_with_gen(self.rows, **self.kw)
        except Exception as exc:
            self.error = exc

    def done(self, timeout=10.0):
        self.join(timeout)
        assert not self.is_alive(), "the call never came back"
        return self


def _wait_for(cond, what, timeout=5.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, f"never saw: {what}"
        time.sleep(0.002)


def _handed_over(svc):
    """Rows handed to the coalescer so far: hits by their callers, the
    rest by the device worker as its flush embeds them."""
    return svc.health()["scans"]["requests"]


def _to_embed(svc):
    """Rows handed to the text batcher so far (the misses)."""
    return svc.health()["batcher"]["requests"]


def _alone(svc, index, rows):
    """What ``index.topk`` answers for these rows by themselves."""
    return index.rank(svc.engine.embed_text(rows))


# ---- (a) callers that wait share a pass ------------------------------------

def test_waiting_callers_share_at_most_two_scans(made):
    svc, index, ring = made()
    n = 7
    index.hold()
    callers = [_Caller(svc, _rows(100 + i)) for i in range(n)]
    _wait_for(lambda: _to_embed(svc) == n and index.entered.is_set(),
              "all rows in a queue behind the first scan")
    index.release()
    for c in callers:
        assert c.done().error is None, c.error
    assert len(index.scans) <= 2 and sum(index.scans) == n
    for i, c in enumerate(callers):
        scores, idx, gen = c.answer
        want_scores, want_idx = _alone(svc, index, _rows(100 + i))
        np.testing.assert_array_equal(idx, want_idx)
        np.testing.assert_allclose(scores, want_scores, rtol=1e-6)
        assert idx.dtype == np.int32 and gen is None
    flushes = [r for r in ring.tail() if r["name"] == "topk.flush"]
    assert [r["rows"] for r in flushes] == index.scans
    assert all(r["batcher"] == "topk" and r["bucket"] == _bucket_for(r["rows"])
               and {"queue_wait_ms", "queue_wait_mean_ms", "dur_ms"} <= set(r)
               for r in flushes)
    # the text batcher's flush records keep their name to themselves
    assert {r["batcher"] for r in ring.tail()
            if r["name"] == "batcher.flush"} == {"text"}


def test_a_pass_never_carries_more_than_the_top_bucket(made):
    svc, index, _ = made()
    n = 2 * _LADDER[-1] + 3
    index.hold()
    callers = [_Caller(svc, _rows(200 + i)) for i in range(n)]
    _wait_for(lambda: _to_embed(svc) == n and index.entered.is_set(),
              "all rows in a queue")
    index.release()
    for c in callers:
        assert c.done().error is None, c.error
    assert max(index.scans) <= _LADDER[-1] and sum(index.scans) == n
    assert len(index.scans) <= 4          # the first, then 8 + 8 + the rest


# ---- (b) a lone caller waits for nobody ------------------------------------

def test_a_lone_call_is_scanned_at_once(made):
    svc, index, ring = made()
    rows = _rows(300)
    svc.query_ids(rows)                       # a miss: flush, then pass
    t0 = time.monotonic()
    svc.query_ids(rows)                       # a hit: the scan alone
    took_ms = (time.monotonic() - t0) * 1e3
    assert took_ms < 250.0, took_ms
    assert index.scans == [1, 1]
    hit = [r for r in ring.tail() if r["name"] == "query"][-1]
    assert hit["cache_hits"] == 1 and hit["topk_ms"] < 250.0
    flush = [r for r in ring.tail() if r["name"] == "topk.flush"][-1]
    assert flush["queue_wait_ms"] < 100.0


# ---- (c) a deadline holds in the scan queue --------------------------------

def test_a_row_whose_deadline_passes_is_never_scanned(made):
    svc, index, ring = made()
    first, late = _rows(400), _rows(401)
    svc.query_ids(np.concatenate([first, late]))      # both cached now
    assert index.scans == [2]
    index.hold()
    a = _Caller(svc, first)
    _wait_for(index.entered.is_set, "the first scan in flight")
    b = _Caller(svc, late, timeout_ms=30.0)
    _wait_for(lambda: _handed_over(svc) == 4, "the late row in the queue")
    time.sleep(0.06)         # its deadline passes behind the scan in flight
    index.release()
    assert a.done().error is None
    assert isinstance(b.done().error, DeadlineExpired), b.error
    time.sleep(0.05)
    assert index.scans == [2, 1]          # the late row rode no pass
    flushes = [r for r in ring.tail() if r["name"] == "topk.flush"]
    assert [r["rows"] for r in flushes] == [2, 1]
    assert svc.health()["scans"]["deadline_expired"] == 1


# ---- (d) a scan that fails -------------------------------------------------

def test_a_failing_scan_fails_its_rows_and_the_worker_lives(made,
                                                            monkeypatch):
    svc, index, _ = made()
    real, calls = index.topk, []

    def failing_once(q):
        calls.append(q.shape[0])
        if len(calls) == 2:
            raise RuntimeError("scan failed")
        return real(q)

    monkeypatch.setattr(index, "topk", failing_once)
    index.hold()
    head = _Caller(svc, _rows(500))
    _wait_for(index.entered.is_set, "the first scan in flight")
    riders = [_Caller(svc, _rows(501 + i)) for i in range(3)]
    _wait_for(lambda: _to_embed(svc) == 4, "three rows behind it")
    index.release()
    assert head.done().error is None
    for c in riders:
        assert isinstance(c.done().error, RuntimeError)
        assert "scan failed" in str(c.error)
    assert calls == [1, 3]
    scores, idx = svc.query_ids(_rows(500))           # answered again
    np.testing.assert_array_equal(idx, _alone(svc, index, _rows(500))[1])
    health = svc.health()
    assert health["scans"]["batch_errors"] == 1
    assert health["query_errors"] == 3


# ---- (e) the index's method is looked up at every scan ---------------------

def test_replacing_topk_on_the_instance_changes_the_answers(made):
    svc, index, _ = made()
    rows = _rows(600)
    _, before = svc.query_ids(rows)
    real = index.topk

    def altered(q):
        scores, idx = real(q)
        return scores, (idx + 1) % index.corpus.shape[0]

    index.topk = altered                  # as the benchmark's fault does
    _, after = svc.query_ids(rows)
    np.testing.assert_array_equal(after,
                                  (before + 1) % index.corpus.shape[0])


# ---- (f) a live index: each call the generation that ranked it -------------

def test_a_swap_between_two_scans_stamps_each_call_with_its_own(made):
    svc, index, _ = made(index=_LiveIndex(), cache=EmbeddingLRUCache(0))
    index.hold()
    a = _Caller(svc, _rows(700, n=2))
    _wait_for(index.entered.is_set, "the first scan in flight")
    b = _Caller(svc, _rows(701, n=3))
    _wait_for(lambda: _to_embed(svc) == 5, "the second call behind it")
    index.generation = 2                  # the swap lands between the two
    index.release()
    assert a.done().error is None and b.done().error is None
    assert a.answer[2] == 1 and b.answer[2] == 2
    assert index.scans == [2, 3]          # a call's rows ride one scan
    assert a.answer[0].shape == (2, _K) and b.answer[1].shape == (3, _K)


# ---- (g) close ---------------------------------------------------------------

def test_close_resolves_every_waiting_future(made):
    svc, index, _ = made()
    index.hold()
    head = _Caller(svc, _rows(800))
    _wait_for(index.entered.is_set, "the first scan in flight")
    waiting = [_Caller(svc, _rows(801 + i)) for i in range(4)]
    _wait_for(lambda: _to_embed(svc) == 5, "four rows behind it")
    closer = threading.Thread(target=svc.close, daemon=True)
    closer.start()
    time.sleep(0.05)
    index.release()                       # the scan in flight finishes
    closer.join(10)
    assert not closer.is_alive()
    assert head.done().error is None      # it was on the device: answered
    for c in waiting:
        c.done()
        assert c.answer is not None or "closed" in str(c.error)
    assert any(c.error is not None for c in waiting) or sum(index.scans) == 5
    with pytest.raises(RuntimeError, match="closed"):
        svc.query_ids(_rows(800))


# ---- calls of more than one row ----------------------------------------------

def test_a_calls_rows_are_never_split_over_two_scans(made):
    svc, index, _ = made()
    index.hold()
    head = _Caller(svc, _rows(900))
    _wait_for(index.entered.is_set, "the first scan in flight")
    # 5 + 5 rows do not fit the top bucket of 8: two passes of 5, never
    # 8 and 2
    calls = [_Caller(svc, _rows(901 + i, n=5)) for i in range(2)]
    _wait_for(lambda: _to_embed(svc) == 11, "both calls behind it")
    index.release()
    for c in [head] + calls:
        assert c.done().error is None, c.error
    assert index.scans == [1, 5, 5]
    for i, c in enumerate(calls):
        np.testing.assert_array_equal(
            c.answer[1], _alone(svc, index, _rows(901 + i, n=5))[1])


def test_a_call_larger_than_the_top_bucket_keeps_its_error(made):
    svc, index, _ = made()
    with pytest.raises(ValueError, match="exceeds the top query bucket"):
        svc.query_ids(_rows(1000, n=_LADDER[-1] + 1))
    assert index.scans == []
    svc.query_ids(_rows(1001))            # and the worker lives on
    assert index.scans == [1]


def test_a_shed_call_touches_neither_queue(made):
    svc, index, _ = made(max_inflight=1)
    with pytest.raises(ShedError):
        svc.query_ids(_rows(1100, n=2))
    health = svc.health()
    assert health["scans"]["requests"] == 0
    assert health["batcher"]["requests"] == 0 and index.scans == []


def test_a_service_without_an_index_has_no_coalescer():
    engine = _Engine()
    svc = RetrievalService(engine, None,
                           registry=obs_metrics.MetricsRegistry())
    try:
        assert svc.health()["scans"] is None
        with pytest.raises(ValueError, match="without a retrieval index"):
            svc.query_ids(_rows(1200))
        # the embedding-only entry rides the device worker's flush
        rows = _rows(1201, n=3)
        got = svc.embed_text_ids(rows)
        assert {kind for kind, _ in engine.order} == {"flush"}
        assert sum(n for _, n in engine.order) == 3
        np.testing.assert_allclose(got, engine.embed_text(rows), rtol=1e-6)
    finally:
        svc.close()



# ---- the device worker: flush, pass, flush, pass (ISSUE 29) -------------------

def _passes(ring):
    return [r for r in ring.tail() if r["name"] == "topk.flush"]


def test_a_flushs_rows_ride_the_very_next_pass(made):
    """(a) and (h): what a flush embedded is ranked before the next flush
    starts, and the pass's record says how many of its rows came so."""
    svc, index, ring = made()
    engine = svc.engine
    engine.hold()
    first = _Caller(svc, _rows(1300))
    _wait_for(engine.entered.is_set, "the first flush in flight")
    rest = [_Caller(svc, _rows(1301 + i)) for i in range(3)]
    _wait_for(lambda: _to_embed(svc) == 4, "three rows behind the flush")
    engine.step()                 # the first flush ends; the next is held
    _wait_for(engine.entered.is_set, "the second flush in flight")
    # the first flush's row was ranked, and answered, before it started
    assert index.order == [("flush", 1), ("pass", 1), ("flush", 3)]
    assert first.done().error is None
    engine.release()
    for c in rest:
        assert c.done().error is None, c.error
    assert index.order == [("flush", 1), ("pass", 1), ("flush", 3),
                           ("pass", 3)]
    assert [(r["rows"], r["chained_rows"]) for r in _passes(ring)] == [
        (1, 1), (3, 3)]
    # the flush's own record carries no such attribute
    assert all("chained_rows" not in r for r in ring.tail()
               if r["name"] == "batcher.flush")


def test_a_hit_that_waits_during_a_flush_shares_its_pass(made):
    """(b): the cached row goes straight to the scan queue and rides the
    ONE pass that ranks what the flush in flight embeds."""
    svc, index, ring = made()
    hit, miss = _rows(1400), _rows(1401)
    svc.query_ids(hit)                                  # cached now
    del index.order[:]
    svc.engine.hold()
    m = _Caller(svc, miss)
    _wait_for(svc.engine.entered.is_set, "the flush in flight")
    h = _Caller(svc, hit)
    _wait_for(lambda: _handed_over(svc) == 2, "the hit in the scan queue")
    svc.engine.release()
    assert m.done().error is None and h.done().error is None
    assert index.order == [("flush", 1), ("pass", 2)]
    last = _passes(ring)[-1]
    assert (last["rows"], last["chained_rows"]) == (2, 1)
    np.testing.assert_array_equal(h.answer[1], _alone(svc, index, hit)[1])
    np.testing.assert_array_equal(m.answer[1], _alone(svc, index, miss)[1])


def test_flushes_and_passes_alternate_while_both_queues_hold_rows(made):
    """(c): 20 hits and 20 misses wait behind a pass in flight, more than
    two top buckets of each.  Neither program runs twice in a row until
    the other's queue is empty: the flush does not starve under hits that
    never stop, nor the pass under misses."""
    svc, index, _ = made(cache=EmbeddingLRUCache(64))
    top = _LADDER[-1]
    hits = [_rows(1500 + i) for i in range(20)]
    for lo in range(0, 20, top):
        svc.embed_text_ids(np.concatenate(hits[lo:lo + top]))   # cached
    before = _handed_over(svc)
    index.hold()
    callers = [_Caller(svc, hits[0])]
    _wait_for(index.entered.is_set, "a pass in flight")
    del index.order[:]
    callers += [_Caller(svc, r) for r in hits[1:]]
    callers += [_Caller(svc, _rows(1600 + i)) for i in range(20)]
    _wait_for(lambda: _handed_over(svc) - before == 20
              and _to_embed(svc) - 20 == 20, "everything in a queue")
    index.release()
    for c in callers:
        assert c.done().error is None, c.error
    # 20 rows to embed: three flushes; 19 + 20 blocks to rank: five passes
    assert index.order == [
        ("flush", 8), ("pass", 8), ("flush", 8), ("pass", 8),
        ("flush", 4), ("pass", 8), ("pass", 8), ("pass", 7)]


def test_64_callers_of_misses_settle_into_two_cohorts():
    """(d): a flush that lasts until every other caller has sent its next
    query (a LONG flush, told by counts: the stub waits for them) leaves
    two cohorts that take the tower in turn — every query is embedded by
    the first or the second flush that starts after it was sent, and a
    flush carries half the callers — where a pass that waits out the
    NEXT cohort's flush makes three."""
    ladder, n_callers, n_calls = (16, 32, 64), 64, 6
    carried = {}                      # a query's token -> its flush
    # the worker's takes of the text queue, counted as each returns, and
    # the count at each pass: a caller answered by a pass sends again
    # once the worker has come back to the text queue after it (what it
    # does before a woken caller runs, unless the host is loaded)
    taken, taken_at_pass = [0], []

    class LongFlush(_Engine):
        def embed_text(self, rows):
            out = super().embed_text(rows)
            flush_no = sum(kind == "flush" for kind, _ in self.order)
            # what the flushes before this one embedded has been ranked
            # and answered: each of those callers sends its next query,
            # if it has one left
            answered = np.bincount(
                [(t - 1) // n_calls for t in carried], minlength=n_callers)
            for r in rows[rows.any(axis=1)]:
                carried[int(r[0])] = flush_no
            expected = int(np.minimum(answered + 1, n_calls).sum())
            deadline = time.monotonic() + 10
            # every query sent so far is taken or IN the queue (a submit
            # is counted before its row is put there)
            while len(carried) + svc._batcher.depth() < expected:
                assert time.monotonic() < deadline, "callers never came"
                time.sleep(0.0005)
            return out

    class CountedIndex(_Index):
        def topk(self, q):
            taken_at_pass.append(taken[0])
            return super().topk(q)

    index = CountedIndex(ladder)
    engine = LongFlush(index.order, ladder)
    svc = RetrievalService(engine, index, cache=EmbeddingLRUCache(0),
                           registry=obs_metrics.MetricsRegistry(),
                           recorder=obs_spans.SpanRecorder(ring=8192))
    real_take = svc._batcher.take

    def counted_take():
        batch = real_take()
        taken[0] += 1
        return batch

    svc._batcher.take = counted_take
    sent_at, errors = {}, []

    def loop(c):
        try:
            for i in range(n_calls):
                if i:       # the take after the pass that answered it
                    deadline = time.monotonic() + 10
                    while taken[0] <= taken_at_pass[-1]:
                        assert time.monotonic() < deadline, "no take"
                        time.sleep(0.0005)
                token = 1 + c * n_calls + i                 # unique, not 0
                sent_at[token] = sum(k == "flush" for k, _ in index.order)
                svc.query_ids(np.full((1, _WORDS), token, np.int32))
        except Exception as exc:                            # noqa: BLE001
            errors.append(exc)

    threads = [threading.Thread(target=loop, args=(c,), daemon=True)
               for c in range(n_callers)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not [t for t in threads if t.is_alive()]
        assert not errors, errors[:3]
    finally:
        svc.close()
    assert len(carried) == n_callers * n_calls
    late = {t: carried[t] - sent_at[t] for t in carried
            if carried[t] - sent_at[t] > 2}
    assert not late, late
    flushes = [n for kind, n in index.order if kind == "flush"]
    # the first flush carries whoever came first; from then on two
    # cohorts alternate, until callers begin to leave
    settled = flushes[1:2 * (n_calls - 1)]
    assert sum(settled) / len(settled) >= 28, flushes
    assert all(a + b == n_callers for a, b in zip(settled, settled[1:])), \
        flushes
    # and every flush's rows were ranked by the pass right after it
    kinds = [kind for kind, _ in index.order]
    assert "flush,flush" not in ",".join(kinds), index.order


def test_a_call_of_hits_and_misses_rides_one_pass_one_generation(made):
    """(e): the call's misses are embedded by two different flushes; its
    block enters the scan queue when the LAST of them exists, whole."""
    svc, index, ring = made(index=_LiveIndex())
    engine = svc.engine
    mixed = _rows(1700, n=5)
    svc.query_ids(mixed[:2])                            # two of five cached
    del index.order[:]
    engine.hold()
    head = _Caller(svc, _rows(1710))
    _wait_for(engine.entered.is_set, "a flush in flight")
    singles = [_Caller(svc, _rows(1711 + i)) for i in range(6)]
    _wait_for(lambda: _to_embed(svc) == 2 + 7, "six rows behind it")
    call = _Caller(svc, mixed)
    _wait_for(lambda: _to_embed(svc) == 2 + 10, "the call's three misses")
    engine.step()             # the 8-row flush: six singles + two of three
    _wait_for(engine.entered.is_set, "the call's last miss in a flush")
    index.generation = 2      # a swap lands before the call is ranked
    engine.release()
    for c in [head, call] + singles:
        assert c.done().error is None, c.error
    assert index.order == [("flush", 1), ("pass", 1), ("flush", 8),
                           ("pass", 6), ("flush", 1), ("pass", 5)]
    scores, idx, gen = call.answer
    assert gen == 2 and idx.shape == (5, _K)
    np.testing.assert_array_equal(idx, _alone(svc, index, mixed)[1])
    # of the last pass's five rows the flush before it embedded one
    assert [r["chained_rows"] for r in _passes(ring)][-3:] == [1, 6, 1]
    query = [r for r in ring.tail() if r["name"] == "query"
             and r["rows"] == 5][-1]
    assert query["cache_hits"] == 2 and query["embed_wait_ms"] > 0.0
    assert 0.0 <= query["topk_ms"] <= query["embed_wait_ms"] + query["dur_ms"]


def test_a_lone_callers_miss_is_flushed_with_no_timer(made):
    """(f): a flush and a pass, in that order, as soon as it arrives."""
    svc, index, ring = made()
    c = _Caller(svc, _rows(1800))
    assert c.done(10.0).error is None
    assert index.order == [("flush", 1), ("pass", 1)]
    flush = [r for r in ring.tail() if r["name"] == "batcher.flush"][-1]
    assert flush["queue_wait_ms"] < 5_000.0


def test_a_row_whose_deadline_passes_is_never_embedded(made):
    """(g): ``timeout_ms`` bounds the wait in the text queue too; the
    worker finds out when it comes back from the device."""
    svc, index, _ = made()
    svc.engine.hold()
    a = _Caller(svc, _rows(1900))
    _wait_for(svc.engine.entered.is_set, "a flush in flight")
    b = _Caller(svc, _rows(1901), timeout_ms=30.0)
    _wait_for(lambda: _to_embed(svc) == 2, "the late row in the queue")
    time.sleep(0.06)          # its deadline passes behind the flush
    svc.engine.release()
    assert a.done().error is None
    assert isinstance(b.done().error, DeadlineExpired), b.error
    assert index.order == [("flush", 1), ("pass", 1)]
    health = svc.health()
    assert health["batcher"]["deadline_expired"] == 1
    assert health["admission"]["inflight"] == 0


def test_a_failing_flush_fails_its_rows_and_the_worker_lives(made,
                                                             monkeypatch):
    svc, index, _ = made()
    engine = svc.engine
    real, calls = engine.embed_text, []

    def failing_once(rows):
        calls.append(int(rows.any(axis=1).sum()))
        if len(calls) == 2:
            raise RuntimeError("flush failed")
        return real(rows)

    monkeypatch.setattr(engine, "embed_text", failing_once)
    svc._batcher._run_batch = failing_once      # bound at construction
    engine.hold()
    head = _Caller(svc, _rows(2000))
    _wait_for(engine.entered.is_set, "the first flush in flight")
    riders = [_Caller(svc, _rows(2001 + i, n=2)) for i in range(2)]
    _wait_for(lambda: _to_embed(svc) == 5, "four rows behind it")
    engine.release()
    assert head.done().error is None
    for c in riders:
        assert isinstance(c.done().error, RuntimeError)
        assert "flush failed" in str(c.error)
    assert calls == [1, 4] and index.scans == [1]   # no pass for them
    scores, idx = svc.query_ids(_rows(2001, n=2))   # embedded again
    np.testing.assert_array_equal(
        idx, _alone(svc, index, _rows(2001, n=2))[1])
    health = svc.health()
    assert health["batcher"]["batch_errors"] == 1
    assert health["query_errors"] == 4
    assert health["admission"]["inflight"] == 0

# ---- stress ------------------------------------------------------------------

def test_stress_every_caller_gets_its_own_rows_back(made):
    """More callers than cores, a short switch interval, calls of 1 to 5
    rows: every answer is the one its rows would get alone, every row
    rides exactly one pass, and no pass is larger than the top bucket."""
    import sys

    svc, index, _ = made(cache=EmbeddingLRUCache(16))
    callers, calls = 24, 25
    errors, answered = [], [0] * callers

    def loop(c):
        rng = np.random.default_rng(5000 + c)
        try:
            for i in range(calls):
                rows = _rows(int(rng.integers(0, 40)), n=int(rng.integers(1, 6)))
                scores, idx = svc.query_ids(rows)
                want_scores, want_idx = _alone(svc, index, rows)
                np.testing.assert_array_equal(idx, want_idx)
                np.testing.assert_allclose(scores, want_scores, rtol=1e-6)
                answered[c] += rows.shape[0]
        except Exception as exc:                     # noqa: BLE001
            errors.append(exc)

    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=loop, args=(c,), daemon=True)
                   for c in range(callers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(before)
    assert not [t for t in threads if t.is_alive()]
    assert not errors, errors[:3]
    assert sum(index.scans) == sum(answered) > 0
    assert max(index.scans) <= _LADDER[-1]
    assert len(index.scans) < callers * calls       # passes were shared
    health = svc.health()["scans"]
    assert health["requests"] == sum(answered)
    assert health["flushes"] == len(index.scans)


# ---- (h) the device worker's turn, phase by phase (ISSUE 37) ----------------

_PHASES = ("sleep", "take", "prepare", "run", "scatter", "account")
# a kernel that accounts CPU time by the tick (the chip's host: 10 ms)
# reads a thread's CPU up to one tick above its wall time
_TICK_MS = 10.5


def _named(ring, name):
    return [r for r in ring.tail() if r["name"] == name]


def test_a_miss_is_two_turns_of_the_worker_each_joined_to_its_flush(made):
    svc, index, ring = made()
    svc.query_ids(_rows(900))                 # a miss: flush, then pass
    _wait_for(lambda: len(_named(ring, "worker.turn")) == 2, "two turns")
    text, topk = _named(ring, "worker.turn")
    assert (text["batcher"], topk["batcher"]) == ("text", "topk")
    assert text["rows"] == topk["rows"] == 1
    assert (text["bucket"], topk["bucket"]) == (_LADDER[0], _LADDER[0])
    for turn in (text, topk):
        assert {p + "_ms" for p in _PHASES} <= set(turn)
        assert sum(turn[p + "_ms"] for p in _PHASES) == pytest.approx(
            turn["dur_ms"], abs=1e-3)
        for p in _PHASES[1:]:
            assert 0 <= turn[p + "_cpu_ms"] <= turn[p + "_ms"] + _TICK_MS, p
    assert text["sleep_ms"] > 0               # it slept until the submit
    # a turn and its flush record join by (epoch, batcher), not by order
    (flush,) = _named(ring, "batcher.flush")
    (scan,) = _named(ring, "topk.flush")
    assert flush["epoch"] == text["epoch"] and flush["dur_ms"] == text["run_ms"]
    assert scan["epoch"] == topk["epoch"] and scan["dur_ms"] == topk["run_ms"]
    assert scan["chained_rows"] == 1 and "chained_rows" not in topk
    # the hand-over to the scan queue is the text turn's scatter: the pass
    # found the row there
    assert text["scatter_ms"] > 0 and text["mono"] < scan["mono"]


def test_every_flush_record_has_exactly_one_turn_of_its_epoch_and_batcher(
        made):
    svc, index, ring = made()
    callers = [_Caller(svc, _rows(950 + i % 7, n=1 + i % 3))
               for i in range(24)]
    for c in callers:
        assert c.done().error is None, c.error
    flushes = _named(ring, "batcher.flush") + _named(ring, "topk.flush")
    _wait_for(lambda: len(_named(ring, "worker.turn")) == len(flushes),
              "a turn a flush")
    turns = [(t["epoch"], t["batcher"]) for t in _named(ring, "worker.turn")]
    assert len(set(turns)) == len(turns)
    assert sorted(turns) == sorted((f["epoch"], f["batcher"])
                                   for f in flushes)
    by_key = {(t["epoch"], t["batcher"]): t
              for t in _named(ring, "worker.turn")}
    for f in flushes:
        assert by_key[f["epoch"], f["batcher"]]["rows"] == f["rows"]


def test_the_workers_turns_account_for_its_time(made):
    """Between the ends of the first and the last turn lies the sum of
    the turns after the first (each record is written right after its
    turn's last reading: a millisecond a record of slack)."""
    svc, index, ring = made()
    for i in range(12):
        svc.query_ids(_rows(1000 + i))
        time.sleep(0.003)                     # the worker sleeps between
    _wait_for(lambda: len(_named(ring, "worker.turn")) == 24, "24 turns")
    turns = _named(ring, "worker.turn")
    covered = sum(t["dur_ms"] for t in turns[1:]) / 1e3
    assert covered == pytest.approx(turns[-1]["mono"] - turns[0]["mono"],
                                    abs=0.001 * len(turns))
    assert sum(t["sleep_ms"] for t in turns[1:]) > 0


# ---- (i) a pass's scatter rides the next program -----------------------------

class _Fetch:
    """What a timed stub's program returns: ``jax.device_get`` calls
    ``copy_to_host_async`` first — the stub's fetch, listed in ``order``
    as ``("got flush" | "got pass", rows)`` and held there like a
    device still computing — and then ``__array__``."""

    def __init__(self, stub, kind, rows, value):
        self.stub, self.kind, self.rows, self.value = stub, kind, rows, value

    def copy_to_host_async(self):
        self.stub.order.append(("got " + self.kind, self.rows))
        self.stub._wait_here()
        if self.stub.fail_fetch:
            self.stub.fail_fetch -= 1
            raise RuntimeError(f"{self.kind} fetch failed")

    def __array__(self, dtype=None, copy=None):
        return self.value


class _Timed:
    """A stub whose program runs through a hold's ``round_trip`` on ONE
    lock shared with the other stub: the call lists ``(kind, rows)`` in
    ``order`` (or raises, ``fail_call`` times), the fetch is held by
    ``hold()``.  ``<kind>_ms`` a rung is what warm-up would have timed."""

    fail_call = fail_fetch = 0

    def _round_trip(self, site, kind, rows, value):
        from milnce_tpu.serving.engine import device_dispatch

        def call(x):
            self.order.append((kind, rows))
            if self.fail_call:
                self.fail_call -= 1
                raise RuntimeError(f"{kind} call failed")
            return _Fetch(self, kind, rows, value)

        with device_dispatch(site, lock=self.lock, recorder=self.ring,
                             rows=rows) as hold:
            return np.asarray(hold.round_trip(call, value, None))


class _TimedEngine(_Timed, _Engine):
    def __init__(self, order, ladder, ms, lock, ring):
        _Engine.__init__(self, order, ladder)
        self.text_device_ms = dict.fromkeys(ladder, ms)
        self.lock, self.ring = lock, ring

    def embed_text(self, rows):
        return self._round_trip("engine.text", "flush",
                                int(rows.any(axis=1).sum()),
                                rows.astype(np.float32) @ self._w)


class _TimedIndex(_Timed, _Index):
    def __init__(self, ladder, ms, lock, ring):
        _Index.__init__(self, ladder)
        self.device_ms = dict.fromkeys(ladder, ms)
        self.lock, self.ring = lock, ring

    def topk(self, q):
        self.scans.append(q.shape[0])
        scores, idx = self.rank(q)
        out = self._round_trip("index.topk", "pass", q.shape[0],
                               np.concatenate([scores, idx], axis=1))
        return out[:, :_K], out[:, _K:].astype(np.int32)


@pytest.fixture
def timed():
    """-> make(text_ms, pass_ms) -> (service, index, ring): stubs whose
    rungs have device times, so the service holds each pass's scatter."""
    services = []

    def make(text_ms, pass_ms):
        ring = obs_spans.SpanRecorder(ring=4096)
        lock = threading.Lock()
        index = _TimedIndex(_LADDER, pass_ms, lock, ring)
        engine = _TimedEngine(index.order, _LADDER, text_ms, lock, ring)
        svc = RetrievalService(engine, index, recorder=ring,
                               cache=EmbeddingLRUCache(64),
                               registry=obs_metrics.MetricsRegistry())
        services.append((svc, index))
        return svc, index, ring

    yield make
    for svc, index in services:
        for held in (index, svc.engine):
            if held._gate is not None:
                held.release()
        svc.close()


def _ranked(svc, index, rows):
    """What these rows would be answered alone, off the stubs' order."""
    return index.rank(rows.astype(np.float32) @ svc.engine._w)


def _overlaps(ring, site):
    return [r.get("overlap_rows", 0) for r in _named(ring, "dispatch")
            if r["site"] == site]


def test_a_lone_caller_is_answered_without_waiting_for_another_program(
        timed):
    svc, index, ring = timed(text_ms=0.2, pass_ms=8.0)
    c = _Caller(svc, _rows(4100))
    assert c.done().error is None
    np.testing.assert_array_equal(c.answer[1],
                                  _ranked(svc, index, _rows(4100))[1])
    assert index.order == [("flush", 1), ("got flush", 1), ("pass", 1),
                           ("got pass", 1)]
    (scan,) = _passes(ring)
    assert scan["rode"] == "none"           # nothing to carry it: at once
    assert _overlaps(ring, "index.topk") == _overlaps(ring, "engine.text") \
        == [0]


def test_a_short_flush_runs_alone_and_the_pass_after_it_carries(timed):
    """The flush is the shorter program: the next pass is CALLED before
    the previous pass's answers are set, and FETCHED after them."""
    svc, index, ring = timed(text_ms=0.2, pass_ms=8.0)
    index.hold()
    head = _Caller(svc, _rows(4200))
    _wait_for(index.entered.is_set, "the head's pass at its fetch")
    rest = [_Caller(svc, _rows(4201 + i)) for i in range(2)]
    _wait_for(lambda: _to_embed(svc) == 3, "two rows behind the pass")
    index.step()                # the head's pass is fetched, the next held
    _wait_for(index.entered.is_set, "the next pass at its fetch")
    assert head.done().error is None        # answered while it computes
    assert index.order == [
        ("flush", 1), ("got flush", 1), ("pass", 1), ("got pass", 1),
        ("flush", 2), ("got flush", 2), ("pass", 2), ("got pass", 2)]
    first = _passes(ring)[0]
    assert (first["rows"], first["rode"]) == (1, "topk")
    # its scatter ran after the short flush and inside the next pass's
    # round trip, between the call and the fetch
    (_, flush2) = _named(ring, "batcher.flush")
    assert flush2["mono"] < first["mono"]
    assert first["chained_rows"] == 1
    index.release()
    for c in rest:
        assert c.done().error is None, c.error
        np.testing.assert_array_equal(
            c.answer[1], _ranked(svc, index, c.rows)[1])
    assert _overlaps(ring, "index.topk") == [0, 1]
    assert [r["rode"] for r in _passes(ring)] == ["topk", "none"]
    assert [r["chained_rows"] for r in _passes(ring)] == [1, 2]
    overlap = [r for r in _named(ring, "dispatch") if r.get("overlap_rows")]
    assert overlap[0]["overlap_ms"] >= 0 and "overlap_error" not in overlap[0]


def test_a_long_flush_carries_the_scatter_and_no_answer_waits_for_its_get(
        timed):
    svc, index, ring = timed(text_ms=40.0, pass_ms=5.0)
    engine = svc.engine
    index.hold()
    head = _Caller(svc, _rows(4300))
    _wait_for(index.entered.is_set, "the head's pass at its fetch")
    rest = [_Caller(svc, _rows(4301 + i)) for i in range(3)]
    _wait_for(lambda: _to_embed(svc) == 4, "three rows behind the pass")
    engine.hold()
    index.release()
    _wait_for(engine.entered.is_set, "the next flush at its fetch")
    assert head.done().error is None        # answered before that get
    assert index.order[4:] == [("flush", 3), ("got flush", 3)]
    first = _passes(ring)[0]
    assert (first["rows"], first["rode"]) == (1, "text")
    engine.release()
    for c in rest:
        assert c.done().error is None, c.error
    assert _overlaps(ring, "engine.text") == [0, 1]
    assert [r["rode"] for r in _passes(ring)] == ["text", "none"]
    kinds = ",".join(k for k, _ in index.order if not k.startswith("got"))
    assert kinds == "flush,pass,flush,pass"


def test_a_next_program_that_fails_at_its_call_leaves_the_scatter_at_once(
        timed):
    svc, index, ring = timed(text_ms=0.2, pass_ms=8.0)
    index.hold()
    head = _Caller(svc, _rows(4400))
    _wait_for(index.entered.is_set, "the head's pass at its fetch")
    rest = [_Caller(svc, _rows(4401 + i)) for i in range(2)]
    _wait_for(lambda: _to_embed(svc) == 3, "two rows behind the pass")
    index.fail_call = 1                     # the pass that would carry it
    index.release()
    assert head.done().error is None
    for c in rest:
        assert "pass call failed" in str(c.done().error)
    # the failed pass's record is written first, then the head's, at once
    failed, first = _passes(ring)
    assert (first["rows"], first["rode"]) == (1, "none")
    assert (failed["rows"], failed["error"]) == (2, "RuntimeError")
    scores, idx = svc.query_ids(_rows(4401))          # the worker lives
    np.testing.assert_array_equal(idx, _ranked(svc, index, _rows(4401))[1])


def test_a_next_program_that_fails_at_its_fetch_answers_what_it_carried(
        timed):
    svc, index, ring = timed(text_ms=40.0, pass_ms=5.0)
    index.hold()
    head = _Caller(svc, _rows(4500))
    _wait_for(index.entered.is_set, "the head's pass at its fetch")
    rest = [_Caller(svc, _rows(4501 + i)) for i in range(2)]
    _wait_for(lambda: _to_embed(svc) == 3, "two rows behind the pass")
    svc.engine.fail_fetch = 1               # the flush that carries it
    index.release()
    assert head.done().error is None
    for c in rest:
        assert "flush fetch failed" in str(c.done().error)
    assert _passes(ring)[0]["rode"] == "text"
    assert svc.health()["batcher"]["batch_errors"] == 1


def test_a_pass_that_fails_at_its_fetch_fails_its_own_callers(timed):
    svc, index, ring = timed(text_ms=0.2, pass_ms=8.0)
    index.fail_fetch = 1
    c = _Caller(svc, _rows(4600))
    assert "pass fetch failed" in str(c.done().error)
    (scan,) = _passes(ring)
    assert scan["error"] == "RuntimeError" and scan["rode"] == "none"
    assert svc.health()["scans"]["batch_errors"] == 1
    assert svc.query_ids(_rows(4600))[1].shape == (1, _K)


def test_close_answers_or_fails_every_caller_of_a_held_scatter(timed):
    svc, index, ring = timed(text_ms=0.2, pass_ms=8.0)
    engine = svc.engine
    index.hold()
    head = _Caller(svc, _rows(4700))
    _wait_for(index.entered.is_set, "the head's pass at its fetch")
    rest = [_Caller(svc, _rows(4701 + i)) for i in range(2)]
    _wait_for(lambda: _to_embed(svc) == 3, "two rows behind the pass")
    engine.hold()
    index.release()             # the head's scatter is held, the flush
    _wait_for(engine.entered.is_set, "the short flush at its fetch")
    closer = threading.Thread(target=svc.close, daemon=True)
    closer.start()
    _wait_for(lambda: svc._scans._closed.is_set(), "the queues closed")
    engine.release()
    closer.join(10)
    assert not closer.is_alive()
    assert head.done().error is None        # the held scatter ran
    for c in rest:
        c.done()
        assert c.answer is not None or "closed" in str(c.error)
    assert _passes(ring)[0]["rode"] == "none"


def test_a_scatter_that_raises_fails_its_callers_and_not_the_carrier(
        timed, monkeypatch):
    svc, index, ring = timed(text_ms=0.2, pass_ms=8.0)
    scans, real = svc._scans, svc._scans._scatter
    index.hold()
    head = _Caller(svc, _rows(4800))
    _wait_for(index.entered.is_set, "the head's pass at its fetch")
    rest = [_Caller(svc, _rows(4801 + i)) for i in range(2)]
    _wait_for(lambda: _to_embed(svc) == 3, "two rows behind the pass")

    def raising_once(live, out):
        monkeypatch.setattr(scans, "_scatter", real)
        raise KeyError("scatter")

    monkeypatch.setattr(scans, "_scatter", raising_once)
    index.release()
    assert isinstance(head.done().error, KeyError)
    for c in rest:
        assert c.done().error is None, c.error
    assert _passes(ring)[0]["rode"] == "topk"


def test_a_service_without_times_holds_no_scatter(made):
    """Stubs that cannot give their rungs' device times: today's order,
    and no pass record says where its scatter rode."""
    svc, index, ring = made()
    assert svc._device_ms is None
    svc.query_ids(_rows(4900, n=2))
    assert index.order == [("flush", 2), ("pass", 2)]
    assert all("rode" not in r for r in _passes(ring))


def test_the_worker_s_turns_tile_its_time_with_held_scatters(timed):
    """One ``worker.turn`` a flush or pass, joined by epoch; a pass's
    scatter counts in its own turn wherever it ran, and between the ends
    of the first and the last record lies the sum of the others."""
    svc, index, ring = timed(text_ms=0.2, pass_ms=8.0)
    callers = [_Caller(svc, _rows(5000 + i % 9, n=1 + i % 2))
               for i in range(24)]
    for c in callers:
        assert c.done().error is None, c.error
    flushes = _named(ring, "batcher.flush") + _passes(ring)
    _wait_for(lambda: len(_named(ring, "worker.turn")) == len(flushes),
              "a turn a flush")
    turns = _named(ring, "worker.turn")
    assert sorted((t["epoch"], t["batcher"]) for t in turns) == sorted(
        (f["epoch"], f["batcher"]) for f in flushes)
    for t in turns:
        assert sum(t[p + "_ms"] for p in _PHASES) == pytest.approx(
            t["dur_ms"], abs=1e-3)
    rode = [p for p in _passes(ring) if p["rode"] != "none"]
    by_key = {(t["epoch"], t["batcher"]): t for t in turns}
    assert all(by_key[p["epoch"], "topk"]["scatter_ms"] > 0 for p in rode)
    covered = sum(t["dur_ms"] for t in turns[1:]) / 1e3
    assert covered == pytest.approx(turns[-1]["mono"] - turns[0]["mono"],
                                    abs=0.001 * len(turns))
