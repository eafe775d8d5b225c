"""Dynamic micro-batcher edge semantics (ISSUE 4 satellite): pad/unpad
identity, bucket selection at boundaries, deadline-expired -> error
(never a silent drop), batch-failure propagation, blocks, lanes.  The
batcher owns no thread (ISSUE 30): the tests are its owner — ``submit``,
then ``take()`` and ``flush()`` on the test's own thread — and the two
that need a second thread start :class:`_Owner`.  jax-free by
construction — the batcher is numpy-only and these tests pin that
boundary too (a fake run_batch stands in for the engine)."""

import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from milnce_tpu.obs import metrics as obs_metrics
from milnce_tpu.obs import spans as obs_spans
from milnce_tpu.serving.batcher import DeadlineExpired, DynamicBatcher

_BUCKETS = (4, 8)
# a deadline that has passed by the time the owner next looks (1 ns)
_AGED_MS = 1e-6


def _bucket_for(n: int) -> int:
    for b in _BUCKETS:
        if n <= b:
            return b
    raise ValueError(n)


class _FakeEngine:
    """Records every padded batch; result row = payload * 2 (so per-row
    identity is checkable through pad/unpad)."""

    def __init__(self, fail=False, delay_s=0.0):
        self.batches: list[np.ndarray] = []
        self.fail = fail
        self.delay_s = delay_s
        self._lock = threading.Lock()

    def __call__(self, rows: np.ndarray) -> np.ndarray:
        if self.delay_s:
            time.sleep(self.delay_s)
        if self.fail:
            raise ValueError("injected batch failure")
        with self._lock:
            self.batches.append(np.array(rows, copy=True))
        return rows * 2.0


def _mk(engine, **kw):
    kw.setdefault("max_batch", _BUCKETS[-1])
    kw.setdefault("wake", lambda: None)
    return DynamicBatcher(engine, _bucket_for, **kw)


def _woken(engine, **kw):
    """A batcher and the list its ``wake`` appends to."""
    woken = []
    return _mk(engine, wake=lambda: woken.append(1), **kw), woken


def _turn(b) -> list:
    """One turn of the owner: what waits now, flushed."""
    batch = b.take()
    b.flush(batch)
    return batch


class _Owner(threading.Thread):
    """The owner as service.py has it, for the tests that need the flush
    on another thread than the submits: sleeps until woken, then takes
    and flushes until nothing waits."""

    def __init__(self, engine, **kw):
        super().__init__(daemon=True, name="owner")
        self._wake, self._done = threading.Event(), False
        self.b = _mk(engine, wake=self._wake.set, **kw)
        self.start()

    def run(self):
        while not self._done:
            self._wake.wait()
            self._wake.clear()
            while _turn(self.b):
                pass

    def close(self):
        self._done = True
        self.b.close()                  # wakes the loop: its take fails
        self.join(5.0)                  # what was held over, then it ends


def _held_async():
    """``run_batch_async`` whose futures the test resolves by hand."""
    sent: list[tuple] = []

    def run_async(rows):
        sent.append((Future(), np.array(rows, copy=True)))
        return sent[-1][0]

    return run_async, sent


def _rows(n, w=3):
    return [np.full((w,), float(i), np.float32) for i in range(n)]


def test_pad_unpad_identity_matches_per_sample_results():
    eng = _FakeEngine()
    b = _mk(eng)
    futs = [b.submit(r) for r in _rows(3)]
    _turn(b)
    batched = np.stack([f.result(timeout=0) for f in futs])
    assert np.array_equal(batched, np.stack(_rows(3)) * 2.0)
    # the engine really saw ONE padded bucket, zeros in the pad slots
    (batch,) = eng.batches
    assert batch.shape == (4, 3)
    assert np.array_equal(batch[3], np.zeros((3,)))
    b.close()


@pytest.mark.parametrize("n,bucket", [(1, 4), (4, 4), (5, 8), (8, 8)])
def test_bucket_selection_at_boundaries(n, bucket):
    eng = _FakeEngine()
    b = _mk(eng)
    futs = [b.submit(r) for r in _rows(n)]
    _turn(b)
    for f in futs:
        f.result(timeout=0)
    assert len(eng.batches) == 1, "expected one flush for the burst"
    assert eng.batches[0].shape == (bucket, 3)
    b.close()


def test_expired_deadline_is_an_error_not_a_silent_drop():
    eng = _FakeEngine()
    b = _mk(eng)
    fut = b.submit(np.ones((3,), np.float32), timeout_ms=_AGED_MS)
    assert _turn(b) == []
    with pytest.raises(DeadlineExpired):
        fut.result(timeout=0)
    assert b.stats()["deadline_expired"] == 1
    assert eng.batches == []              # never reached the engine
    b.close()


def test_live_requests_survive_a_neighbors_expiry():
    eng = _FakeEngine()
    b = _mk(eng)
    doomed = b.submit(np.zeros((3,), np.float32), timeout_ms=_AGED_MS)
    alive = b.submit(np.ones((3,), np.float32))     # no deadline
    _turn(b)
    with pytest.raises(DeadlineExpired):
        doomed.result(timeout=0)
    assert np.array_equal(alive.result(timeout=0), np.full((3,), 2.0))
    b.close()


def test_a_request_that_ages_between_take_and_flush_is_failed_by_the_flush():
    b = _mk(_FakeEngine())
    fut = b.submit(np.ones((3,), np.float32), timeout_ms=30.0)
    batch = b.take()
    assert len(batch) == 1
    batch[0].deadline = time.monotonic() - 1.0      # it aged on the way
    b.flush(batch)
    with pytest.raises(DeadlineExpired):
        fut.result(timeout=0)
    b.close()


def test_mixed_shape_batch_fails_the_batch_not_the_owner():
    """A malformed payload mix (np.concatenate of unequal row shapes
    raises BEFORE run_batch) must fail that batch's futures and return
    to the owner — an exception out of ``flush`` would kill the thread
    that every later request waits for."""
    eng = _FakeEngine()
    b = _mk(eng)
    f1 = b.submit(np.ones((3,), np.float32))
    f2 = b.submit(np.ones((4,), np.float32))      # width mismatch
    _turn(b)
    for f in (f1, f2):
        with pytest.raises(ValueError):
            f.result(timeout=0)
    assert b.stats()["batch_errors"] == 1
    # a well-formed request still gets served
    ok = b.submit(np.ones((3,), np.float32))
    _turn(b)
    assert np.array_equal(ok.result(timeout=0), np.full((3,), 2.0))
    b.close()


def test_batch_failure_propagates_to_every_caller():
    b = _mk(_FakeEngine(fail=True))
    futs = [b.submit(r) for r in _rows(2)]
    _turn(b)
    for f in futs:
        with pytest.raises(ValueError, match="injected batch failure"):
            f.result(timeout=0)
    assert b.stats()["batch_errors"] == 1
    b.close()


def test_default_timeout_applies_when_submit_passes_none():
    b = _mk(_FakeEngine(), default_timeout_ms=_AGED_MS)
    fut = b.submit(np.ones((3,), np.float32))
    _turn(b)
    with pytest.raises(DeadlineExpired):
        fut.result(timeout=0)
    b.close()


def test_explicit_zero_timeout_disables_the_default_deadline():
    # a request that kept the default would have expired by the owner's
    # turn; timeout_ms=0 opts out and gets served
    b = _mk(_FakeEngine(), default_timeout_ms=_AGED_MS)
    fut = b.submit(np.ones((3,), np.float32), timeout_ms=0)
    _turn(b)
    assert np.array_equal(fut.result(timeout=0), np.full((3,), 2.0))
    b.close()


def test_submit_after_close_raises():
    b = _mk(_FakeEngine())
    b.close()
    with pytest.raises(RuntimeError, match="closed"):
        b.submit(np.ones((3,), np.float32))


def test_stats_shape():
    eng = _FakeEngine()
    b = _mk(eng)
    b.submit(np.ones((3,), np.float32))
    _turn(b)
    s = b.stats()
    assert s["requests"] == 1 and s["flushes"] == 1
    assert s["deadline_expired"] == 0 and s["batch_errors"] == 0
    assert s["occupancy"]["4"]["mean_fill"] == pytest.approx(0.25)
    b.close()


def test_stats_readers_race_flushes_with_exact_final_occupancy():
    """ISSUE 7 regression: the flushing thread's per-bucket children
    lookup ran OUTSIDE the children lock while stats() iterated under it
    (graftlint GL010) — hammer stats() from readers during a stream of
    flushes; final occupancy totals must be exact."""
    owner = _Owner(_FakeEngine())
    b = owner.b
    stop = threading.Event()
    errors = []

    def reader():
        try:
            while not stop.is_set():
                s = b.stats()
                # mid-race sanity only: counters are monotonic and the
                # occupancy dict never tears (exactness is pinned on
                # the quiesced state below; the flushes/rows PAIR is
                # deliberately not atomic across two counters)
                assert s["requests"] >= s["flushes"] >= 0
                for occ in s["occupancy"].values():
                    assert occ["rows"] >= 0 and occ["flushes"] >= 0
        except Exception as exc:  # pragma: no cover - surfaced below
            errors.append(exc)

    readers = [threading.Thread(target=reader) for _ in range(3)]
    for t in readers:
        t.start()
    n = 40
    futs = [b.submit(np.full((3,), float(i), np.float32)) for i in range(n)]
    for f in futs:
        f.result(timeout=10)
    stop.set()
    for t in readers:
        t.join(timeout=10)
    owner.close()
    assert not errors, errors
    s = b.stats()
    assert s["requests"] == n
    assert sum(occ["rows"] for occ in s["occupancy"].values()) == n
    assert sum(occ["flushes"]
               for occ in s["occupancy"].values()) == s["flushes"]


def test_arrivals_gather_into_bucket_slots_while_the_owner_is_away():
    """While the owner runs one batch, arrivals wait: its next take hands
    all of them out as ONE batch — occupancy rises exactly when the
    device is the bottleneck."""
    eng = _FakeEngine()
    b = _mk(eng)                                     # max_batch 8
    futs = [b.submit(np.full((3,), 0.0, np.float32))]
    first = b.take()                 # the owner turns to its 1-row batch
    futs += [b.submit(np.full((3,), float(i), np.float32))
             for i in range(1, 7)]   # ... and is away on the device
    b.flush(first)
    _turn(b)
    for f in futs:
        f.result(timeout=0)
    b.close()
    sizes = [batch.shape[0] for batch in eng.batches]
    assert sizes == [4, 8], (
        f"expected the 6 arrivals to coalesce: {sizes}")


# ---- lanes: asynchronous flushes in flight (ISSUE 30) -----------------------

def test_a_lane_bound_take_hands_out_nothing_and_a_completion_wakes_the_owner():
    """One lane, its batch in flight: what arrives stays queued (and
    counts in ``depth()``), a request that ages meanwhile is failed at
    the take after the completion — which wakes the owner."""
    run_async, sent = _held_async()
    b, woken = _woken(_FakeEngine(), lanes=1, run_batch_async=run_async)
    blocker = b.submit(np.ones((3,), np.float32))
    _turn(b)                                        # occupies the lane
    assert len(sent) == 1 and not blocker.done()
    doomed = b.submit(np.zeros((3,), np.float32), timeout_ms=_AGED_MS)
    live = b.submit(np.ones((3,), np.float32))
    assert b.take() == [] and b.depth() == 2        # lane-bound
    assert not doomed.done() and len(sent) == 1
    n = len(woken)
    sent[0][0].set_result(sent[0][1] * 2.0)         # the pool's worker
    assert len(woken) == n + 1                      # a lane is free
    assert np.array_equal(blocker.result(timeout=0), np.full((3,), 2.0))
    batch = b.take()
    assert [r.future for r in batch] == [live]
    with pytest.raises(DeadlineExpired):
        doomed.result(timeout=0)
    assert b.stats()["deadline_expired"] == 1
    b.flush(batch)
    sent[1][0].set_result(sent[1][1] * 2.0)
    assert np.array_equal(live.result(timeout=0), np.full((3,), 2.0))
    b.close()


def test_lanes_bound_the_asynchronous_batches_in_flight():
    """Two lanes: the third batch goes out only when a completion has
    freed one, and every batch still resolves."""
    run_async, sent = _held_async()
    b, woken = _woken(_FakeEngine(), max_batch=4, lanes=2,
                      run_batch_async=run_async)
    futs = [b.submit(r) for r in _rows(12)]         # three batches' worth

    def unresolved():
        return sum(not f.done() for f, _ in sent)

    assert len(_turn(b)) == 4 and len(_turn(b)) == 4
    assert unresolved() == 2
    assert b.take() == [] and b.depth() == 4        # both lanes busy
    fut, rows = sent[0]
    fut.set_result(rows * 2.0)
    assert len(_turn(b)) == 4 and unresolved() == 2  # ... and busy again
    assert b.take() == []
    for fut, rows in sent[1:]:
        fut.set_result(rows * 2.0)
    for i, f in enumerate(futs):
        assert np.array_equal(f.result(timeout=0), np.full((3,), 2.0 * i))
    assert b.stats()["flushes"] == 3 and len(sent) == 3
    b.close()


def test_a_failed_submit_takes_no_lane():
    def refuse(rows):
        raise RuntimeError("every replica queue is full")

    b = _mk(_FakeEngine(), lanes=1, run_batch_async=refuse)
    first = b.submit(np.ones((3,), np.float32))
    _turn(b)
    with pytest.raises(RuntimeError, match="queue is full"):
        first.result(timeout=0)
    b.submit(np.ones((3,), np.float32))
    assert len(b.take()) == 1           # the lane was never taken
    b.close()


def test_injected_recorder_receives_flush_spans():
    # an owner that isolates its span stream (recorder=...) must get the
    # flush spans there — not on the process-default recorder, which a
    # co-resident train run can swap out via spans.install()
    rec = obs_spans.SpanRecorder()
    b = _mk(_FakeEngine(), recorder=rec)
    b.submit(np.ones((3,), np.float32))
    _turn(b)
    b.close()
    spans = [r for r in rec.tail() if r.get("name") == "batcher.flush"]
    assert len(spans) == 1 and spans[0]["rows"] == 1


def test_on_flush_observer_sees_duration_and_rows():
    """ISSUE 9: the flush-latency observer (the service's EWMA spike
    detector feed) fires once per successful flush with (dur_ms, rows)
    — and never for a failed batch."""
    seen = []
    eng = _FakeEngine(delay_s=0.02)
    b = _mk(eng, on_flush=lambda dur_ms, rows: seen.append((dur_ms, rows)))
    for r in _rows(3):
        b.submit(r)
    _turn(b)
    b.close()
    assert len(seen) == 1
    dur_ms, rows = seen[0]
    assert rows == 3 and dur_ms >= 20.0 - 1.0   # the engine's delay

    seen.clear()
    bad = _mk(_FakeEngine(fail=True),
              on_flush=lambda dur_ms, rows: seen.append((dur_ms, rows)))
    fut = bad.submit(np.ones((3,), np.float32))
    _turn(bad)
    with pytest.raises(ValueError, match="injected"):
        fut.result(timeout=0)
    bad.close()
    assert seen == []


def test_deadline_expired_hints_the_last_flushs_duration():
    """``retry_after_ms``: how long the batcher's most recent flush took
    (0 before the first) — not a window that does not exist."""
    seen = []
    b = _mk(_FakeEngine(delay_s=0.01),
            on_flush=lambda dur_ms, rows: seen.append(dur_ms))
    early = b.submit(np.ones((3,), np.float32), timeout_ms=_AGED_MS)
    _turn(b)
    assert early.exception(timeout=0).retry_after_ms == 0.0
    b.submit(np.ones((3,), np.float32))
    _turn(b)
    late = b.submit(np.ones((3,), np.float32), timeout_ms=_AGED_MS)
    _turn(b)
    (dur_ms,) = seen
    assert dur_ms >= 9.0 and late.exception(timeout=0).retry_after_ms == dur_ms
    b.close()


def test_two_ladders_share_one_registry():
    """ISSUE 30: the occupancy histogram's edges came from the engine's
    ladder, so a second service with another ladder could not be built
    on the process-wide registry."""
    reg = obs_metrics.MetricsRegistry()
    small = DynamicBatcher(_FakeEngine(), lambda n: 8, max_batch=8,
                           wake=lambda: None, name="small", registry=reg)
    tall = DynamicBatcher(_FakeEngine(), lambda n: 16 if n > 8 else 8,
                          max_batch=16, wake=lambda: None, name="tall",
                          registry=reg)
    for b, n in ((small, 3), (tall, 11)):
        for r in _rows(n):
            b.submit(r)
        _turn(b)
    assert small.stats()["occupancy"] == {
        "8": {"flushes": 1, "rows": 3, "mean_fill": 3 / 8}}
    assert tall.stats()["occupancy"] == {
        "16": {"flushes": 1, "rows": 11, "mean_fill": 11 / 16}}
    (fam,) = [f for f in reg.collect()
              if f.name == "milnce_serve_batch_occupancy"]
    counts = {labels[0]: child.count for labels, child in fam.items()}
    assert counts == {"small": 1, "tall": 1}
    small.close(), tall.close()


# ---- blocks, and what a batch returns (ISSUE 27: the scan coalescer) --------

def _block(first, n, w=3):
    return np.stack([np.full((w,), float(first + i), np.float32)
                     for i in range(n)])


def test_block_resolves_to_its_rows_in_order_beside_lone_rows():
    eng = _FakeEngine()
    b = _mk(eng)
    head = b.submit(np.full((3,), 50.0, np.float32))
    first = b.take()                    # head's batch is on its way
    blk = b.submit_block(_block(0, 3))
    lone = b.submit(np.full((3,), 7.0, np.float32))
    b.flush(first)
    _turn(b)
    assert np.array_equal(blk.result(timeout=0), _block(0, 3) * 2.0)
    assert np.array_equal(lone.result(timeout=0), np.full((3,), 14.0))
    assert np.array_equal(head.result(timeout=0), np.full((3,), 100.0))
    assert b.stats()["requests"] == 5   # rows, not requests
    b.close()


def test_a_block_is_never_split_over_two_batches():
    """1 + 5 + 5 + 1 rows against a top bucket of 8: the second block is
    held over whole and leads the next batch."""
    eng = _FakeEngine()
    b = _mk(eng)
    head = b.submit(np.zeros((3,), np.float32))
    blocks = [b.submit_block(_block(10 * (i + 1), 5)) for i in range(2)]
    tail = b.submit(np.ones((3,), np.float32))
    while _turn(b):
        pass
    for i, f in enumerate(blocks):
        assert np.array_equal(f.result(timeout=0),
                              _block(10 * (i + 1), 5) * 2.0)
    head.result(timeout=0), tail.result(timeout=0)
    b.close()
    live = [int((batch.sum(axis=1) != 0).sum()) for batch in eng.batches]
    assert live == [5, 6]               # head is a row of zeros
    # the row sums of every block sit in ONE batch
    for i in range(2):
        want = set((_block(10 * (i + 1), 5) * 1.0).sum(axis=1).tolist())
        assert sum(want <= set(batch.sum(axis=1).tolist())
                   for batch in eng.batches) == 1


def test_a_block_past_the_top_bucket_fails_alone():
    eng = _FakeEngine()
    b = _mk(eng)
    ok = b.submit(np.ones((3,), np.float32))
    too_big = b.submit_block(_block(0, 9))
    after = b.submit(np.ones((3,), np.float32))
    while _turn(b):
        pass
    with pytest.raises(ValueError):
        too_big.result(timeout=0)
    assert np.array_equal(ok.result(timeout=0), np.full((3,), 2.0))
    assert np.array_equal(after.result(timeout=0), np.full((3,), 2.0))
    assert b.stats()["batch_errors"] == 1
    b.close()


def test_take_cuts_each_requests_share_out_of_what_the_batch_returned():
    """The batch returns two arrays and a stamp; a lone row gets its
    row of each, a block its rows, both the stamp."""
    seen = []

    def run(rows):
        seen.append(rows.shape[0])
        return rows * 2.0, rows.sum(axis=1).astype(np.int32), len(seen)

    b = _mk(run, pad=False, span_name="topk.flush",
            take=lambda out, at: (out[0][at], out[1][at], out[2]))
    blk = b.submit_block(_block(1, 3))
    _turn(b)
    doubled, total, stamp = blk.result(timeout=0)
    assert np.array_equal(doubled, _block(1, 3) * 2.0)
    assert total.tolist() == [3, 6, 9] and total.dtype == np.int32
    row = b.submit(np.full((3,), 4.0, np.float32))
    _turn(b)
    doubled, total, stamp2 = row.result(timeout=0)
    assert doubled.shape == (3,) and total == 12
    assert (stamp, stamp2) == (1, 2)
    assert seen == [3, 1]               # pad=False: the live rows alone
    b.close()


def test_span_name_and_unpadded_rows_on_the_flush_record():
    rec = obs_spans.SpanRecorder(ring=64)
    eng = _FakeEngine()
    b = _mk(eng, pad=False, span_name="topk.flush", recorder=rec,
            name="topk")
    b.submit_block(_block(0, 5))
    _turn(b)
    b.close()
    (flush,) = [r for r in rec.tail() if r["name"] == "topk.flush"]
    assert (flush["rows"], flush["bucket"], flush["batcher"]) == (5, 8,
                                                                  "topk")
    assert {"queue_wait_ms", "queue_wait_mean_ms", "dur_ms"} <= set(flush)
    assert not [r for r in rec.tail() if r["name"] == "batcher.flush"]
    assert eng.batches[0].shape[0] == 5


def test_close_fails_the_block_that_was_held_over_behind_a_busy_lane():
    """The one lane busy: a block held over and a block queued behind it
    both count in ``depth()`` and both are failed, never dropped, at
    close; the batch in flight still gets its result."""
    run_async, sent = _held_async()
    b = _mk(_FakeEngine(), lanes=1, run_batch_async=run_async)
    first = b.submit_block(_block(0, 5))
    held = b.submit_block(_block(5, 5))     # does not fit behind `first`
    _turn(b)                                # `first` is in flight
    queued = b.submit_block(_block(10, 5))
    assert b.take() == []                   # lane-bound
    assert b.depth() == 5 + 1               # the held block's rows + a request
    b.close()
    assert b.take() == []                   # the owner comes round
    for f in (held, queued):
        with pytest.raises(RuntimeError, match="closed"):
            f.result(timeout=0)
    fut, rows = sent[0]
    fut.set_result(rows * 2.0)
    assert np.array_equal(first.result(timeout=0), _block(0, 5) * 2.0)


# ---- the owner's thread takes and flushes (ISSUE 29) -------------------------

def test_it_starts_no_thread_and_wakes_its_owner_at_every_submit():
    eng = _FakeEngine()
    before = set(threading.enumerate())
    b, woken = _woken(eng)
    futs = [b.submit(r) for r in _rows(3)]
    assert len(woken) == 3 and not eng.batches     # nobody flushes for it
    assert set(threading.enumerate()) == before
    assert b.depth() == 3
    batch = b.take()                    # every row that waits now
    assert len(batch) == 3 and b.take() == [] and b.depth() == 0
    b.flush(batch, chained_rows=2)
    for i, f in enumerate(futs):
        assert np.array_equal(f.result(timeout=0), np.full((3,), 2.0 * i))
    assert eng.batches[0].shape == (4, 3)           # padded to its bucket
    assert b.stats()["flushes"] == 1
    b.close()


def test_take_hands_out_blocks_whole_up_to_the_top_bucket():
    b = _mk(_FakeEngine())
    first = b.submit_block(_block(0, 5))
    held = b.submit_block(_block(5, 5))             # 5 + 5 > 8: held over
    tail = b.submit(np.ones((3,), np.float32))
    one = b.take()
    assert [r.rows for r in one] == [5] and b.depth() == 6
    two = b.take()                                  # it leads the next
    assert [r.rows for r in two] == [5, 1] and b.depth() == 0
    b.flush(one), b.flush(two)
    assert np.array_equal(held.result(timeout=0), _block(5, 5) * 2.0)
    first.result(timeout=0), tail.result(timeout=0)
    b.close()


def test_take_fails_what_expired_while_the_owner_was_away():
    b = _mk(_FakeEngine())
    late = b.submit(np.ones((3,), np.float32), timeout_ms=_AGED_MS)
    live = b.submit(np.ones((3,), np.float32))
    batch = b.take()                    # the owner is back from the device
    assert len(batch) == 1
    with pytest.raises(DeadlineExpired):
        late.result(timeout=0)
    b.flush(batch)
    assert live.result(timeout=0) is not None
    assert b.stats()["deadline_expired"] == 1
    b.close()


def test_flush_record_carries_the_owners_attributes():
    rec = obs_spans.SpanRecorder(ring=64)
    b = _mk(_FakeEngine(), recorder=rec, span_name="topk.flush", name="topk")
    b.submit(np.ones((3,), np.float32))
    b.flush(b.take(), chained_rows=1)
    b.submit(np.ones((3,), np.float32))
    b.flush(b.take())
    first, second = [r for r in rec.tail() if r["name"] == "topk.flush"]
    assert first["chained_rows"] == 1 and "chained_rows" not in second
    b.close()


def test_a_callers_own_future_is_resolved_on_the_flushing_thread():
    """``future=``: callbacks added before the submit run where the row
    is flushed — never on the submitting thread, however early the flush."""
    ran_on = []
    owner = _Owner(_FakeEngine())
    mine: Future = Future()
    mine.add_done_callback(
        lambda f: ran_on.append(threading.current_thread().name))
    assert owner.b.submit(np.ones((3,), np.float32), future=mine) is mine
    assert np.array_equal(mine.result(timeout=5), np.full((3,), 2.0))
    owner.close()
    assert ran_on == ["owner"]


def test_close_fails_the_queue_and_the_next_take_the_held_block():
    b, woken = _woken(_FakeEngine())
    b.submit_block(_block(0, 5))
    held = b.submit_block(_block(5, 5))
    b.take()                            # the first leaves; the second is held
    queued = b.submit(np.ones((3,), np.float32))
    n = len(woken)
    b.close()
    assert len(woken) == n + 1          # the owner is told
    with pytest.raises(RuntimeError, match="closed"):
        queued.result(timeout=0)
    assert not held.done()              # the owner's to fail ...
    assert b.take() == []               # ... when it comes round
    with pytest.raises(RuntimeError, match="closed"):
        held.result(timeout=0)
    with pytest.raises(RuntimeError, match="closed"):
        b.submit(np.ones((3,), np.float32))


# ---- the owner's turn, phase by phase (ISSUE 37) -----------------------------

_PHASES = ("sleep", "take", "prepare", "run", "scatter", "account")
# a kernel that accounts CPU time by the tick (the chip's host: 10 ms)
# reads a thread's CPU up to one tick above its wall time
_TICK_MS = 10.5


def _turns(rec):
    return [r for r in rec.tail() if r["name"] == "worker.turn"]


def test_a_flush_is_one_turn_of_six_phases_that_sum_to_its_length():
    rec = obs_spans.SpanRecorder()
    b = _mk(_FakeEngine(delay_s=0.01), recorder=rec, name="text")
    for r in _rows(3):
        b.submit(r)
    b.flush(b.take(), epoch=7)
    b.close()
    (turn,) = _turns(rec)
    assert turn["kind"] == "event"
    assert (turn["batcher"], turn["rows"], turn["bucket"], turn["epoch"]) \
        == ("text", 3, 4, 7)
    assert {p + "_ms" for p in _PHASES} <= set(turn)
    assert {p + "_cpu_ms" for p in _PHASES if p != "sleep"} <= set(turn)
    assert "sleep_cpu_ms" not in turn           # a wait: its CPU is not read
    assert sum(turn[p + "_ms"] for p in _PHASES) == pytest.approx(
        turn["dur_ms"], abs=1e-3)
    assert turn["run_ms"] >= 10.0 > turn["run_cpu_ms"]      # it slept there
    # the flush record is timed by the turn's own readings, and joins it
    (flush,) = [r for r in rec.tail() if r["name"] == "batcher.flush"]
    assert flush["kind"] == "span" and flush["dur_ms"] == turn["run_ms"]
    assert flush["epoch"] == turn["epoch"] and flush["mono"] < turn["mono"]


def test_consecutive_turns_leave_none_of_the_owners_time_uncovered(
        monkeypatch):
    """On a clock that moves 1 ms at every reading: from one turn's end
    to the next one's lies exactly the next one's ``dur_ms`` — its
    ``take`` begins where the last ``account`` ended, whatever the owner
    does in between."""
    clock = [50.0]

    def tick():
        clock[0] += 0.001
        return clock[0]

    monkeypatch.setattr(obs_spans, "_now", tick)
    rec = obs_spans.SpanRecorder()
    b = _mk(_FakeEngine(), recorder=rec)
    for i in range(3):
        b.submit(_rows(1)[0])
        batch = b.take()
        for _ in range(i):
            assert b.take() == []               # an idle take or two
        b.flush(batch)
    b.close()
    first, second, third = _turns(rec)
    # ``mono`` is stamped one reading after the turn's last (the same
    # offset on every record)
    for before, turn in ((first, second), (second, third)):
        assert turn["mono"] - before["mono"] == pytest.approx(
            turn["dur_ms"] / 1e3, abs=1e-6)
        assert turn["take_ms"] > 0 and turn["sleep_ms"] == 0


def test_a_scatter_blocked_on_a_lock_shows_wall_far_above_cpu():
    """A done-callback that waits for a lock held elsewhere: the worker
    stands in ``scatter`` without running."""
    rec = obs_spans.SpanRecorder()
    b = _mk(_FakeEngine(), recorder=rec)
    gate = threading.Lock()
    gate.acquire()
    fut = Future()
    fut.add_done_callback(lambda f: (gate.acquire(), gate.release()))
    b.submit(_rows(1)[0], future=fut)
    threading.Timer(0.08, gate.release).start()
    b.flush(b.take())
    b.close()
    (turn,) = _turns(rec)
    assert turn["scatter_ms"] >= 60.0
    assert turn["scatter_cpu_ms"] <= turn["scatter_ms"] / 4
    for p in _PHASES[1:]:
        assert turn[p + "_cpu_ms"] <= turn[p + "_ms"] + _TICK_MS, p


def test_a_failed_batch_is_a_turn_too_and_the_flush_record_names_the_error():
    rec = obs_spans.SpanRecorder()
    b = _mk(_FakeEngine(fail=True), recorder=rec)
    fut = b.submit(_rows(1)[0])
    b.flush(b.take(), epoch=3)
    b.close()
    with pytest.raises(ValueError, match="injected"):
        fut.result(timeout=0)
    (turn,) = _turns(rec)
    (flush,) = [r for r in rec.tail() if r["name"] == "batcher.flush"]
    assert flush["error"] == "ValueError" and flush["epoch"] == 3
    assert turn["epoch"] == 3 and turn["dur_ms"] > 0


def test_a_pooled_flush_ends_the_turn_at_the_submit_and_the_completion_rides_its_event():
    run_async, sent = _held_async()
    rec = obs_spans.SpanRecorder()
    b = _mk(_FakeEngine(), lanes=1, run_batch_async=run_async, recorder=rec)
    futs = [b.submit(r) for r in _rows(2)]
    b.flush(b.take(), epoch=1)
    (turn,) = _turns(rec)               # written at the submit
    assert turn["scatter_ms"] == 0 and turn["account_ms"] == 0
    assert turn["run_ms"] > 0 and turn["epoch"] == 1
    assert not [r for r in rec.tail() if r["name"] == "batcher.flush"]
    fut, rows = sent[0]
    fut.set_result(rows * 2.0)          # the "pool's worker": this thread
    assert all(f.done() for f in futs)
    (flush,) = [r for r in rec.tail() if r["name"] == "batcher.flush"]
    assert flush["kind"] == "event" and flush["epoch"] == 1
    for p in ("scatter", "account"):
        assert 0 <= flush[p + "_cpu_ms"] <= flush[p + "_ms"] + _TICK_MS
        assert flush[p + "_ms"] <= flush["dur_ms"]
    assert flush["scatter_ms"] > 0
    assert len(_turns(rec)) == 1        # the completion is no turn
    b.close()


# ---- a held scatter --------------------------------------------------------------

def test_a_held_flush_scatters_inside_the_next_run_and_keeps_its_turn(
        monkeypatch):
    """``flush(hold=True)``: the batch runs, nothing is answered, no
    record is written; called inside the next flush's executor, it
    answers, and its turn gets that scatter out of the other's ``run``.
    On a clock that moves 1 ms a reading the split is exact."""
    clock = [50.0]

    def tick():
        clock[0] += 0.001
        return clock[0]

    monkeypatch.setattr(obs_spans, "_now", tick)
    rec = obs_spans.SpanRecorder()
    scans = _mk(_FakeEngine(), recorder=rec, name="topk",
                span_name="topk.flush")
    carried = []

    class Carrier(_FakeEngine):
        def __call__(self, rows):
            out = super().__call__(rows)
            carried.pop()("engine.text")    # what round_trip does
            return out

    text = _mk(Carrier(), recorder=rec, name="text", turns=scans.turns)
    fut = scans.submit(_rows(1)[0])
    held = scans.flush(scans.take(), hold=True, epoch=1)
    assert (held.rows, held.bucket) == (1, 4) and not fut.done()
    assert not rec.tail()                   # no turn, no flush record yet
    carried.append(held)
    other = text.submit(_rows(2)[1])
    assert text.flush(text.take(), epoch=2) is None
    np.testing.assert_array_equal(fut.result(timeout=0), _rows(1)[0] * 2)
    assert other.done()
    (scan,) = [r for r in rec.tail() if r["name"] == "topk.flush"]
    (flush,) = [r for r in rec.tail() if r["name"] == "batcher.flush"]
    assert scan["rode"] == "text" and scan["epoch"] == 1
    assert "rode" not in flush
    mine, its = _turns(rec)                 # the held turn is written first
    assert (mine["batcher"], its["batcher"]) == ("topk", "text")
    assert mine["scatter_ms"] > 0 and scan["dur_ms"] == mine["run_ms"]
    # the text flush's record keeps the whole run; its turn gives the
    # carried scatter and account to the turn they belong to
    assert flush["dur_ms"] == pytest.approx(
        its["run_ms"] + mine["scatter_ms"] + mine["account_ms"], abs=1e-3)
    for turn in (mine, its):
        assert sum(turn[p + "_ms"] for p in _PHASES) == pytest.approx(
            turn["dur_ms"], abs=1e-3)
    scans.close()
    text.close()


def test_a_held_flush_run_at_once_rode_nothing():
    rec = obs_spans.SpanRecorder()
    b = _mk(_FakeEngine(), recorder=rec, name="topk", span_name="topk.flush")
    futs = [b.submit(r) for r in _rows(3)]
    held = b.flush(b.take(), hold=True, epoch=4)
    held("")
    assert all(f.done() for f in futs)
    (scan,) = [r for r in rec.tail() if r["name"] == "topk.flush"]
    (turn,) = _turns(rec)
    assert scan["rode"] == "none" and (scan["rows"], turn["rows"]) == (3, 3)
    assert turn["epoch"] == 4 and turn["scatter_ms"] > 0
    assert b.stats()["flushes"] == 1
    b.close()


def test_a_held_flush_that_fails_is_scattered_at_once():
    """Nothing to hold: its callers see the error there and then."""
    rec = obs_spans.SpanRecorder()
    b = _mk(_FakeEngine(fail=True), recorder=rec, name="topk",
            span_name="topk.flush")
    fut = b.submit(_rows(1)[0])
    assert b.flush(b.take(), hold=True, epoch=2) is None
    with pytest.raises(ValueError, match="injected"):
        fut.result(timeout=0)
    (scan,) = [r for r in rec.tail() if r["name"] == "topk.flush"]
    assert scan["error"] == "ValueError" and scan["rode"] == "none"
    assert len(_turns(rec)) == 1
    b.close()
