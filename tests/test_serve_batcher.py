"""Dynamic micro-batcher edge semantics (ISSUE 4 satellite): flush on
size and on delay, pad/unpad identity, bucket selection at boundaries,
deadline-expired -> error (never a silent drop), batch-failure
propagation.  jax-free by construction — the batcher is numpy-only and
these tests pin that boundary too (a fake run_batch stands in for the
engine)."""

import threading
import time

import numpy as np
import pytest

from milnce_tpu.serving.batcher import DeadlineExpired, DynamicBatcher

_BUCKETS = (4, 8)


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
    return DynamicBatcher(engine, _bucket_for, **kw)


def _rows(n, w=3):
    return [np.full((w,), float(i), np.float32) for i in range(n)]


def test_flush_on_max_batch_does_not_wait_for_delay():
    eng = _FakeEngine()
    b = _mk(eng, max_batch=4, max_delay_ms=10_000)   # delay flush never fires
    t0 = time.monotonic()
    futs = [b.submit(r) for r in _rows(4)]
    out = [f.result(timeout=5) for f in futs]
    assert time.monotonic() - t0 < 5.0               # well under the 10s delay
    assert len(eng.batches) == 1 and eng.batches[0].shape == (4, 3)
    for i, row in enumerate(out):
        assert np.array_equal(row, np.full((3,), 2.0 * i))
    occ = b.stats()["occupancy"]["4"]
    assert occ == {"flushes": 1, "rows": 4, "mean_fill": 1.0}
    b.close()


def test_flush_on_delay_serves_a_lone_request():
    eng = _FakeEngine()
    b = _mk(eng, max_delay_ms=40)
    t0 = time.monotonic()
    row = b.submit(np.ones((3,), np.float32)).result(timeout=5)
    waited = time.monotonic() - t0
    assert np.array_equal(row, np.full((3,), 2.0))
    assert waited >= 0.03                 # did wait for company...
    assert eng.batches[0].shape == (4, 3)  # ...then padded to the floor bucket
    b.close()


def test_pad_unpad_identity_matches_per_sample_results():
    eng = _FakeEngine()
    b = _mk(eng, max_delay_ms=30)
    futs = [b.submit(r) for r in _rows(3)]
    batched = np.stack([f.result(timeout=5) for f in futs])
    assert np.array_equal(batched, np.stack(_rows(3)) * 2.0)
    # the engine really saw ONE padded bucket, zeros in the pad slots
    (batch,) = eng.batches
    assert batch.shape == (4, 3)
    assert np.array_equal(batch[3], np.zeros((3,)))
    b.close()


@pytest.mark.parametrize("n,bucket", [(1, 4), (4, 4), (5, 8), (8, 8)])
def test_bucket_selection_at_boundaries(n, bucket):
    eng = _FakeEngine()
    b = _mk(eng, max_delay_ms=150)        # plenty to collect all n submits
    futs = [b.submit(r) for r in _rows(n)]
    for f in futs:
        f.result(timeout=5)
    assert len(eng.batches) == 1, "expected one flush for the burst"
    assert eng.batches[0].shape == (bucket, 3)
    b.close()


def test_expired_deadline_is_an_error_not_a_silent_drop():
    eng = _FakeEngine()
    b = _mk(eng, max_delay_ms=10_000)     # only the deadline can end the wait
    fut = b.submit(np.ones((3,), np.float32), timeout_ms=40)
    with pytest.raises(DeadlineExpired):
        fut.result(timeout=5)             # resolves promptly, NOT after 10s
    assert b.stats()["deadline_expired"] == 1
    assert eng.batches == []              # never reached the engine
    b.close()


def test_live_requests_survive_a_neighbors_expiry():
    eng = _FakeEngine()
    b = _mk(eng, max_delay_ms=10_000)
    doomed = b.submit(np.zeros((3,), np.float32), timeout_ms=40)
    alive = b.submit(np.ones((3,), np.float32))     # no deadline
    with pytest.raises(DeadlineExpired):
        doomed.result(timeout=5)
    assert np.array_equal(alive.result(timeout=5), np.full((3,), 2.0))
    b.close()


def test_mixed_shape_batch_fails_the_batch_not_the_worker():
    """A malformed payload mix (np.stack of unequal row shapes raises
    BEFORE run_batch) must fail that batch's futures and leave the
    worker alive — a dead worker would strand every later request."""
    eng = _FakeEngine()
    b = _mk(eng, max_delay_ms=60)
    f1 = b.submit(np.ones((3,), np.float32))
    f2 = b.submit(np.ones((4,), np.float32))      # width mismatch
    for f in (f1, f2):
        with pytest.raises(ValueError):
            f.result(timeout=5)
    assert b.stats()["batch_errors"] == 1
    # the worker survived: a well-formed request still gets served
    ok = b.submit(np.ones((3,), np.float32)).result(timeout=5)
    assert np.array_equal(ok, np.full((3,), 2.0))
    b.close()


def test_batch_failure_propagates_to_every_caller():
    b = _mk(_FakeEngine(fail=True), max_delay_ms=20)
    futs = [b.submit(r) for r in _rows(2)]
    for f in futs:
        with pytest.raises(ValueError, match="injected batch failure"):
            f.result(timeout=5)
    assert b.stats()["batch_errors"] == 1
    b.close()


def test_default_timeout_applies_when_submit_passes_none():
    b = _mk(_FakeEngine(), max_delay_ms=10_000, default_timeout_ms=40)
    with pytest.raises(DeadlineExpired):
        b.submit(np.ones((3,), np.float32)).result(timeout=5)
    b.close()


def test_explicit_zero_timeout_disables_the_default_deadline():
    # default deadline (20ms) < delay flush (60ms): a request that kept
    # the default would expire; timeout_ms=0 opts out and gets served
    b = _mk(_FakeEngine(), max_delay_ms=60, default_timeout_ms=20)
    fut = b.submit(np.ones((3,), np.float32), timeout_ms=0)
    assert np.array_equal(fut.result(timeout=5), np.full((3,), 2.0))
    b.close()


def test_submit_after_close_raises():
    b = _mk(_FakeEngine())
    b.close()
    with pytest.raises(RuntimeError, match="closed"):
        b.submit(np.ones((3,), np.float32))


def test_stats_shape():
    eng = _FakeEngine()
    b = _mk(eng, max_delay_ms=20)
    b.submit(np.ones((3,), np.float32)).result(timeout=5)
    s = b.stats()
    assert s["requests"] == 1 and s["flushes"] == 1
    assert s["deadline_expired"] == 0 and s["batch_errors"] == 0
    assert s["occupancy"]["4"]["mean_fill"] == pytest.approx(0.25)
    b.close()

def test_stats_readers_race_flushes_with_exact_final_occupancy():
    """ISSUE 7 regression: the worker's per-bucket children lookup ran
    OUTSIDE the children lock while stats() iterated under it
    (graftlint GL010) — hammer stats() from readers during a stream of
    flushes; final occupancy totals must be exact."""
    eng = _FakeEngine()
    b = _mk(eng, max_delay_ms=1)
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
    b.close()
    assert not errors, errors
    s = b.stats()
    assert s["requests"] == n
    assert sum(occ["rows"] for occ in s["occupancy"].values()) == n
    assert sum(occ["flushes"]
               for occ in s["occupancy"].values()) == s["flushes"]


def test_continuous_lone_request_skips_the_delay_wait():
    """Continuous batching (ISSUE 14): a lone request flushes the
    moment the lane is free — it never pays max_delay_ms waiting for
    company that isn't coming (the flush-and-wait path's cost)."""
    eng = _FakeEngine()
    b = _mk(eng, max_delay_ms=10_000, continuous=True)
    t0 = time.monotonic()
    row = b.submit(np.ones((3,), np.float32)).result(timeout=5)
    waited = time.monotonic() - t0
    assert np.array_equal(row, np.full((3,), 2.0))
    assert waited < 2.0, f"continuous mode waited {waited:.3f}s"
    b.close()


def test_continuous_accumulates_into_bucket_slots_while_lane_busy():
    """While the single lane executes, arrivals accumulate into the
    forming batch — occupancy rises exactly when the device is the
    bottleneck (the slot-reuse win over flush-and-wait)."""
    eng = _FakeEngine(delay_s=0.15)
    b = _mk(eng, continuous=True)                    # max_batch 8
    futs = [b.submit(np.full((3,), 0.0, np.float32))]
    time.sleep(0.03)                 # first flush (1 row) is in flight
    futs += [b.submit(np.full((3,), float(i), np.float32))
             for i in range(1, 7)]
    for f in futs:
        f.result(timeout=5)
    b.close()
    sizes = [batch.shape[0] for batch in eng.batches]
    assert sizes == [4, 8], (
        f"expected the 6 lane-busy arrivals to coalesce: {sizes}")


def test_continuous_deadline_expires_promptly_while_lane_busy():
    """Pipelined continuous mode: a request aging out while the worker
    is PARKED on a busy lane fails with DeadlineExpired at the
    lane-wait tick — it never waits for the lane to free first."""
    from concurrent.futures import Future

    slow: list[Future] = []

    def run_async(rows):
        fut: Future = Future()
        slow.append(fut)
        return fut                        # resolved manually, late

    b = _mk(_FakeEngine(), continuous=True, lanes=1,
            run_batch_async=run_async)
    blocker = b.submit(np.ones((3,), np.float32))     # occupies the lane
    deadline = time.monotonic() + 5.0
    while not slow and time.monotonic() < deadline:
        time.sleep(0.005)
    assert slow, "the blocker batch never dispatched"
    doomed = b.submit(np.zeros((3,), np.float32), timeout_ms=60)
    t0 = time.monotonic()
    with pytest.raises(DeadlineExpired):
        doomed.result(timeout=5)
    waited = time.monotonic() - t0
    assert waited < 0.4, (f"expiry took {waited:.3f}s — waited for the "
                          "lane instead of the deadline")
    slow[0].set_result(np.ones((4, 3), np.float32) * 2.0)
    assert np.array_equal(blocker.result(timeout=5), np.full((3,), 2.0))
    assert b.stats()["deadline_expired"] == 1
    b.close()


def test_continuous_async_lanes_bound_inflight_batches():
    """Pipelined continuous mode: at most ``lanes`` batches are ever in
    flight at once (the semaphore), and every batch still resolves."""
    from concurrent.futures import Future

    inflight = {"now": 0, "max": 0}
    lock = threading.Lock()
    pending: list[tuple] = []

    def run_async(rows):
        fut: Future = Future()
        with lock:
            inflight["now"] += 1
            inflight["max"] = max(inflight["max"], inflight["now"])
            pending.append((fut, np.array(rows, copy=True)))
        return fut

    def resolver():
        while not stop.is_set():
            with lock:
                item = pending.pop(0) if pending else None
            if item is None:
                time.sleep(0.005)
                continue
            time.sleep(0.05)                  # the "dispatch"
            fut, rows = item
            with lock:
                inflight["now"] -= 1
            fut.set_result(rows * 2.0)

    stop = threading.Event()
    t = threading.Thread(target=resolver, daemon=True)
    t.start()
    b = _mk(_FakeEngine(), continuous=True, lanes=2,
            run_batch_async=run_async)
    try:
        futs = []
        for burst in range(6):                # 6 bursts of 2 rows
            futs += [b.submit(np.full((3,), float(burst), np.float32))
                     for _ in range(2)]
            time.sleep(0.02)
        out = [f.result(timeout=10) for f in futs]
        assert all(o.shape == (3,) for o in out)
        assert inflight["max"] <= 2, (
            f"{inflight['max']} batches in flight > 2 lanes")
        assert inflight["max"] >= 2, "lanes never actually pipelined"
    finally:
        stop.set()
        b.close()
        t.join(timeout=5)


def test_injected_recorder_receives_flush_spans():
    # an owner that isolates its span stream (recorder=...) must get the
    # flush spans there — not on the process-default recorder, which a
    # co-resident train run can swap out via spans.install()
    from milnce_tpu.obs.spans import SpanRecorder

    rec = SpanRecorder()
    b = _mk(_FakeEngine(), max_delay_ms=20, recorder=rec)
    b.submit(np.ones((3,), np.float32)).result(timeout=5)
    b.close()
    spans = [r for r in rec.tail() if r.get("name") == "batcher.flush"]
    assert len(spans) == 1 and spans[0]["rows"] == 1


def test_on_flush_observer_sees_duration_and_rows():
    """ISSUE 9: the flush-latency observer (the service's EWMA spike
    detector feed) fires once per successful flush with (dur_ms, rows)
    — and never for a failed batch."""
    seen = []
    eng = _FakeEngine(delay_s=0.02)
    b = _mk(eng, max_delay_ms=10,
            on_flush=lambda dur_ms, rows: seen.append((dur_ms, rows)))
    futs = [b.submit(r) for r in _rows(3)]
    for f in futs:
        f.result(timeout=5)
    b.close()
    assert len(seen) == 1
    dur_ms, rows = seen[0]
    assert rows == 3 and dur_ms >= 20.0 - 1.0   # the engine's delay

    seen.clear()
    bad = _mk(_FakeEngine(fail=True), max_delay_ms=10,
              on_flush=lambda dur_ms, rows: seen.append((dur_ms, rows)))
    fut = bad.submit(np.ones((3,), np.float32))
    with pytest.raises(ValueError, match="injected"):
        fut.result(timeout=5)
    bad.close()
    assert seen == []


# ---- blocks, and what a batch returns (ISSUE 27: the scan coalescer) --------

def _block(first, n, w=3):
    return np.stack([np.full((w,), float(first + i), np.float32)
                     for i in range(n)])


@pytest.mark.parametrize("continuous", [False, True])
def test_block_resolves_to_its_rows_in_order_beside_lone_rows(continuous):
    eng = _FakeEngine(delay_s=0.05)
    b = _mk(eng, max_delay_ms=30.0, continuous=continuous)
    head = b.submit(np.full((3,), 50.0, np.float32))
    time.sleep(0.02)                    # continuous: head is in flight
    blk = b.submit_block(_block(0, 3))
    lone = b.submit(np.full((3,), 7.0, np.float32))
    assert np.array_equal(blk.result(timeout=5), _block(0, 3) * 2.0)
    assert np.array_equal(lone.result(timeout=5), np.full((3,), 14.0))
    assert np.array_equal(head.result(timeout=5), np.full((3,), 100.0))
    assert b.stats()["requests"] == 5   # rows, not requests
    b.close()


@pytest.mark.parametrize("continuous", [False, True])
def test_a_block_is_never_split_over_two_batches(continuous):
    """5 + 5 rows against a top bucket of 8: two batches of 5 — the
    second block is held over whole and leads the next batch."""
    eng = _FakeEngine(delay_s=0.1)
    b = _mk(eng, max_delay_ms=30.0, continuous=continuous)
    head = b.submit(np.zeros((3,), np.float32))
    time.sleep(0.04 if continuous else 0.0)
    blocks = [b.submit_block(_block(10 * (i + 1), 5)) for i in range(2)]
    tail = b.submit(np.ones((3,), np.float32))
    for i, f in enumerate(blocks):
        assert np.array_equal(f.result(timeout=5),
                              _block(10 * (i + 1), 5) * 2.0)
    head.result(timeout=5), tail.result(timeout=5)
    b.close()
    live = [int((batch.sum(axis=1) != 0).sum()) for batch in eng.batches]
    assert sum(live) == 11 and max(live) <= 8
    # the row sums of every block sit in ONE batch
    for i in range(2):
        want = set((_block(10 * (i + 1), 5) * 1.0).sum(axis=1).tolist())
        assert sum(want <= set(batch.sum(axis=1).tolist())
                   for batch in eng.batches) == 1


def test_a_block_past_the_top_bucket_fails_alone():
    eng = _FakeEngine()
    b = _mk(eng, continuous=True)
    ok = b.submit(np.ones((3,), np.float32))
    too_big = b.submit_block(_block(0, 9))
    after = b.submit(np.ones((3,), np.float32))
    with pytest.raises(ValueError):
        too_big.result(timeout=5)
    assert np.array_equal(ok.result(timeout=5), np.full((3,), 2.0))
    assert np.array_equal(after.result(timeout=5), np.full((3,), 2.0))
    assert b.stats()["batch_errors"] == 1
    b.close()


def test_take_cuts_each_requests_share_out_of_what_the_batch_returned():
    """The batch returns two arrays and a stamp; a lone row gets its
    row of each, a block its rows, both the stamp."""
    seen = []

    def run(rows):
        seen.append(rows.shape[0])
        return rows * 2.0, rows.sum(axis=1).astype(np.int32), len(seen)

    b = DynamicBatcher(run, _bucket_for, max_batch=8, continuous=True,
                       pad=False, span_name="topk.flush",
                       take=lambda out, at: (out[0][at], out[1][at], out[2]))
    doubled, total, stamp = b.submit_block(_block(1, 3)).result(timeout=5)
    assert np.array_equal(doubled, _block(1, 3) * 2.0)
    assert total.tolist() == [3, 6, 9] and total.dtype == np.int32
    doubled, total, stamp2 = b.submit(
        np.full((3,), 4.0, np.float32)).result(timeout=5)
    assert doubled.shape == (3,) and total == 12
    assert (stamp, stamp2) == (1, 2)
    assert seen == [3, 1]               # pad=False: the live rows alone
    b.close()


def test_span_name_and_unpadded_rows_on_the_flush_record():
    from milnce_tpu.obs import spans as obs_spans

    rec = obs_spans.SpanRecorder(ring=64)
    eng = _FakeEngine()
    b = _mk(eng, continuous=True, pad=False, span_name="topk.flush",
            recorder=rec, name="topk")
    b.submit_block(_block(0, 5)).result(timeout=5)
    b.close()
    (flush,) = [r for r in rec.tail() if r["name"] == "topk.flush"]
    assert (flush["rows"], flush["bucket"], flush["batcher"]) == (5, 8,
                                                                  "topk")
    assert {"queue_wait_ms", "queue_wait_mean_ms", "dur_ms"} <= set(flush)
    assert not [r for r in rec.tail() if r["name"] == "batcher.flush"]
    assert eng.batches[0].shape[0] == 5


def test_close_fails_the_block_that_was_held_over():
    """Pipelined continuous mode, the one lane busy: the worker parks
    with a forming batch and a block held over behind it; both count in
    ``depth()`` and both are failed, never dropped, at close."""
    from concurrent.futures import Future

    inflight: list[Future] = []

    def run_async(rows):
        inflight.append(Future())
        return inflight[-1]             # never resolved: the lane stays busy

    b = _mk(_FakeEngine(), continuous=True, lanes=1,
            run_batch_async=run_async)
    b.submit(np.zeros((3,), np.float32))
    deadline = time.monotonic() + 5.0
    while not inflight and time.monotonic() < deadline:
        time.sleep(0.005)
    first = b.submit_block(_block(0, 5))
    held = b.submit_block(_block(5, 5))     # does not fit behind `first`
    while b.depth() < 10 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert b.depth() == 10              # forming rows + the held block
    b.close()
    for f in (first, held):
        with pytest.raises(RuntimeError, match="closed"):
            f.result(timeout=5)


# ---- driven: the owner's thread takes and flushes (ISSUE 29) -----------------

def _driven(eng, **kw):
    woken = []
    b = _mk(eng, wake=lambda: woken.append(1), max_delay_ms=60_000.0, **kw)
    return b, woken


def test_driven_starts_no_worker_and_wakes_its_owner_at_every_submit():
    eng = _FakeEngine()
    b, woken = _driven(eng)
    futs = [b.submit(r) for r in _rows(3)]
    time.sleep(0.05)
    assert len(woken) == 3 and not eng.batches     # nobody flushes for it
    assert not any(t.name == "batcher-worker" for t in threading.enumerate())
    assert b.depth() == 3
    batch = b.take()                    # every row that waits now
    assert len(batch) == 3 and b.take() == [] and b.depth() == 0
    b.flush(batch, chained_rows=2)
    for i, f in enumerate(futs):
        assert np.array_equal(f.result(timeout=0), np.full((3,), 2.0 * i))
    assert eng.batches[0].shape == (4, 3)           # padded to its bucket
    assert b.stats()["flushes"] == 1
    b.close()


def test_driven_take_hands_out_blocks_whole_up_to_the_top_bucket():
    b, _ = _driven(_FakeEngine())
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


def test_driven_take_fails_what_expired_while_the_owner_was_away():
    b, _ = _driven(_FakeEngine())
    late = b.submit(np.ones((3,), np.float32), timeout_ms=10.0)
    live = b.submit(np.ones((3,), np.float32))
    time.sleep(0.03)                    # the owner is on the device
    batch = b.take()
    assert len(batch) == 1
    with pytest.raises(DeadlineExpired):
        late.result(timeout=0)
    b.flush(batch)
    assert live.result(timeout=0) is not None
    assert b.stats()["deadline_expired"] == 1
    b.close()


def test_driven_flush_record_carries_the_owners_attributes():
    from milnce_tpu.obs import spans as obs_spans

    rec = obs_spans.SpanRecorder(ring=64)
    b, _ = _driven(_FakeEngine(), recorder=rec, span_name="topk.flush",
                   name="topk")
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
    from concurrent.futures import Future

    ran_on = []
    b = _mk(_FakeEngine(), continuous=True)
    mine: Future = Future()
    mine.add_done_callback(
        lambda f: ran_on.append(threading.current_thread().name))
    assert b.submit(np.ones((3,), np.float32), future=mine) is mine
    assert np.array_equal(mine.result(timeout=5), np.full((3,), 2.0))
    b.close()
    assert ran_on == ["batcher-worker"]


def test_driven_close_fails_the_queue_and_the_next_take_the_held_block():
    b, woken = _driven(_FakeEngine())
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
