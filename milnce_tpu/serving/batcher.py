"""Dynamic micro-batcher: request queue -> padded bucket -> per-request
results.

The serving engine (engine.py) only executes fixed, pre-traced batch
shapes (the bucket ladder); individual requests arrive one row at a
time.  This module is the shim between the two worlds: a worker thread
drains a queue, groups rows into a batch, pads the batch to the
smallest bucket that fits, runs it, and scatters per-row results back
to the callers' futures.

Flush policy (both bounds are SLO knobs, SERVING.md):

- **size**: a batch flushes as soon as ``max_batch`` rows are waiting —
  never pads past the top bucket;
- **delay**: a batch flushes at most ``max_delay_ms`` after its FIRST
  row arrived — a lone request never waits longer than the delay bound
  for company.

**Continuous batching** (``continuous=True`` — the vLLM slot-reuse idea
adapted to the fixed bucket ladder, SERVING.md "Continuous batching"):
instead of flush-and-wait, the worker flushes the moment a dispatch
LANE is free — a lone request never pays ``max_delay_ms`` for company
that isn't coming — and while every lane is busy, arrivals accumulate
into the forming batch, filling bucket slots for free (occupancy rises
exactly when the device is the bottleneck).  ``lanes`` is the number of
concurrently-dispatchable batches (1 for a single engine; the replica
count for a pool in pipelined mode); a semaphore bounds in-flight
batches to it.  Deadlines stay prompt: the lane-wait loop expires aged
requests at the same ~2 ms resolution the deadline wake gives the
flush-and-wait path.

Deadline semantics (the request-path analogue of the training side's
decode watchdog, ROBUSTNESS.md): a request may carry a deadline that
bounds its QUEUE WAIT.  A request whose deadline passes before its
batch runs completes with :class:`DeadlineExpired` — an error the
caller sees, never a silent drop — and the worker wakes early at the
nearest pending deadline so expiry is prompt, not discovered at the
next size/delay flush.  A deadline does NOT abort device work already
in flight: once a batch is submitted its rows get their results.

**Blocks** (``submit_block``): a request may be a block of rows that
must share one batch (a multi-row retrieval call: one scan, one index
generation).  A block is never split: one that would take the forming
batch past the top bucket is held over and leads the next batch.  What
a batch returns need not be one array either: ``take`` says how a
request's rows are cut out of it (the scan coalescer of service.py gets
scores, indices and the generation that ranked them).

**Driven** (``wake=`` — service.py's device worker): the batcher starts
no worker of its own.  ``wake()`` is called at every submit; the owner's
one thread asks for ``take()`` — every request that waits NOW, up to the
top bucket, blocks whole — at the instant it turns to this batcher, and
runs it with ``flush(batch)``.  One thread can so take two batchers in
turn (a text flush, then the pass that ranks its rows), with neither a
window nor a lane between them: ``max_delay_ms``, ``continuous`` and
``lanes`` govern a batcher that has its own worker.

numpy-only on purpose: payloads and results are host arrays; every
device interaction lives behind the injected ``run_batch`` callable.
Thread safety: ``submit`` may be called from any number of threads;
one thread (the worker, or the owner of a driven batcher) owns the flush
path; every counter lives on the obs
metrics registry (lock-guarded there — OBSERVABILITY.md), so request
threads and the worker can no longer race an unlocked dict.
"""

from __future__ import annotations

import collections
import queue
import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass
from typing import Callable, Optional

import numpy as np

from milnce_tpu.analysis.lockrt import make_lock
from milnce_tpu.obs import metrics as obs_metrics
from milnce_tpu.obs import spans as obs_spans

# The worker wakes this soon after the nearest deadline so an expired
# request fails promptly (bounded staleness of the expiry verdict).
_DEADLINE_SLACK_S = 0.002
# Idle poll period: how often the worker re-checks the closed flag when
# the queue is empty (bounds close() latency, costs nothing hot).
_IDLE_POLL_S = 0.05
# Continuous mode's lane-wait tick: bounds both deadline-expiry
# staleness and close() latency while every dispatch lane is busy.
_LANE_POLL_S = 0.002


class DeadlineExpired(RuntimeError):
    """The request's deadline passed while it was still queued.

    ``retry_after_ms`` is the server's retry hint (a fresh, lone request's
    expected queue wait) — the HTTP front surfaces it as a real
    ``Retry-After`` header plus a ``retry_after_ms`` JSON body field
    (SERVING.md "HTTP error contract")."""

    def __init__(self, msg: str, retry_after_ms: float = 0.0):
        super().__init__(msg)
        self.retry_after_ms = float(retry_after_ms)


def pad_rows(rows: np.ndarray, bucket: int) -> np.ndarray:
    """Zero-pad ``(n, ...)`` rows up to ``(bucket, ...)`` on axis 0
    (no-op when already at the bucket).  THE pad rule of the serve path
    — batcher, engine and index all share it so it cannot diverge."""
    n = rows.shape[0]
    if bucket <= n:
        return rows
    pad = np.zeros((bucket - n,) + rows.shape[1:], dtype=rows.dtype)
    return np.concatenate([rows, pad], axis=0)


@dataclass
class _Request:
    payload: np.ndarray              # (rows, ...) — a lone row is (1, ...)
    future: Future
    deadline: Optional[float]        # absolute time.monotonic() seconds
    submitted: float                 # time.monotonic() at submit()
    rows: int = 1
    block: bool = False              # resolves to its rows, not to row i


class DynamicBatcher:
    """Queue + worker thread turning single-row submits into bucket-padded
    batch executions.

    - ``run_batch(padded (bucket, ...)) -> (bucket, D)``: the batch
      executor (e.g. ``InferenceEngine.embed_text``).  Row ``i`` of the
      output must correspond to row ``i`` of the input — the pad/unpad
      identity the batcher relies on (pinned by tests).
    - ``bucket_for(n) -> bucket >= n``: the engine's ladder lookup.
    - ``max_batch``: size-flush threshold (== the top bucket).
    - ``max_delay_ms``: delay-flush bound.
    - ``default_timeout_ms``: deadline applied to submits that don't pass
      their own; 0 disables.
    - ``registry``: obs metrics registry the counters/occupancy histogram
      land on (None = a private one, so standalone batchers stay
      isolated; the service passes its registry down so ``GET /metrics``
      sees the request path).
    - ``buckets``: the engine's ladder, used as the occupancy histogram's
      fixed edges (None = powers of two up to ``max_batch``).
    - ``run_batch_async``: optional Future-returning batch executor (e.g.
      ``ReplicaPool.submit_text``).  When set, the worker SUBMITS each
      padded batch and moves on — results scatter to the callers' futures
      from a completion callback — so several batches can be in flight
      across pool replicas at once and one wedged replica never blocks
      the flush loop.  ``run_batch`` is ignored when this is set.
    - ``continuous``: continuous batching (module docstring) — flush the
      instant a lane is free, accumulate while lanes are busy;
      ``max_delay_ms`` is ignored (a lone request never waits for
      company that isn't coming).
    - ``lanes``: concurrently-in-flight batch bound in continuous mode
      (the pool's replica count in pipelined mode, else 1).
    - ``take(out, at)``: a request's share of what the batch returned;
      ``at`` is the row index of a ``submit`` and the slice of a
      ``submit_block``.  None = ``out`` is one array and the share is
      ``out[at]``.
    - ``pad``: False hands ``run_batch`` the live rows alone (the
      executor pads to its own ladder and sees how many rows are real);
      ``bucket_for`` then only names the bucket on the flush record.
    - ``span_name``: the flush record's name, so that two batchers of
      one service can be told apart by it (OBSERVABILITY.md).
    - ``wake``: driven (module docstring): no worker is started,
      ``wake()`` runs at every submit and at ``close``, and the owner's
      thread calls :meth:`take` and :meth:`flush`; ``max_delay_ms``,
      ``continuous`` and ``lanes`` are then ignored.
    """

    def __init__(self, run_batch: Callable[[np.ndarray], np.ndarray],
                 bucket_for: Callable[[int], int], *, max_batch: int,
                 max_delay_ms: float = 5.0, default_timeout_ms: float = 0.0,
                 name: str = "batcher",
                 registry: Optional[obs_metrics.MetricsRegistry] = None,
                 buckets: Optional[tuple] = None,
                 recorder: Optional[obs_spans.SpanRecorder] = None,
                 on_flush: Optional[Callable[[float, int], None]] = None,
                 run_batch_async: Optional[Callable[[np.ndarray],
                                                    Future]] = None,
                 continuous: bool = False, lanes: int = 1,
                 take: Optional[Callable] = None, pad: bool = True,
                 span_name: str = "batcher.flush",
                 wake: Optional[Callable[[], None]] = None):
        assert max_batch >= 1
        self._run_batch = run_batch
        self._take = take
        self._pad = bool(pad)
        self._span_name = span_name
        self._wake = wake
        self._run_batch_async = run_batch_async
        self.continuous = bool(continuous) and wake is None
        # in-flight batch bound for continuous mode: acquired by the
        # worker before each flush, released when the flush resolves
        # (sync: after run_batch; async: in the completion callback)
        self._lane_sem = (threading.Semaphore(max(1, int(lanes)))
                          if self.continuous else None)
        # flush-latency observer ``(dur_ms, live_rows) -> None``: the
        # service feeds its EWMA spike detector here (anomaly-triggered
        # profiler capture).  Invoked on the worker thread AFTER the
        # flush resolves, outside every batcher lock (GL012 discipline:
        # the callee takes its own locks)
        self._on_flush = on_flush
        # flush spans go to the injected recorder when the owner (the
        # service) isolates one; None = the process default, resolved at
        # flush time so a later spans.install() is honored
        self._recorder = recorder
        self._bucket_for = bucket_for
        self.max_batch = int(max_batch)
        self.max_delay_s = float(max_delay_ms) / 1000.0
        self.default_timeout_ms = float(default_timeout_ms)
        self.name = name
        self._q: queue.Queue[_Request] = queue.Queue()
        # a block that did not fit the batch being formed (one at most):
        # it leads the next one.  The flushing thread's alone (put, taken
        # and failed at close on it)
        self._held_over: collections.deque[_Request] = collections.deque()
        self._closed = threading.Event()
        self.registry = registry if registry is not None \
            else obs_metrics.MetricsRegistry()
        if buckets is None:
            buckets, b = [], 1
            while b < self.max_batch:
                buckets.append(b)
                b *= 2
            buckets.append(self.max_batch)
        lbl = {"batcher": name}
        reg = self.registry
        self._m_requests = reg.counter(
            "milnce_serve_requests_total",
            "rows submitted to the batcher", ("batcher",)).labels(**lbl)
        self._m_flushes = reg.counter(
            "milnce_serve_flushes_total",
            "batches executed", ("batcher",)).labels(**lbl)
        self._m_expired = reg.counter(
            "milnce_serve_deadline_expired_total",
            "requests failed with DeadlineExpired while queued",
            ("batcher",)).labels(**lbl)
        self._m_batch_errors = reg.counter(
            "milnce_serve_batch_errors_total",
            "batch executions that failed (propagated to every caller)",
            ("batcher",)).labels(**lbl)
        self._m_occupancy = reg.histogram(
            "milnce_serve_batch_occupancy",
            "live rows per executed batch (bucket edges = the ladder)",
            buckets=tuple(buckets), labels=("batcher",)).labels(**lbl)
        self._f_bucket_flushes = reg.counter(
            "milnce_serve_bucket_flushes_total",
            "batches executed per padded bucket size",
            ("batcher", "bucket"))
        self._f_bucket_rows = reg.counter(
            "milnce_serve_bucket_rows_total",
            "live rows executed per padded bucket size",
            ("batcher", "bucket"))
        # cached per-bucket child handles (resolved once per bucket on
        # the worker thread).  Children are keyed by label values, so
        # two batchers sharing a registry AND a name read combined
        # totals — isolation is a private registry (the default) or a
        # distinct name, not this cache.  Lock-guarded: the worker
        # inserts on a bucket's first flush while request threads
        # iterate it in stats() (/healthz) — EVERY access, including the
        # worker's own lookup (graftlint GL010: single-writer does not
        # make a lock-free read of a guarded dict safe)
        self._bucket_children: dict[int, tuple] = {}
        self._children_lock = make_lock("serving.batcher.children")
        # rows the continuous worker has dequeued into its FORMING batch
        # (left _q, not yet flushed): depth() must count them or the
        # admission feasibility floor undercounts by up to max_batch
        # while the worker parks on busy lanes
        self._forming = 0                     # guarded-by: _forming_lock
        self._forming_lock = make_lock("serving.batcher.forming")
        self._worker = None
        if wake is None:
            self._worker = threading.Thread(target=self._run, daemon=True,
                                            name=f"{name}-worker")
            self._worker.start()

    # ---- client side ----------------------------------------------------

    def submit(self, payload: np.ndarray, timeout_ms: Optional[float] = None,
               future: Optional[Future] = None) -> Future:
        """Enqueue one row; returns a Future resolving to its result row.

        ``timeout_ms``: deadline for THIS request (None = the batcher
        default; <= 0 = no deadline).  ``future``: the caller's own, to
        resolve in place of a new one — its done-callbacks, added before
        the row can be flushed, run on the thread that flushes it."""
        return self._enqueue(np.asarray(payload)[None], timeout_ms, False,
                             future)

    def submit_block(self, rows: np.ndarray,
                     timeout_ms: Optional[float] = None,
                     future: Optional[Future] = None) -> Future:
        """Enqueue ``(n, ...)`` rows that ride ONE batch; the Future
        (``future``, as :meth:`submit` takes it) resolves to their share
        of it, in order.  More rows than the top bucket fail with
        ``bucket_for``'s error."""
        return self._enqueue(np.asarray(rows), timeout_ms, True, future)

    def _enqueue(self, payload: np.ndarray, timeout_ms: Optional[float],
                 block: bool, future: Optional[Future]) -> Future:
        if self._closed.is_set():
            raise RuntimeError("batcher is closed")
        t_ms = self.default_timeout_ms if timeout_ms is None else timeout_ms
        now = time.monotonic()
        deadline = (now + t_ms / 1000.0) if t_ms > 0 else None
        fut: Future = Future() if future is None else future
        n = payload.shape[0]
        self._m_requests.inc(n)
        self._q.put(_Request(payload, fut, deadline, now, n, block))
        if self._wake is not None:
            self._wake()
        if self._closed.is_set():
            # close() raced the put above: the worker may already have
            # drained and exited, so this request would hang forever —
            # sweep the queue from here (idempotent, InvalidStateError-
            # safe) so the future resolves either way
            self._drain_closed()
        return fut

    # ---- worker side ----------------------------------------------------

    def _run(self) -> None:
        (self._run_continuous if self.continuous else self._run_windowed)()
        self._fail_waiting()

    def _fail_waiting(self) -> None:
        """Closed: fail the held-over block and the queue (on the
        flushing thread, the held-over block's owner)."""
        while self._held_over:
            self._fail_closed(self._held_over.popleft())
        self._drain_closed()

    def take(self) -> list:
        """Driven: every request that waits NOW — the held-over block,
        then the queue's, up to the top bucket, blocks whole — less those
        whose deadline has passed, failed here (the owner has just come
        back from the device).  Empty when nothing waits; once closed,
        what waited is failed and nothing is handed out."""
        if self._closed.is_set():
            self._fail_waiting()
            return []
        batch: list = []
        self._drain_into(batch)
        self._set_forming(sum(r.rows for r in self._held_over))
        return self._expire(batch)

    def _next(self, timeout: Optional[float] = None) -> _Request:
        """The held-over block first, else the queue's next request
        (``queue.Empty`` after ``timeout``; None = without waiting)."""
        if self._held_over:
            return self._held_over.popleft()
        if timeout is None:
            return self._q.get_nowait()
        return self._q.get(timeout=timeout)

    def _offer(self, batch: list, n: int, r: _Request) -> bool:
        """``r`` joins ``batch`` (``n`` rows so far) unless that takes the
        batch past the top bucket: then it is held over, whole.  An empty
        batch takes anything — a block larger than the top bucket fails
        alone, at ``bucket_for``."""
        if batch and n + r.rows > self.max_batch:
            self._held_over.append(r)
            return False
        batch.append(r)
        return True

    def _run_windowed(self) -> None:
        while not self._closed.is_set():
            try:
                first = self._next(_IDLE_POLL_S)
            except queue.Empty:
                continue
            batch, n = [first], first.rows
            flush_at = time.monotonic() + self.max_delay_s
            while n < self.max_batch:
                wake = flush_at
                for r in batch:
                    if r.deadline is not None:
                        wake = min(wake, r.deadline + _DEADLINE_SLACK_S)
                remaining = wake - time.monotonic()
                if remaining <= 0:
                    break
                try:
                    r = self._q.get(timeout=remaining)
                except queue.Empty:
                    break        # woke at flush_at or a pending deadline
                if not self._offer(batch, n, r):
                    break
                n += r.rows
            self.flush(batch)

    def _run_continuous(self) -> None:
        """Continuous batching: flush as soon as a lane is free, fill
        bucket slots from new arrivals while every lane is busy."""
        while not self._closed.is_set():
            try:
                first = self._next(_IDLE_POLL_S)
            except queue.Empty:
                continue
            batch = [first]
            self._drain_into(batch)
            got_lane = self._lane_sem.acquire(timeout=_LANE_POLL_S)
            while not got_lane and not self._closed.is_set():
                # parked on busy lanes: expire aged requests promptly
                # and keep topping the forming batch up to the bucket
                batch = self._expire(batch)
                self._drain_into(batch)
                got_lane = self._lane_sem.acquire(timeout=_LANE_POLL_S)
            self._set_forming(0)
            if not got_lane:        # closing: fail the collected batch
                for r in batch:
                    self._fail_closed(r)
                break
            self.flush(batch)       # the flush resolution frees the lane

    def _set_forming(self, n: int) -> None:
        with self._forming_lock:
            self._forming = n

    def _drain_into(self, batch: list) -> None:
        """Move whatever is queued RIGHT NOW into ``batch`` (up to the
        top bucket) without waiting — the continuous-mode accumulator —
        and publish the forming rows for :meth:`depth`."""
        n = sum(r.rows for r in batch)
        while n < self.max_batch:
            try:
                r = self._next()
            except queue.Empty:
                break
            if not self._offer(batch, n, r):
                break
            n += r.rows
        self._set_forming(n + sum(r.rows for r in self._held_over))

    def _release_lane(self) -> None:
        if self._lane_sem is not None:
            self._lane_sem.release()

    def _expire(self, batch: list) -> list:
        """Fail (promptly) every request in ``batch`` whose deadline has
        passed; returns the survivors."""
        now = time.monotonic()
        live, expired = [], 0
        for r in batch:
            if r.deadline is not None and r.deadline < now:
                r.future.set_exception(DeadlineExpired(
                    f"deadline exceeded by {self._past_ms(r, now):.1f} ms "
                    "while queued (request was never batched)",
                    retry_after_ms=self.max_delay_s * 1e3))
                expired += 1
            else:
                live.append(r)
        if expired:
            self._m_expired.inc(expired)
        return live

    def flush(self, batch: list[_Request], **attrs) -> None:
        """Run ``batch`` (the worker's, or what :meth:`take` handed a
        driven batcher's owner) and scatter what it returns; ``attrs``
        join the flush record."""
        live = self._expire(batch)
        if not live:
            self._release_lane()
            return
        n = sum(r.rows for r in live)
        # how long the requests sat queued before this flush began: the
        # oldest one's wait and the mean, on the flush's own record
        t0 = time.monotonic()
        waits_ms = [(t0 - r.submitted) * 1e3 for r in live]
        waited = {"queue_wait_ms": round(max(waits_ms), 4),
                  "queue_wait_mean_ms": round(sum(waits_ms) / len(live), 4),
                  **attrs}
        try:
            # the whole batch computation is inside the try: a bad
            # payload (mixed row shapes -> np.concatenate raises) must fail
            # THIS batch's futures, never kill the worker thread — a
            # dead worker would strand every later submit forever
            bucket = self._bucket_for(n)
            rows = np.concatenate([r.payload for r in live])
            if self._pad:
                rows = pad_rows(rows, bucket)
            if self._run_batch_async is not None:
                # pipelined mode: submit and move on — the pool resolves
                # the batch on its own worker and the completion callback
                # scatters results, so the NEXT batch can flush (to
                # another replica) while this one is still in flight
                fut = self._run_batch_async(rows)
                fut.add_done_callback(
                    lambda f: self._complete(f, live, bucket, n, t0,
                                             waited))
                return
            rec = self._recorder if self._recorder is not None \
                else obs_spans.get_recorder()
            with rec.span(self._span_name, batcher=self.name,
                          bucket=bucket, rows=n, **waited) as flush_span:
                out = self._run_batch(rows)
        except Exception as exc:
            # batch failure -> every caller sees the error (never a hang)
            self._release_lane()
            for r in live:
                r.future.set_exception(exc)
            self._m_batch_errors.inc()
            return
        self._release_lane()
        self._scatter(live, out)
        self._account_flush(bucket, n, flush_span["dur_ms"])

    def _complete(self, f: Future, live: list[_Request], bucket: int,
                  n: int, t0: float, waited: dict) -> None:
        """Async-flush completion (runs on the pool's worker thread):
        scatter per-row results / the batch error, then the same
        accounting as a synchronous flush.  The timed record is an
        ``event`` with ``dur_ms`` (a span cannot straddle threads)."""
        self._release_lane()            # frees the lane for the NEXT
        try:                            # batch before scattering results
            out = f.result()
        except Exception as exc:
            for r in live:
                r.future.set_exception(exc)
            self._m_batch_errors.inc()
            return
        self._scatter(live, out)
        dur_ms = round((time.monotonic() - t0) * 1e3, 4)
        rec = self._recorder if self._recorder is not None \
            else obs_spans.get_recorder()
        rec.event(self._span_name, batcher=self.name, bucket=bucket,
                  rows=n, dur_ms=dur_ms, **waited)
        self._account_flush(bucket, n, dur_ms)

    def _scatter(self, live: list[_Request], out) -> None:
        """Each request its share of what the batch returned, in the
        order the rows went in."""
        take = self._take
        if take is None:
            out = np.asarray(out)
        lo = 0
        for r in live:
            at = slice(lo, lo + r.rows) if r.block else lo
            r.future.set_result(out[at] if take is None else take(out, at))
            lo += r.rows

    def _account_flush(self, bucket: int, n: int, dur_ms: float) -> None:
        self._m_flushes.inc()
        self._m_occupancy.observe(n)
        with self._children_lock:
            children = self._bucket_children.get(bucket)
        if children is None:
            # insert: flush path only (worker thread, or the pool worker
            # resolving an async flush).  The label resolution happens
            # OUTSIDE the children lock so it never nests over the
            # registry family lock (lock-order hygiene, GL011); a racing
            # double-insert writes the same label children twice, which
            # is idempotent.
            children = (
                self._f_bucket_flushes.labels(batcher=self.name,
                                              bucket=bucket),
                self._f_bucket_rows.labels(batcher=self.name, bucket=bucket))
            with self._children_lock:
                self._bucket_children[bucket] = children
        children[0].inc()
        children[1].inc(n)
        if self._on_flush is not None:
            self._on_flush(dur_ms, n)

    @staticmethod
    def _past_ms(r: _Request, now: float) -> float:
        return max(0.0, (now - r.deadline) * 1000.0) if r.deadline else 0.0

    @staticmethod
    def _fail_closed(r: _Request) -> None:
        from concurrent.futures import InvalidStateError

        try:
            r.future.set_exception(RuntimeError("batcher closed"))
        except InvalidStateError:
            pass                        # the other drainer got it first

    def _drain_closed(self) -> None:
        """Fail (never drop) anything still queued when the batcher
        closes.  Callable from both the exiting worker and a racing
        ``submit`` thread — double-resolution is tolerated."""
        while True:
            try:
                r = self._q.get_nowait()
            except queue.Empty:
                return
            self._fail_closed(r)

    # ---- lifecycle / observability --------------------------------------

    def close(self, timeout: float = 5.0) -> None:
        """Refuse new submits and fail what waits: the worker does on its
        way out; a driven batcher's queue is failed here and its
        held-over block by the owner's next :meth:`take`."""
        self._closed.set()
        if self._worker is not None:
            self._worker.join(timeout)
        else:
            self._wake()
            self._drain_closed()

    def depth(self) -> int:
        """Requests currently queued (approximate — stdlib qsize) plus
        any rows the continuous worker holds in its forming batch.  The
        admission controller's feasibility input (service.py)."""
        with self._forming_lock:
            forming = self._forming
        return self._q.qsize() + forming

    def stats(self) -> dict:
        """Counters + the batch-occupancy histogram (bucket -> how full
        batches ran) — the number that tells you whether max_delay_ms is
        tuned right for the offered load.  Keys are the pre-registry
        ``/healthz`` contract; the values now READ the registry metrics
        (one source of truth — SERVING.md observability section)."""
        occupancy = {}
        with self._children_lock:
            children = sorted(self._bucket_children.items())
        for b, (fc, rc) in children:
            f, rows = int(fc.value), int(rc.value)
            occupancy[str(b)] = {
                "flushes": f, "rows": rows,
                "mean_fill": (rows / (f * b)) if f else 0.0}
        # flushes read BEFORE requests: each read is atomic but the PAIR
        # is only monotonically consistent in this order (a reader
        # preempted between the two reads then sees requests >= the
        # causal floor of the flush count, never flushes > requests)
        flushes = int(self._m_flushes.value)
        return {
            "requests": int(self._m_requests.value),
            "flushes": flushes,
            "deadline_expired": int(self._m_expired.value),
            "batch_errors": int(self._m_batch_errors.value),
            "occupancy": occupancy,
        }
