"""Dynamic micro-batcher: request queue -> padded bucket -> per-request
results.

The serving engine (engine.py) only executes fixed, pre-traced batch
shapes (the bucket ladder); individual requests arrive one row at a
time.  This module is the shim between the two worlds: a queue that
forms batches.  It owns NO thread.  ``wake()`` is called at every submit;
the owner's one thread (service.py's device worker) asks for ``take()``
— every request that waits NOW, up to the top bucket, blocks whole — at
the instant it turns to this batcher, and runs it with ``flush(batch)``:
rows padded to the smallest bucket that fits, one execution, per-row
results scattered back to the callers' futures.  One thread can so take
two batchers in turn (a text flush, then the pass that ranks its rows)
with no window between them: a lone request is flushed at once, and
while the device is busy arrivals gather for the next batch, so
occupancy rises exactly when the device is the bottleneck.

**Lanes** (``run_batch_async`` — a pool of replicas): a flush that only
SUBMITS its batch returns at once, and its results are scattered from
the executor's completion callback.  At most ``lanes`` such batches are
in flight: ``take()`` hands out nothing while that many are unresolved,
and the completion that frees a lane calls ``wake()``.

Deadline semantics (the request-path analogue of the training side's
decode watchdog, ROBUSTNESS.md): a request may carry a deadline that
bounds its QUEUE WAIT.  A request whose deadline has passed when the
owner next takes or flushes completes with :class:`DeadlineExpired` —
an error the caller sees, never a silent drop.  A deadline does NOT
abort device work already in flight: once a batch is submitted its rows
get their results.

**Blocks** (``submit_block``): a request may be a block of rows that
must share one batch (a multi-row retrieval call: one scan, one index
generation).  A block is never split: one that would take the forming
batch past the top bucket is held over and leads the next batch.  What
a batch returns need not be one array either: ``take`` says how a
request's rows are cut out of it (the scan coalescer of service.py gets
scores, indices and the generation that ranked them).

**The owner's turn** (``turns``, an ``obs_spans.PhaseClock``): every
flush is one turn of the owner's thread, and the turn's phases tile that
thread's time from the end of its last turn: ``sleep`` (the owner's own
wait for a wake, between two marks of its own), ``take`` (:meth:`take`,
and whatever else the owner does between two flushes), ``prepare``
(``flush`` up to the executor's call: ``_expire``, the queue waits,
``bucket_for``, ``np.concatenate``, ``pad_rows``), ``run`` (the
executor; over a pool, the submit), ``scatter`` (``set_result`` on every
request and whatever its done-callbacks do on this thread), ``account``
(counters, ``on_flush``).  One ``worker.turn`` record a flush, written
after its last phase, wall and CPU time a phase (OBSERVABILITY.md); the
flush record's ``dur_ms`` IS the turn's ``run_ms`` (one pair of clock
readings).  An owner of several batchers hands them one clock.

**A held scatter** (``flush(hold=True)``): the batch runs and its
results stay in hand, a :class:`HeldScatter` the owner runs later —
inside the round trip of the next program it dispatches
(``engine.defer``), so that the callers it answers run while the device
computes.  The flush's turn is set aside when its ``run`` ends and gets
its ``scatter`` and ``account`` where the held scatter runs; a program
that carried one has that stretch taken out of its own ``run`` (its
flush record's ``dur_ms`` keeps it, and its ``dispatch`` record names it
``overlap_ms``).

numpy-only on purpose: payloads and results are host arrays; every
device interaction lives behind the injected ``run_batch`` callable.
Thread safety: ``submit`` may be called from any number of threads; one
thread (the owner) takes and flushes; an asynchronous flush completes on
the executor's thread.  Every counter lives on the obs metrics registry
(lock-guarded there — OBSERVABILITY.md).
"""

from __future__ import annotations

import collections
import queue
import threading
import time
from concurrent.futures import Future, InvalidStateError
from dataclasses import dataclass
from typing import Callable, Optional

import numpy as np

from milnce_tpu.analysis.lockrt import make_lock
from milnce_tpu.obs import metrics as obs_metrics
from milnce_tpu.obs import spans as obs_spans

# What tiles the owner thread's time, a turn (one flush) at a time: module
# docstring.  ``sleep`` only blocks, so its CPU time is not read.
TURN_PHASES = ("sleep", "take", "prepare", "run", "scatter", "account")


def turn_clock() -> obs_spans.PhaseClock:
    """The clock of one owner thread's turns (``worker.turn`` records,
    ``worker.<phase>`` annotations); between two flushes the owner is in
    ``take``."""
    return obs_spans.PhaseClock("worker", TURN_PHASES, rest="take",
                                waits=("sleep",))


class DeadlineExpired(RuntimeError):
    """The request's deadline passed while it was still queued.

    ``retry_after_ms`` is the server's retry hint (how long the batcher's
    most recent flush took; 0 before the first) — the HTTP front
    surfaces it as a real
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


# Edges of the occupancy histogram: powers of two past any ladder's top rung.
_OCCUPANCY_EDGES = tuple(2 ** i for i in range(11))


class DynamicBatcher:
    """Queue turning single-row submits into bucket-padded batch
    executions, on its owner's thread.

    - ``run_batch(padded (bucket, ...)) -> (bucket, D)``: the batch
      executor (e.g. ``InferenceEngine.embed_text``).  Row ``i`` of the
      output must correspond to row ``i`` of the input — the pad/unpad
      identity the batcher relies on (pinned by tests).
    - ``bucket_for(n) -> bucket >= n``: the engine's ladder lookup.
    - ``max_batch``: the most rows :meth:`take` hands out (== the top
      bucket).
    - ``wake``: called at every submit, at ``close`` and when an
      asynchronous flush frees a lane; the owner's thread then calls
      :meth:`take` and :meth:`flush`.
    - ``default_timeout_ms``: deadline applied to submits that don't pass
      their own; 0 disables.
    - ``registry``: obs metrics registry the counters/occupancy histogram
      land on (None = a private one, so standalone batchers stay
      isolated; the service passes its registry down so ``GET /metrics``
      sees the request path).
    - ``run_batch_async``: optional Future-returning batch executor (e.g.
      ``ReplicaPool.submit_text``).  When set, a flush SUBMITS its padded
      batch and returns — results scatter to the callers' futures from a
      completion callback — so several batches can be in flight across
      pool replicas at once and one wedged replica never blocks the
      owner.  ``run_batch`` is ignored when this is set.
    - ``lanes``: at most that many asynchronous batches in flight (the
      pool's replica count; module docstring).
    - ``take(out, at)``: a request's share of what the batch returned;
      ``at`` is the row index of a ``submit`` and the slice of a
      ``submit_block``.  None = ``out`` is one array and the share is
      ``out[at]``.
    - ``pad``: False hands ``run_batch`` the live rows alone (the
      executor pads to its own ladder and sees how many rows are real);
      ``bucket_for`` then only names the bucket on the flush record.
    - ``span_name``: the flush record's name, so that two batchers of
      one service can be told apart by it (OBSERVABILITY.md).
    - ``turns``: the owner thread's phase clock (module docstring), one
      for all the batchers that thread drives; None = one of its own.
    """

    def __init__(self, run_batch: Callable[[np.ndarray], np.ndarray],
                 bucket_for: Callable[[int], int], *, max_batch: int,
                 wake: Callable[[], None], default_timeout_ms: float = 0.0,
                 name: str = "batcher",
                 registry: Optional[obs_metrics.MetricsRegistry] = None,
                 recorder: Optional[obs_spans.SpanRecorder] = None,
                 on_flush: Optional[Callable[[float, int], None]] = None,
                 run_batch_async: Optional[Callable[[np.ndarray],
                                                    Future]] = None,
                 lanes: int = 1,
                 take: Optional[Callable] = None, pad: bool = True,
                 span_name: str = "batcher.flush",
                 turns: Optional[obs_spans.PhaseClock] = None):
        assert max_batch >= 1
        self.turns = turns if turns is not None else turn_clock()
        self._run_batch = run_batch
        self._take = take
        self._pad = bool(pad)
        self._span_name = span_name
        self._wake = wake
        self._run_batch_async = run_batch_async
        self._lanes = max(1, int(lanes))
        # flush-latency observer ``(dur_ms, live_rows) -> None``: the
        # service feeds its EWMA spike detector here (anomaly-triggered
        # profiler capture).  Invoked on the flushing thread AFTER the
        # flush resolves, outside every batcher lock (GL012 discipline:
        # the callee takes its own locks)
        self._on_flush = on_flush
        # flush spans go to the injected recorder when the owner (the
        # service) isolates one; None = the process default, resolved at
        # flush time so a later spans.install() is honored
        self._recorder = recorder
        self._bucket_for = bucket_for
        self.max_batch = int(max_batch)
        self.default_timeout_ms = float(default_timeout_ms)
        self.name = name
        self._q: queue.Queue[_Request] = queue.Queue()
        # a block that did not fit the batch being formed (one at most):
        # it leads the next one.  The owner's thread's alone (put, taken
        # and failed at close on it)
        self._held_over: collections.deque[_Request] = collections.deque()
        self._closed = threading.Event()
        self.registry = registry if registry is not None \
            else obs_metrics.MetricsRegistry()
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
        # the edges are the same for every batcher, whatever its ladder:
        # services with different ladders share the process-wide registry
        # (the per-bucket counters below carry the ladder)
        self._m_occupancy = reg.histogram(
            "milnce_serve_batch_occupancy",
            "live rows per executed batch",
            buckets=_OCCUPANCY_EDGES, labels=("batcher",)).labels(**lbl)
        self._f_bucket_flushes = reg.counter(
            "milnce_serve_bucket_flushes_total",
            "batches executed per padded bucket size",
            ("batcher", "bucket"))
        self._f_bucket_rows = reg.counter(
            "milnce_serve_bucket_rows_total",
            "live rows executed per padded bucket size",
            ("batcher", "bucket"))
        # cached per-bucket child handles (resolved once per bucket on
        # the flushing thread).  Children are keyed by label values, so
        # two batchers sharing a registry AND a name read combined
        # totals — isolation is a private registry (the default) or a
        # distinct name, not this cache.  Lock-guarded: the flushing
        # thread inserts on a bucket's first flush while request threads
        # iterate it in stats() (/healthz) — EVERY access, including the
        # flushing thread's own lookup (graftlint GL010: single-writer
        # does not make a lock-free read of a guarded dict safe)
        self._bucket_children: dict[int, tuple] = {}
        self._children_lock = make_lock("serving.batcher.children")
        self._state_lock = make_lock("serving.batcher.state")
        # rows of the held-over block (left _q, not yet flushed): depth()
        # must count them or the admission feasibility floor undercounts
        self._held_rows = 0                   # guarded-by: _state_lock
        # asynchronous flushes submitted and not yet resolved
        self._inflight = 0                    # guarded-by: _state_lock
        # how long the most recent flush took: DeadlineExpired's retry hint
        self._last_flush_ms = 0.0             # guarded-by: _state_lock

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
        self._wake()
        if self._closed.is_set():
            # close() raced the put above and has already swept the
            # queue, so this request would hang forever — sweep it from
            # here (idempotent, InvalidStateError-safe) so the future
            # resolves either way
            self._drain_closed()
        return fut

    # ---- owner side -----------------------------------------------------

    def take(self) -> list:
        """Every request that waits NOW — the held-over block, then the
        queue's, up to the top bucket, blocks whole — less those whose
        deadline has passed, failed here (the owner has just come back
        from the device).  Empty when nothing waits, and while ``lanes``
        asynchronous flushes are unresolved (what waits stays queued; the
        completion that frees a lane wakes the owner); once closed, what
        waited is failed and nothing is handed out."""
        if self._closed.is_set():
            while self._held_over:
                self._fail_closed(self._held_over.popleft())
            self._drain_closed()
            return []
        with self._state_lock:
            if self._inflight >= self._lanes:
                return []
        batch: list = []
        n = 0
        while n < self.max_batch:
            try:
                r = (self._held_over.popleft() if self._held_over
                     else self._q.get_nowait())
            except queue.Empty:
                break
            if batch and n + r.rows > self.max_batch:
                # a block is never split: it leads the next batch.  (An
                # empty batch takes anything — a block larger than the
                # top bucket fails alone, at ``bucket_for``)
                self._held_over.append(r)
                break
            batch.append(r)
            n += r.rows
        with self._state_lock:
            self._held_rows = sum(r.rows for r in self._held_over)
        return self._expire(batch)

    def _expire(self, batch: list) -> list:
        """Fail every request in ``batch`` whose deadline has passed;
        returns the survivors."""
        now = time.monotonic()
        live = [r for r in batch if r.deadline is None or r.deadline >= now]
        if len(live) == len(batch):
            return live
        with self._state_lock:
            hint_ms = self._last_flush_ms
        for r in batch:
            if r.deadline is not None and r.deadline < now:
                r.future.set_exception(DeadlineExpired(
                    f"deadline exceeded by {self._past_ms(r, now):.1f} ms "
                    "while queued (request was never batched)",
                    retry_after_ms=hint_ms))
        self._m_expired.inc(len(batch) - len(live))
        return live

    def flush(self, batch: list[_Request], *, hold: bool = False,
              **attrs) -> Optional["HeldScatter"]:
        """Run ``batch`` (what :meth:`take` handed the owner) and scatter
        what it returns; ``attrs`` join the flush record, and ``epoch``
        among them (the owner's count of its turns) the ``worker.turn``
        record too, so that the two join by it.  ``hold``: a batch that
        ran is not scattered here — its :class:`HeldScatter` is returned
        for the owner to run, or to hand to the next program it
        dispatches; its turn and flush record are written when that ran
        (``rode`` on the record: where).  Otherwise None."""
        turn = self.turns
        turn.mark("prepare")
        live = self._expire(batch)
        if not live:
            return                      # what this took is the next turn's
        n = sum(r.rows for r in live)
        # how long the requests sat queued before this flush began: the
        # oldest one's wait and the mean, on the flush's own record
        t0 = time.monotonic()
        waits_ms = [(t0 - r.submitted) * 1e3 for r in live]
        waited = {"queue_wait_ms": round(max(waits_ms), 4),
                  "queue_wait_mean_ms": round(sum(waits_ms) / len(live), 4),
                  **attrs}
        rec = self._recorder if self._recorder is not None \
            else obs_spans.get_recorder()
        bucket = ran_from = failed = None

        def end_turn():
            turn.finish(rec, batcher=self.name, rows=n, bucket=bucket,
                        epoch=attrs.get("epoch"))

        try:
            # the whole batch computation is inside the try: a bad
            # payload (mixed row shapes -> np.concatenate raises) must fail
            # THIS batch's futures, never kill the owner's thread — a
            # dead owner would strand every later submit forever
            bucket = self._bucket_for(n)
            rows = np.concatenate([r.payload for r in live])
            if self._pad:
                rows = pad_rows(rows, bucket)
            if self._run_batch_async is not None:
                # submit and move on — the pool resolves the batch on
                # its own worker and the completion callback scatters
                # results, so the NEXT batch can flush (to another
                # replica) while this one is still in flight: the
                # owner's turn ends at the submit
                turn.mark("run")
                fut = self._run_batch_async(rows)
                with self._state_lock:
                    self._inflight += 1
                fut.add_done_callback(
                    lambda f: self._complete(f, live, bucket, n, t0,
                                             waited))
                end_turn()
                return
            ran_from = turn.mark("run")
            with obs_spans.annotation(self._span_name):
                out = self._run_batch(rows)
        except Exception as exc:
            failed = exc
        if hold and failed is None:
            aside, ran_to = turn.set_aside()
            return HeldScatter(self, live, out, aside, rec,
                               (ran_from, ran_to), bucket, n, waited)
        ran_to = turn.mark("scatter")
        if hold:
            waited["rode"] = "none"
        if ran_from is not None:        # the executor ran, or failed
            error = {} if failed is None else {"error": type(failed).__name__}
            flush_span = rec.closed_span(
                self._span_name, ran_from, ran_to, batcher=self.name,
                bucket=bucket, rows=n, **waited, **error)
        if failed is not None:
            # batch failure -> every caller sees the error (never a hang)
            for r in live:
                r.future.set_exception(failed)
            turn.mark("account")
            self._m_batch_errors.inc()
        else:
            self._scatter(live, out)
            turn.mark("account")
            self._account_flush(bucket, n, flush_span["dur_ms"])
        end_turn()

    def _complete(self, f: Future, live: list[_Request], bucket: int,
                  n: int, t0: float, waited: dict) -> None:
        """Async-flush completion (runs on the pool's worker thread):
        scatter per-row results / the batch error, then the same
        accounting as a synchronous flush.  The timed record is an
        ``event`` with ``dur_ms`` (a span cannot straddle threads); the
        completion's ``scatter`` and ``account``, which the owner's turn
        ended before, ride on it in wall and CPU time of this thread."""
        with self._state_lock:
            self._inflight -= 1
        self._wake()    # a lane is free: the owner may send the NEXT
        try:            # batch while these results are scattered
            out = f.result()
        except Exception as exc:
            for r in live:
                r.future.set_exception(exc)
            self._m_batch_errors.inc()
            return
        t1, c1 = obs_spans.now(), time.thread_time()
        self._scatter(live, out)
        t2, c2 = obs_spans.now(), time.thread_time()
        dur_ms = round((time.monotonic() - t0) * 1e3, 4)
        self._account_flush(bucket, n, dur_ms)
        t3, c3 = obs_spans.now(), time.thread_time()
        rec = self._recorder if self._recorder is not None \
            else obs_spans.get_recorder()
        took = {name: round((b - a) * 1e3, 4) for name, a, b in (
            ("scatter_ms", t1, t2), ("scatter_cpu_ms", c1, c2),
            ("account_ms", t2, t3), ("account_cpu_ms", c2, c3))}
        rec.event(self._span_name, batcher=self.name, bucket=bucket,
                  rows=n, dur_ms=dur_ms, **took, **waited)

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
        with self._state_lock:
            self._last_flush_ms = dur_ms
        self._m_flushes.inc()
        self._m_occupancy.observe(n)
        with self._children_lock:
            children = self._bucket_children.get(bucket)
        if children is None:
            # insert: flush path only (the owner's thread, or the pool
            # worker resolving an async flush).  The label resolution happens
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
        try:
            r.future.set_exception(RuntimeError("batcher closed"))
        except InvalidStateError:
            pass                        # the other drainer got it first

    def _drain_closed(self) -> None:
        """Fail (never drop) anything still queued when the batcher
        closes.  Callable from ``close``, the owner's ``take`` and a
        racing ``submit`` thread — double-resolution is tolerated."""
        while True:
            try:
                r = self._q.get_nowait()
            except queue.Empty:
                return
            self._fail_closed(r)

    # ---- lifecycle / observability --------------------------------------

    def close(self) -> None:
        """Refuse new submits and fail what waits: the queue here, the
        held-over block at the owner's next :meth:`take`.  A batch in
        flight still gets its results from its executor."""
        self._closed.set()
        self._wake()
        self._drain_closed()

    def depth(self) -> int:
        """Requests currently queued (approximate — stdlib qsize) plus
        the rows of the held-over block.  The admission controller's
        feasibility input (service.py)."""
        with self._state_lock:
            held = self._held_rows
        return self._q.qsize() + held

    def stats(self) -> dict:
        """Counters + the batch-occupancy histogram (bucket -> how full
        batches ran) — the number that tells you whether the ladder fits
        the offered load.  Keys are the pre-registry
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


class HeldScatter:
    """A flush that ran and whose results wait to be handed back: what
    ``flush(hold=True)`` returns.  Called as ``held(site)`` — by the
    round trip of the next program it rides (``engine.defer``), ``site``
    that program's, or by the owner between programs with ``""`` — it
    scatters, accounts and writes the flush record (``rode``: the last
    part of ``site``, ``"none"`` for ``""``) and the flush's
    ``worker.turn``, whose ``scatter`` and ``account`` are this call's,
    wherever it ran.  A scatter that raises fails the requests it had
    not answered."""

    __slots__ = ("_batcher", "_live", "_out", "_turn", "_rec", "_ran",
                 "bucket", "rows", "_waited")

    def __init__(self, batcher: DynamicBatcher, live: list, out, turn: tuple,
                 rec, ran: tuple, bucket: int, rows: int, waited: dict):
        self._batcher, self._live, self._out = batcher, live, out
        self._turn, self._rec, self._ran = turn, rec, ran
        self.bucket, self.rows, self._waited = bucket, rows, waited

    def __call__(self, site: str = "") -> None:
        b, clock = self._batcher, self._batcher.turns
        with clock.resume(self._turn, "scatter"):
            try:
                b._scatter(self._live, self._out)
            except Exception as exc:
                for r in self._live:
                    try:
                        r.future.set_exception(exc)
                    except InvalidStateError:
                        pass            # answered before the scatter raised
            clock.mark("account")
            flush_span = self._rec.closed_span(
                b._span_name, *self._ran, batcher=b.name, bucket=self.bucket,
                rows=self.rows, **self._waited,
                rode=site.rsplit(".", 1)[-1] if site else "none")
            b._account_flush(self.bucket, self.rows, flush_span["dur_ms"])
        clock.finish(self._rec, self._turn, batcher=b.name, rows=self.rows,
                     bucket=self.bucket, epoch=self._waited.get("epoch"))
