"""Monotonic-clock span/event recorder: RUN_EVENTS.jsonl + in-memory ring.

The span taxonomy (OBSERVABILITY.md) covers the moments that explain a
run after the fact: ``step`` (hot-loop dispatch), ``decode.timeout`` /
``decode.retry`` (watchdog escalations), ``batcher.flush`` (serving
micro-batches), ``ladder.warmup`` (engine pre-trace sweep),
``ckpt.save`` / ``ckpt.restore`` / ``rollback`` (checkpoint lifecycle),
``display`` (the train loop's cadenced fetch), ``query`` / ``dispatch``
(a served call and each hold of the device lock), ``worker.turn`` (the
serving path's device worker, phase by phase: :class:`PhaseClock`),
``runtime.gc`` / ``runtime.beat`` / ``runtime.stall`` (a collector
pause, the interpreter's latency a second, one late wake-up:
:class:`RuntimeWatch`).

Durability has two tiers:

- the **ring** (``tail()``) always records — bounded memory, surfaced
  over HTTP by the serving front (``GET /obs/events``);
- the **JSONL file** records when a path is configured (the train loop
  writes ``<log_root>/RUN_EVENTS.jsonl``): append-only, one JSON object
  per line, line-buffered so a crash loses at most the current line.

Durations come from ``time.monotonic`` (wall-clock ``ts`` is attached
for human correlation only).  A span around a jitted call measures
HOST-SIDE dispatch, not device work — that is deliberate: the recorder
must never block on the device (the same host-side-only invariant as
the metrics registry).  For device truth every span is also a
``jax.profiler.TraceAnnotation`` of its name (:func:`annotation`)
whenever jax is already imported in the process: free while no profiler
session runs, and inside one — a benchmark's trace, an operator's
``POST /obs/capture``, a flush-spike capture — the program's spans lie
on ``/host:CPU`` beside the device's ops, on one clock.  Nothing here
imports jax: a host-only process (a loader thread) stays host-only.

Thread-safe: ring appends and file writes are lock-guarded (spans fire
from reader threads, the batcher worker and request threads).
"""

from __future__ import annotations

import contextlib
import gc
import json
import os
import sys
import threading
import time
from collections import deque
from typing import Optional

from milnce_tpu.analysis.lockrt import make_lock
from milnce_tpu.obs import runctx


def _now() -> float:
    """Monotonic seconds (single helper so span timing has one clock —
    and so tests can monkeypatch it)."""
    return time.monotonic()


def _wall() -> float:
    return time.time()


def now() -> float:
    """The recorder's clock, for what a caller times itself and puts on
    a record (``lock_wait_ms``, ``topk_ms``): a span's ``dur_ms`` and
    its attributes then share one clock.  Host time by design."""
    return _now()


def ms_since(t0: float) -> float:
    """Milliseconds from ``t0`` (a :func:`now` reading), rounded as
    ``dur_ms`` is."""
    return round((_now() - t0) * 1e3, 4)


def annotation(name: str):
    """``jax.profiler.TraceAnnotation(name)`` where jax is already
    imported in this process, else a null context.  Read from
    ``sys.modules`` so that no caller ever imports jax through here; a
    ``jax.profiler`` still half-way through its own import counts as
    absent."""
    make = getattr(sys.modules.get("jax.profiler"), "TraceAnnotation", None)
    return make(name) if make is not None else contextlib.nullcontext()


class _Span:
    """One timed region of :meth:`SpanRecorder.span`.  A class and not a
    generator: spans sit on the serving path's per-call host time, which
    is what the device waits for between two scans."""

    __slots__ = ("_recorder", "_rec", "_t0", "_bridge")

    def __init__(self, recorder: "SpanRecorder", name: str, attrs: dict):
        self._recorder = recorder
        self._rec = {"kind": "span", "name": name, "ts": _wall(), **attrs}

    def __enter__(self) -> dict:
        self._t0 = _now()
        self._bridge = annotation(self._rec["name"])
        self._bridge.__enter__()
        return self._rec

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._bridge.__exit__(exc_type, exc, tb)
        if exc_type is not None:
            self._rec["error"] = exc_type.__name__
        self._rec["dur_ms"] = ms_since(self._t0)
        self._recorder._record(self._rec)
        return False


class SpanRecorder:
    def __init__(self, path: Optional[str] = None, ring: int = 2048):
        self.path = path or None
        self._ring: deque = deque(maxlen=max(1, int(ring)))
        self._lock = make_lock("obs.spans.recorder")
        self._mono_last = 0.0
        self._fh = None
        if self.path:
            # line-buffered append handle, opened ONCE (the RunLogger
            # reopen-per-line pathology is the anti-pattern)
            self._fh = open(self.path, "a", buffering=1)

    # ---- recording -------------------------------------------------------

    def _record(self, rec: dict) -> None:
        # run identity stamped at RECORD time (not construction): the
        # owning entry point installs the context once, and every line —
        # including library events from reader/worker threads — carries
        # it, so obs_report can split a shared append-only stream by run
        # and aggregate.py can merge a pod's streams by process
        run_id, pi = runctx.get_run_context()
        if run_id is not None and "run_id" not in rec:
            rec["run_id"] = run_id
        if pi is not None and "process_index" not in rec:
            rec["process_index"] = pi
        with self._lock:
            # append-order monotonic cursor (``GET /obs/events?since=``):
            # stamped under the lock, and forced STRICTLY increasing —
            # two back-to-back records rounding to the same microsecond
            # would otherwise let a poller whose cursor lands between
            # them miss the second one forever (tail()'s filter is a
            # strict '>')
            mono = round(_now(), 6)
            if mono <= self._mono_last:
                mono = round(self._mono_last + 1e-6, 6)
            self._mono_last = mono
            rec["mono"] = mono
            self._ring.append(rec)
            if self._fh is not None:
                self._fh.write(json.dumps(rec) + "\n")

    def event(self, name: str, **attrs) -> None:
        """Point-in-time occurrence (a retry, a rollback, a display)."""
        rec = {"kind": "event", "name": name, "ts": _wall()}
        rec.update(attrs)
        self._record(rec)

    def span(self, name: str, **attrs) -> "_Span":
        """Timed region; ``with`` yields the record's dict (a caller may
        add attributes to it) and records on exit with ``dur_ms``
        (host-side elapsed).  Exceptions propagate — the span still
        records, with ``error`` naming the exception type."""
        return _Span(self, name, attrs)

    def closed_span(self, name: str, t0: float, t1: float, **attrs) -> dict:
        """The record :meth:`span` writes, for a region whose owner read
        the clock itself (``t0``, ``t1``: :func:`now` readings — the two
        ends of a :class:`PhaseClock` phase, so that the phase and the
        span are ONE pair of readings); ``ts`` is the wall clock at
        ``t0``, also where the record is written after ``t1``.  Returns
        the record."""
        rec = {"kind": "span", "name": name, "ts": _wall() - (_now() - t0),
               **attrs, "dur_ms": round((t1 - t0) * 1e3, 4)}
        self._record(rec)
        return rec

    # ---- reading / lifecycle --------------------------------------------

    def tail(self, n: Optional[int] = None,
             since: Optional[float] = None) -> list[dict]:
        """Most recent ``n`` records, oldest first (the whole ring by
        default); ``n <= 0`` is an empty list, not the whole ring (a
        bare ``out[-0:]`` would invert the limit's meaning).

        ``since`` keeps only records appended strictly after that
        ``mono`` cursor (the append-order monotonic stamp every record
        carries) — pollers pass their last-seen ``mono`` back instead of
        re-downloading the whole ring (``GET /obs/events?since=``)."""
        with self._lock:
            out = list(self._ring)
        if since is not None:
            cut = float(since)
            out = [r for r in out if r.get("mono", 0.0) > cut]
        if n is None:
            return out
        n = int(n)
        return out[-n:] if n > 0 else []

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None

    def __del__(self):
        try:
            self.close()
        except Exception:  # graftlint: disable=GL007(interpreter-teardown finalizer: close is best-effort, raising only makes unraisable-exception noise)
            pass


# ---------------------------------------------------------------------------
# one thread's turn, phase by phase
# ---------------------------------------------------------------------------

class PhaseClock:
    """One thread's timeline cut into named phases that TILE it: the
    thread is in exactly one phase at every instant, :meth:`mark` ends
    the current phase and begins the next on ONE pair of clock readings
    (wall: the recorder's clock; CPU: ``time.thread_time`` of the calling
    thread), and :meth:`finish` writes what every phase took since the
    last record as ONE ``<prefix>.turn`` event: ``<phase>_ms`` (wall),
    ``<phase>_cpu_ms`` (left out for the ``waits``, phases that only
    block) and ``dur_ms``, their sum.  Consecutive records leave no
    stretch of the thread's time uncovered: the phase after a record is
    ``rest``, begun on the record's last reading.

    In a phase that is pure Python, wall less CPU is the time the thread
    stood runnable or blocked without running: waiting for the
    interpreter, or for a core.  (Where the kernel accounts CPU time by
    the tick — 10 ms on the chip's host — ONE record's CPU reads 0 or a
    tick: read sums over many records.)  Each phase is also an
    :func:`annotation` ``<prefix>.<phase>``, so that a profiler session
    shows the thread's line tiled by its phases on the device trace's
    clock.  Host time only, no lock: the owner's ONE thread marks it (the
    first mark starts the clock, on the thread that makes it).

    A turn may end in two parts: :meth:`set_aside` takes what its phases
    took so far out of the clock, unwritten, and :meth:`resume` charges
    a later stretch of the thread's time to it (a flush whose scatter
    runs inside the next program): its record is written by
    :meth:`finish` once it is whole, and each instant still belongs to
    exactly one record."""

    __slots__ = ("_names", "_record", "_rest", "_cpu_of", "_wall", "_cpu",
                 "_phase", "_t", "_c", "_bridge")

    def __init__(self, prefix: str, phases: tuple, rest: str,
                 waits: tuple = ()):
        self._names = {p: f"{prefix}.{p}" for p in phases}
        self._record = f"{prefix}.turn"
        self._rest = rest
        self._cpu_of = tuple(p for p in phases if p not in waits)
        self._wall = dict.fromkeys(phases, 0.0)
        self._cpu = dict.fromkeys(phases, 0.0)
        self._phase = rest
        self._t: Optional[float] = None
        self._c = 0.0
        self._bridge = contextlib.nullcontext()

    def mark(self, phase: str) -> float:
        """The current phase ends and ``phase`` begins, here -> the wall
        reading they share (a :func:`now` reading)."""
        t, c = _now(), time.thread_time()
        if self._t is not None:
            self._wall[self._phase] += t - self._t
            self._cpu[self._phase] += c - self._c
        self._t, self._c, self._phase = t, c, phase
        self._bridge.__exit__(None, None, None)
        self._bridge = annotation(self._names[phase])
        self._bridge.__enter__()
        return t

    def finish(self, recorder: "SpanRecorder", turn: Optional[tuple] = None,
               **attrs) -> None:
        """The turn ends here: the current phase ends, ``rest`` begins,
        and the phases' times since the last record are written, with
        ``attrs``, as one event on ``recorder``.  ``turn``: one that
        :meth:`set_aside` returned, written instead; the clock runs on."""
        if turn is None:
            turn, _ = self.set_aside()
        wall, cpu = turn
        rec = dict(attrs)
        for phase, s in wall.items():
            rec[phase + "_ms"] = round(s * 1e3, 4)
        for phase in self._cpu_of:
            rec[phase + "_cpu_ms"] = round(cpu[phase] * 1e3, 4)
        rec["dur_ms"] = round(sum(wall.values()) * 1e3, 4)
        recorder.event(self._record, **rec)

    def set_aside(self) -> tuple:
        """The turn stops here, unwritten: the current phase ends,
        ``rest`` begins, and what every phase took since the last record
        leaves the clock -> ``(turn, the reading)``; ``turn`` is what
        :meth:`resume` charges and :meth:`finish` writes."""
        t = self.mark(self._rest)
        wall, cpu = self._wall, self._cpu
        self._wall = dict.fromkeys(wall, 0.0)
        self._cpu = dict.fromkeys(cpu, 0.0)
        return (wall, cpu), t

    @contextlib.contextmanager
    def resume(self, turn: tuple, phase: str):
        """Inside: the thread's time goes to ``turn`` (set aside earlier),
        from ``phase`` on — marks inside go to it too; after: the thread
        is back in the phase it left, charged to the turn of the moment."""
        was = self._phase
        self.mark(phase)
        mine = self._wall, self._cpu
        self._wall, self._cpu = turn
        try:
            yield
        finally:
            self.mark(was)
            self._wall, self._cpu = mine


# ---------------------------------------------------------------------------
# the runtime watcher: collector pauses, the interpreter's latency, stalls
# ---------------------------------------------------------------------------

# A collection that held the interpreter this long is worth a record: a
# young-generation pass is tens of microseconds, a stall is not.
GC_PAUSE_MIN_MS = 5.0
# The watcher asks to be woken this often, and sums up once a report.
BEAT_S = 0.02
BEAT_REPORT_S = 1.0
# A single wake-up this late is written at once, as ``runtime.stall``.
STALL_MIN_MS = 50.0


def _run_queue_s(fd: Optional[int]) -> float:
    """Seconds the thread behind ``fd`` (its ``schedstat``) has stood
    runnable without a core so far: the file's second number."""
    return 0.0 if fd is None else int(os.pread(fd, 128, 0).split()[1]) * 1e-9


class RuntimeWatch:
    """The process's one runtime watcher: a ``gc.callbacks`` hook and ONE
    daemon thread that asks to be woken every :data:`BEAT_S`.

    - ``runtime.gc`` (``generation``, ``dur_ms``, ``collected``,
      ``end_mono``) for every collection of :data:`GC_PAUSE_MIN_MS` or
      more.  A collection starts wherever an allocation triggers it —
      also inside the recorder's or the run context's critical section,
      on the thread that holds the lock — so the hook takes no lock: it
      reads the clock and appends to a deque, and the thread writes the
      events out at its next wake-up; ``end_mono`` is the pause's own
      end on the recorder's clock (the record's ``mono`` is when it was
      written).
    - ``runtime.beat``, one every :data:`BEAT_REPORT_S`: how LATE the
      thread woke (the clock at wake-up less the instant it asked for:
      what a thread that wants the interpreter, and a core, waits for
      them) over the ``beats`` of ``dur_ms``: ``late_mean_ms``,
      ``late_max_ms``; ``proc_cpu_ms``, the process's CPU time over the
      same stretch (all threads, XLA's too).
    - ``runtime.stall``, at once, for a single wake-up late by
      :data:`STALL_MIN_MS` or more: ``late_ms``, ``end_mono`` (the
      wake-up), ``proc_cpu_ms`` over the wait (the :data:`BEAT_S` asked
      for and the ``late_ms`` after) and, where the kernel keeps it,
      ``runq_ms``: how long of that wait this thread stood runnable with
      no core.  ``proc_cpu_ms`` near 0 says that the whole process stood
      still; near the wait's length, that one thread held the
      interpreter; above it, that threads outside the interpreter ran."""

    def __init__(self, recorder: Optional["SpanRecorder"] = None):
        self._recorder = recorder       # None = the process default
        self._t0: Optional[float] = None
        self._pauses: deque = deque()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="obs-runtime-watch")

    def _hook(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = _now()
            return
        t0, self._t0 = self._t0, None
        if t0 is None:
            return
        end = _now()
        if (end - t0) * 1e3 >= GC_PAUSE_MIN_MS:
            self._pauses.append((end, end - t0, info.get("generation"),
                                 info.get("collected")))

    def _rec(self) -> "SpanRecorder":
        return self._recorder if self._recorder is not None \
            else get_recorder()

    def _drain(self) -> None:
        while self._pauses:
            end, dur, generation, collected = self._pauses.popleft()
            self._rec().event("runtime.gc", generation=generation,
                              dur_ms=round(dur * 1e3, 4),
                              collected=collected, end_mono=round(end, 6))

    def _run(self) -> None:
        try:        # this thread's own; a kernel that keeps none has no file
            fd = os.open("/proc/thread-self/schedstat", os.O_RDONLY)
        except OSError:
            fd = None
        try:
            self._watch(fd)
        finally:
            if fd is not None:
                os.close(fd)

    def _watch(self, fd: Optional[int]) -> None:
        beats, late_sum, late_max = 0, 0.0, 0.0
        woke, cpu, queued = _now(), time.process_time(), _run_queue_s(fd)
        since, cpu_since = woke, cpu
        while True:
            asked = _now()      # what the thread did awake is not lateness
            if self._stop.wait(BEAT_S):
                return
            cpu_asked, queued_asked = cpu, queued
            woke, cpu, queued = _now(), time.process_time(), _run_queue_s(fd)
            late = max(0.0, woke - asked - BEAT_S)
            beats, late_sum = beats + 1, late_sum + late
            late_max = max(late_max, late)
            if late * 1e3 >= STALL_MIN_MS:
                more = ({} if fd is None else
                        {"runq_ms": round((queued - queued_asked) * 1e3, 4)})
                self._rec().event(
                    "runtime.stall", late_ms=round(late * 1e3, 4),
                    end_mono=round(woke, 6),
                    proc_cpu_ms=round((cpu - cpu_asked) * 1e3, 4), **more)
            self._drain()
            if woke - since >= BEAT_REPORT_S:
                self._rec().event(
                    "runtime.beat", beats=beats,
                    late_mean_ms=round(late_sum / beats * 1e3, 4),
                    late_max_ms=round(late_max * 1e3, 4),
                    proc_cpu_ms=round((cpu - cpu_since) * 1e3, 4),
                    dur_ms=round((woke - since) * 1e3, 4))
                beats, late_sum, late_max = 0, 0.0, 0.0
                since, cpu_since = woke, cpu

    def install(self) -> "RuntimeWatch":
        """Hook in and start the thread (once per object)."""
        gc.callbacks.append(self._hook)
        self._thread.start()
        return self

    def remove(self) -> None:
        """Unhook, stop the thread and write out what is left."""
        with contextlib.suppress(ValueError):
            gc.callbacks.remove(self._hook)
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout=5.0)
        self._drain()


# ---------------------------------------------------------------------------
# the process-default recorder
# ---------------------------------------------------------------------------

_default = SpanRecorder()           # ring-only until a run installs a file
_install_lock = make_lock("obs.spans.install")


def get_recorder() -> SpanRecorder:
    """The process-default recorder.  Library call sites (data pipeline
    watchdog, serving batcher/engine) record here; the train loop
    installs a file-backed recorder for the run's lifetime."""
    return _default


def install(rec: SpanRecorder) -> SpanRecorder:
    """Swap the process-default recorder; returns the previous one so
    the caller can restore it (the train loop does, in its finally)."""
    global _default
    with _install_lock:
        prev = _default
        _default = rec
        return prev
