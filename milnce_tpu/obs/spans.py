"""Monotonic-clock span/event recorder: RUN_EVENTS.jsonl + in-memory ring.

The span taxonomy (OBSERVABILITY.md) covers the moments that explain a
run after the fact: ``step`` (hot-loop dispatch), ``decode.timeout`` /
``decode.retry`` (watchdog escalations), ``batcher.flush`` (serving
micro-batches), ``ladder.warmup`` (engine pre-trace sweep),
``ckpt.save`` / ``ckpt.restore`` / ``rollback`` (checkpoint lifecycle),
``display`` (the train loop's cadenced fetch), ``query`` / ``dispatch``
(a served call and each hold of the device lock), ``runtime.gc`` (a
collector pause).

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
# collector pauses
# ---------------------------------------------------------------------------

# A collection that held the interpreter this long is worth a record: a
# young-generation pass is tens of microseconds, a stall is not.
GC_PAUSE_MIN_MS = 5.0
_GC_DRAIN_S = 0.05


class GcPauseEvents:
    """``runtime.gc`` events (``generation``, ``dur_ms``, ``collected``,
    ``end_mono``) for every collection of :data:`GC_PAUSE_MIN_MS` or
    more, from a ``gc.callbacks`` hook.

    A collection starts wherever an allocation triggers it — also inside
    the recorder's or the run context's critical section, on the thread
    that holds the lock — so the hook takes no lock: it reads the clock
    and appends to a deque.  A daemon thread writes the events out at
    most :data:`_GC_DRAIN_S` later; ``end_mono`` is the pause's own end
    on the recorder's clock (the record's ``mono`` is when it was
    written)."""

    def __init__(self, recorder: Optional["SpanRecorder"] = None):
        self._recorder = recorder       # None = the process default
        self._t0: Optional[float] = None
        self._pauses: deque = deque()
        self._stop = threading.Event()
        self._writer = threading.Thread(target=self._run, daemon=True,
                                        name="obs-gc-pauses")

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

    def _drain(self) -> None:
        rec = self._recorder if self._recorder is not None \
            else get_recorder()
        while self._pauses:
            end, dur, generation, collected = self._pauses.popleft()
            rec.event("runtime.gc", generation=generation,
                      dur_ms=round(dur * 1e3, 4), collected=collected,
                      end_mono=round(end, 6))

    def _run(self) -> None:
        while not self._stop.wait(_GC_DRAIN_S):
            self._drain()

    def install(self) -> "GcPauseEvents":
        """Hook in and start the writer (once per object)."""
        gc.callbacks.append(self._hook)
        self._writer.start()
        return self

    def remove(self) -> None:
        """Unhook, stop the writer and write out what is left."""
        with contextlib.suppress(ValueError):
            gc.callbacks.remove(self._hook)
        self._stop.set()
        if self._writer.is_alive():
            self._writer.join(timeout=5.0)
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
