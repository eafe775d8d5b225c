"""Live retrieval index: online ingest with generation-swapped corpus
shards.

``DeviceRetrievalIndex`` (serving/index.py) freezes its corpus at boot —
fine for serving an offline extraction, useless for the paper's end
state, where fresh clips must go live while the service runs.  This
module is the double-buffered twin:

- **ingest** (:meth:`LiveRetrievalIndex.add`) appends embedding rows to
  a host-side pending buffer and returns immediately — no device work,
  no lock shared with the query path beyond a pointer read;
- a **background builder thread** drains the buffer, concatenates the
  grown corpus on host, pads/shards it on-device under the dispatch
  discipline (``DEVICE_DISPATCH_LOCK`` + ``transfer_guard``), then
  performs an **atomic generation swap** — one reference assignment
  under ``_state_lock``.  Queries capture the generation reference once
  per call, so every query is answered by exactly ONE generation (old
  or new, never a torn mix), and the old generation's arrays are freed
  by GC once the last in-flight query drops them;
- **zero recompiles across swaps**: per-shard row capacity rides the
  same power-of-two rung rule as the engine's bucket ladder
  (:func:`shard_rung`), so a swap re-uses the compiled top-k executable
  until the corpus actually outgrows its rung.  Crossing a rung is a
  BUILDER event: the new shape is compiled and warmed on the builder
  thread *before* the swap publishes, and the recompile baseline is
  re-snapshotted there — the query path never compiles
  (:meth:`recompiles` stays 0; ``builder_compiles`` counts the
  boot-equivalent rung compiles honestly).

Failure discipline (ROBUSTNESS.md "Live index"): a build/swap failure
(the ``index.swap_raise`` fault site fires just before publication)
leaves the OLD generation serving, re-queues the drained rows at the
front of the pending buffer (ingest order preserved, nothing lost), and
the builder thread survives to retry — first on the next ingest/flush
signal, else on a bounded idle backoff.  ``index.ingest_hang`` wedges
an ``add`` caller without touching the query path.

Snapshot/restore ties into the ``milnce-export`` artifact family
(serving/export.py): :meth:`snapshot` writes the live generation's
corpus as ``corpus.npz`` (the exact array ``--serve.corpus_npz``
accepts) + ``index_meta.json``; :meth:`restore` boots a new index from
one, generation counter preserved — the round trip is bit-exact.
"""

from __future__ import annotations

import threading
import time
from typing import Optional, Sequence

import numpy as np

import jax

from milnce_tpu.analysis.lockrt import make_lock
from milnce_tpu.obs import metrics as obs_metrics
from milnce_tpu.obs import spans as obs_spans
from milnce_tpu.parallel.mesh import batch_sharding, replicated
from milnce_tpu.resilience import faults
from milnce_tpu.serving.batcher import pad_rows
from milnce_tpu.serving.engine import device_dispatch
from milnce_tpu.serving.export import (export_corpus_snapshot,
                                       load_corpus_snapshot)
from milnce_tpu.serving.index import make_topk_fn, shard_corpus

# Builder idle poll (bounds close() latency) and the backoff before a
# FAILED build is retried without a fresh ingest/flush signal.
_IDLE_POLL_S = 0.05
_RETRY_BACKOFF_S = 0.25


def shard_rung(size: int, n_data: int, k: int, floor: int = 0) -> int:
    """Per-shard row capacity for a ``size``-row corpus: the smallest
    power of two >= max(ceil(size / n_data), k, floor, 1).

    The serving twin of ``engine.bucket_ladder``'s rung rule: corpus
    growth within a rung swaps generations at IDENTICAL padded shapes
    (same executable, zero recompiles); only crossing a rung — a
    doubling, so O(log corpus) times ever — builds a new shape."""
    need = max(-(-size // n_data) if size else 1, k, int(floor), 1)
    rung = 1
    while rung < need:
        rung *= 2
    return rung


def recommended_min_shard_rows(corpus_rows: int, n_data: int,
                               headroom: int = 2) -> int:
    """``--serve.index_min_shard_rows`` sizing rule for a corpus that is
    expected to GROW to ~``corpus_rows``: the rung that fits
    ``headroom`` x the per-device share, so ingest reaches the target
    size (and then some) without ever crossing a rung — zero index
    recompiles over the corpus's whole planned life.

    HowTo100M scale: ~1.2M videos over an 8-way data axis with the
    default 2x headroom lands on 524288 (= 2**19) rows/shard — 4M rows
    of pre-provisioned capacity, ~2 GiB/device of f32 corpus at
    D=512."""
    if corpus_rows <= 0:
        raise ValueError("corpus_rows must be positive")
    if n_data <= 0:
        raise ValueError("n_data must be positive")
    if headroom < 1:
        raise ValueError("headroom must be >= 1")
    return shard_rung(int(corpus_rows) * int(headroom), n_data, 1)


class _Generation:
    """One immutable published corpus generation.  Everything here is
    written once by the builder (or ``__init__``) before publication and
    only ever read afterwards — the atomic-swap contract."""

    __slots__ = ("gen", "host", "size", "rows", "corpus", "valid",
                 "built_mono")

    def __init__(self, gen: int, host: np.ndarray, rows: int,
                 corpus, valid):
        self.gen = int(gen)
        self.host = host                 # (size, D) f32 — snapshot/rebuild
        self.size = int(host.shape[0])
        self.rows = int(rows)            # per-shard capacity (the rung)
        self.corpus = corpus             # device, (rows * n_data, D)
        self.valid = valid               # device, (n_data,) int32
        self.built_mono = time.monotonic()


class LiveRetrievalIndex:
    """Generation-swapped sharded corpus + fixed-k jitted top-k.

    Query surface is a superset of :class:`DeviceRetrievalIndex`
    (``topk`` / ``bucket_for`` / ``stats`` / ``recompiles`` /
    ``topk_program`` / ``query_sharding``), plus the live surface:
    ``add`` / ``flush`` / ``topk_with_gen`` / ``snapshot`` /
    ``restore``.  ``embeddings=None`` boots an EMPTY index (``dim``
    required); queries refuse until the corpus holds at least ``k``
    rows, but ingest works from the first second.
    """

    def __init__(self, mesh, embeddings: Optional[np.ndarray] = None, *,
                 k: int = 10, query_buckets: Sequence[int] = (8,),
                 data_axis: str = "data", dim: Optional[int] = None,
                 min_shard_rows: int = 0, generation: int = 0,
                 precompile: bool = True,
                 registry: Optional[obs_metrics.MetricsRegistry] = None,
                 recorder: Optional[obs_spans.SpanRecorder] = None):
        if embeddings is None:
            if dim is None:
                raise ValueError("an empty live index needs dim= (the "
                                 "embedding width ingest rows will have)")
            emb = np.zeros((0, int(dim)), np.float32)
        else:
            emb = np.ascontiguousarray(embeddings, dtype=np.float32)
            if emb.ndim != 2:
                raise ValueError(f"expected (N, D) embeddings, "
                                 f"got {emb.shape}")
        self.dim = int(emb.shape[1])
        self.k = int(k)
        if self.k < 1:
            raise ValueError(f"k={k} < 1")
        self.query_buckets = tuple(sorted(int(b) for b in query_buckets))
        self.data_axis = data_axis
        # geometry follows the DATA axis extent (index.py's 2-D mesh
        # rule: P(data) shards rows over data, replicates over model)
        self._n_data = int(mesh.shape[data_axis])
        self._min_shard_rows = int(min_shard_rows)
        self._query_sh = replicated(mesh)
        self._corpus_sh = batch_sharding(mesh, data_axis)
        self._fn = make_topk_fn(mesh, data_axis, self.k)
        self._recorder = recorder
        reg = registry if registry is not None \
            else obs_metrics.MetricsRegistry()
        self._m_ingested = reg.counter(
            "milnce_serve_index_ingested_rows_total",
            "embedding rows accepted into the live-index pending buffer")
        self._m_swaps = reg.counter(
            "milnce_serve_index_swaps_total",
            "generation swaps published (the corpus grew atomically)")
        self._m_swap_failures = reg.counter(
            "milnce_serve_index_swap_failures_total",
            "builds/swaps that failed (old generation kept serving, "
            "rows re-queued)")
        self._m_builder_compiles = reg.counter(
            "milnce_serve_index_builder_compiles_total",
            "rung-crossing compiles performed on the builder thread "
            "(boot-equivalent; the query path never compiles)")
        reg.gauge("milnce_serve_index_generation",
                  "live-index generation counter",
                  fn=lambda: float(self.stats()["generation"]))
        reg.gauge("milnce_serve_index_pending_rows",
                  "ingested rows not yet swapped live",
                  fn=lambda: float(self.stats()["pending_rows"]))
        reg.gauge("milnce_serve_index_last_swap_age_seconds",
                  "seconds since the last generation swap",
                  fn=lambda: float(self.stats()["last_swap_age_s"]))
        # One lock for all mutable host state: generation pointer,
        # pending buffer, call/compile accounting.  NEVER held across
        # device work, sleeps, or metric calls — the builder and the
        # query path each take it for pointer/bookkeeping flips only.
        self._state_lock = make_lock("serving.live_index.state")
        self._pending: list[np.ndarray] = []   # guarded-by: _state_lock
        self._pending_rows = 0                 # guarded-by: _state_lock
        self._ingested_total = 0               # guarded-by: _state_lock
        self._calls = 0                        # guarded-by: _state_lock
        self._baseline_cache = None            # guarded-by: _state_lock
        self._swaps = 0                        # guarded-by: _state_lock
        self._swap_failures = 0                # guarded-by: _state_lock
        self._last_attempt = 0.0               # guarded-by: _state_lock
        self._warmed_rungs: set = set()        # guarded-by: _state_lock
        self._warming_recompiles = None        # guarded-by: _state_lock
        # the published generation: written only under _state_lock (one
        # reference assignment — the atomic swap); readers take the lock
        # for the pointer read and hold the REFERENCE, not the lock,
        # through device work
        self._gen = self._make_generation(     # guarded-by: _state_lock
            int(generation), emb)
        self._boot_size = self._gen.size
        self._work = threading.Event()
        self._closed = threading.Event()
        self._builder = threading.Thread(target=self._builder_loop,
                                         daemon=True,
                                         name="live-index-builder")
        if precompile:
            self.warmup()
        self._builder.start()

    # ---- geometry / program construction ---------------------------------

    def _make_generation(self, gen: int, host: np.ndarray) -> _Generation:
        """Pad + shard ``host`` onto the devices at its rung.  Device
        transfers run under the dispatch discipline — the same lock and
        transfer guard as every other serving device interaction."""
        rows = shard_rung(host.shape[0], self._n_data, self.k,
                          self._min_shard_rows)
        corpus, valid = shard_corpus(host, self._n_data, rows)
        with device_dispatch("index.upload", recorder=self._recorder,
                             rows=host.shape[0], bucket=rows) as hold:
            with hold.phase("put"):
                corpus_d = jax.device_put(corpus, self._corpus_sh)
                valid_d = jax.device_put(valid, self._corpus_sh)
        return _Generation(gen, host, rows, corpus_d, valid_d)

    def _dispatch(self, g: _Generation, q_padded: np.ndarray, n: int):
        """Top-k of the ``n`` live rows of ``q_padded`` over ``g``."""
        with device_dispatch("index.topk", recorder=self._recorder,
                             rows=n, bucket=q_padded.shape[0]) as hold:
            scores, idx = hold.round_trip(self._fn, q_padded, self._query_sh,
                                          g.corpus, g.valid)
        return np.asarray(scores), np.asarray(idx)

    def _warm_rung(self, g: _Generation) -> None:
        """Compile + execute the top-k program for every query bucket at
        ``g``'s shape, then re-snapshot the recompile baseline: rung
        compiles are boot-equivalent builder work, never a query-path
        recompile (they are counted separately for honesty).

        While the warm is in flight the jit cache grows BEFORE the
        baseline catches up, so :meth:`recompiles` answers with the
        pre-warm value for the duration — a /healthz poll landing inside
        a multi-second rung compile must not read the builder's own
        compiles as query-path recompiles."""
        with self._state_lock:
            warmed = g.rows in self._warmed_rungs
        if warmed:
            return
        pre = self.recompiles()
        with self._state_lock:
            self._warming_recompiles = pre
        try:
            for b in self.query_buckets:
                self._dispatch(g, np.zeros((b, self.dim), np.float32), b)
            self._m_builder_compiles.inc()
            size = getattr(self._fn, "_cache_size", None)
            baseline = int(size()) if size is not None else None
            with self._state_lock:
                self._warmed_rungs.add(g.rows)
                self._baseline_cache = baseline
        finally:
            with self._state_lock:
                self._warming_recompiles = None

    # ---- query path ------------------------------------------------------

    def bucket_for(self, n: int) -> int:
        for b in self.query_buckets:
            if n <= b:
                return b
        raise ValueError(f"{n} queries exceeds the top query bucket "
                         f"{self.query_buckets[-1]}")

    def topk_with_gen(self, queries: np.ndarray
                      ) -> tuple[np.ndarray, np.ndarray, int]:
        """(n, D) query embeddings -> ((n, k) scores, (n, k) corpus row
        indices, generation).  The generation reference is captured ONCE
        — a swap completing mid-query cannot tear the answer, and the
        returned generation is exactly the corpus the ranking is over."""
        q = np.ascontiguousarray(queries, dtype=np.float32)
        if q.ndim != 2 or q.shape[1] != self.dim:
            raise ValueError(f"expected (n, {self.dim}) queries, "
                             f"got {q.shape}")
        with self._state_lock:
            g = self._gen
        if g.size < self.k:
            raise ValueError(f"corpus holds {g.size} rows < k={self.k} — "
                             "ingest more before querying")
        n = q.shape[0]
        scores, idx = self._dispatch(g, pad_rows(q, self.bucket_for(n)), n)
        with self._state_lock:
            self._calls += 1
        return scores[:n], idx[:n], g.gen

    def topk(self, queries: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """DeviceRetrievalIndex-compatible surface (no generation)."""
        scores, idx, _ = self.topk_with_gen(queries)
        return scores, idx

    def topk_program(self) -> tuple:
        """``(jitted_fn, (corpus, valid))`` of the LIVE generation — the
        analysis surface (trace invariants, Pass 4 planner), same
        contract as ``DeviceRetrievalIndex.topk_program``."""
        with self._state_lock:
            g = self._gen
        return self._fn, (g.corpus, g.valid)

    @property
    def query_sharding(self):
        return self._query_sh

    @property
    def size(self) -> int:
        """LIVE corpus rows (pending ingest not yet included)."""
        with self._state_lock:
            return self._gen.size

    @property
    def generation(self) -> int:
        with self._state_lock:
            return self._gen.gen

    # ---- ingest path -----------------------------------------------------

    def add(self, embeddings: np.ndarray) -> dict:
        """Queue (n, D) embedding rows for the next generation; returns
        ``{"pending_rows", "generation", "target_rows"}`` where
        ``target_rows`` is the corpus size once everything queued so far
        is live (the :meth:`flush` wait target).  Host-only — the
        builder does the device work."""
        rows = np.ascontiguousarray(embeddings, dtype=np.float32)
        if rows.ndim != 2 or rows.shape[1] != self.dim:
            raise ValueError(f"expected (n, {self.dim}) embeddings, "
                             f"got {rows.shape}")
        if rows.shape[0] < 1:
            raise ValueError("empty ingest batch")
        if self._closed.is_set():
            raise RuntimeError("live index is closed")
        # fault site: a wedged ingest caller (slow storage, stuck embed
        # upstream) — must never touch the query path's locks
        faults.maybe_hang("index.ingest_hang")
        n = rows.shape[0]
        with self._state_lock:
            self._pending.append(rows)
            self._pending_rows += n
            self._ingested_total += n
            out = {"pending_rows": self._pending_rows,
                   "generation": self._gen.gen,
                   "target_rows": self._boot_size + self._ingested_total}
        self._m_ingested.inc(n)
        self._work.set()
        return out

    def flush(self, timeout: float = 10.0) -> bool:
        """Block until every row ingested BEFORE this call is live (a
        generation containing them has been published), or ``timeout``
        expires — False means rows are still pending (e.g. the builder
        is riding out injected swap failures), never an exception."""
        with self._state_lock:
            target = self._boot_size + self._ingested_total
        self._work.set()
        deadline = time.monotonic() + timeout  # graftlint: disable=GL005(host-side timeout bookkeeping for the flush wait loop — deliberately wall time, not a device-timing delta; nothing here is dispatched)
        while time.monotonic() < deadline:
            with self._state_lock:
                live = self._gen.size
            if live >= target:
                return True
            if self._closed.is_set():
                return False
            time.sleep(0.005)
        return False

    # ---- builder thread --------------------------------------------------

    def _builder_loop(self) -> None:
        while not self._closed.is_set():
            signaled = self._work.wait(timeout=_IDLE_POLL_S)
            if self._closed.is_set():
                return
            if signaled:
                self._work.clear()
            else:
                # idle tick: retry a previously-failed build, backed off
                with self._state_lock:
                    retry = (self._pending_rows > 0 and
                             time.monotonic() - self._last_attempt
                             > _RETRY_BACKOFF_S)
                if not retry:
                    continue
            self._build_once()

    def _build_once(self) -> None:
        with self._state_lock:
            if not self._pending:
                return
            pending, self._pending = self._pending, []
            moved = self._pending_rows
            self._pending_rows = 0
            base = self._gen
            self._last_attempt = time.monotonic()
        rec = self._recorder if self._recorder is not None \
            else obs_spans.get_recorder()
        try:
            with rec.span("index.build", rows=moved, base_gen=base.gen):
                host = np.concatenate([base.host] + pending) \
                    if base.size else np.concatenate(pending)
                g = self._make_generation(base.gen + 1, host)
                self._warm_rung(g)
                # fault site: the publication step itself fails (a bad
                # device transfer, poisoned executable) — must leave the
                # old generation serving and the builder alive
                faults.maybe_raise("index.swap_raise")
                with self._state_lock:
                    self._gen = g                 # THE atomic swap
                    self._swaps += 1
            self._m_swaps.inc()
            rec.event("index.swap", generation=g.gen, size=g.size,
                      shard_rows=g.rows)
        except Exception as exc:
            # failed build/swap: re-queue the drained rows at the FRONT
            # (ingest order preserved for the retry); the old generation
            # keeps serving and this thread keeps running
            with self._state_lock:
                self._pending = pending + self._pending
                self._pending_rows += moved
                self._swap_failures += 1
            self._m_swap_failures.inc()
            rec.event("index.swap_fail", base_gen=base.gen, rows=moved,
                      error=type(exc).__name__)

    # ---- warmup + recompile accounting -----------------------------------

    def warmup(self) -> None:
        with self._state_lock:
            g = self._gen
        self._warm_rung(g)

    def recompiles(self) -> int:
        """Query-path jit-cache growth since the last builder/boot
        warmup — 0 in a healthy steady state ACROSS generation swaps
        (rung compiles re-baseline on the builder thread and count on
        ``builder_compiles`` instead).  -1 without cache introspection.
        While a rung warm is in flight, answers the pre-warm value (the
        builder's boot-equivalent compiles are not query recompiles)."""
        with self._state_lock:
            if self._warming_recompiles is not None:
                return self._warming_recompiles
            baseline = self._baseline_cache
        if baseline is None:
            return -1
        size = getattr(self._fn, "_cache_size", None)
        if size is None:
            return -1
        return max(0, int(size()) - baseline)

    # ---- snapshot / restore ----------------------------------------------

    def snapshot(self, out_dir: str) -> str:
        """Write the LIVE generation's corpus as a ``milnce-export``
        family artifact (corpus.npz + index_meta.json).  Pending ingest
        rows are not included — :meth:`flush` first to capture them."""
        with self._state_lock:
            g = self._gen
        return export_corpus_snapshot(out_dir, g.host, generation=g.gen,
                                      k=self.k, source="live_index")

    @classmethod
    def restore(cls, snap_dir: str, mesh, **kwargs) -> "LiveRetrievalIndex":
        """Boot a live index from a :meth:`snapshot` directory —
        generation counter preserved, corpus bit-exact."""
        meta, emb = load_corpus_snapshot(snap_dir)
        kwargs.setdefault("k", meta["k"])
        kwargs.setdefault("generation", meta["generation"])
        return cls(mesh, emb, **kwargs)

    # ---- lifecycle / observability ---------------------------------------

    def close(self, timeout: float = 5.0) -> None:
        self._closed.set()
        self._work.set()
        self._builder.join(timeout)

    def stats(self) -> dict:
        """Superset of ``DeviceRetrievalIndex.stats()`` — every frozen
        key byte-compatible, the live keys additive (the ``/healthz``
        ``index`` section contract)."""
        now = time.monotonic()
        with self._state_lock:
            g = self._gen
            out = {
                "size": g.size, "dim": self.dim, "k": self.k,
                "query_buckets": list(self.query_buckets),
                "calls": self._calls,
                "generation": g.gen,
                "pending_rows": self._pending_rows,
                "ingested_rows": self._ingested_total,
                "swaps": self._swaps,
                "swap_failures": self._swap_failures,
                "shard_rows": g.rows,
                "capacity": g.rows * self._n_data,
                "last_swap_age_s": round(now - g.built_mono, 3),
            }
        out["recompiles"] = self.recompiles()
        out["builder_alive"] = self._builder.is_alive()
        return out
