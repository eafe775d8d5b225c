"""Device-resident retrieval index: corpus embeddings sharded over the
mesh data axis, one jitted program that scans each shard and ranks it as
it reads (``ops/scan_topk.py``), then merges the shards' winners.

Offline eval materializes the full T x V similarity matrix on host
(eval/retrieval.py) — fine for a 1k-video benchmark, hopeless for a
served corpus: at production scale the corpus embedding table is the
largest tensor in the system and must live ON the devices, sharded,
with only (Q, k) winners ever crossing back to host.

The retrieval program (one jitted shard_map, fixed shapes, pinned
collectives — see the ``serve_index_topk`` trace invariant):

1. each shard streams its local corpus rows through VMEM a tile at a
   time in ONE Pallas kernel (``ops/scan_topk.scan_topk``): the tile is
   scored against the replicated query block on the MXU, pad rows are
   masked to -inf, and each query keeps a running LOCAL top-k, ties to
   the lower row — the (Q, R_local) scores never reach HBM; the local
   rows are shifted to global ones via ``axis_index``, so per shard only
   (Q, k) survives;
2. the per-shard candidate lists ride ONE all_gather each for scores
   and indices (2 total, pinned), and a final ``lax.top_k`` over the
   ``n_dev * k`` candidates is exact — every true global winner is
   necessarily some shard's local winner.

Query batches are padded to a fixed bucket ladder exactly like the
embed entries (pad queries produce garbage rows that are dropped on
unpad; they never affect real rows), so the whole serve path —
embed + retrieve — runs zero recompiles after boot.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from milnce_tpu.analysis.lockrt import make_lock
from milnce_tpu.obs import spans as obs_spans
from milnce_tpu.ops.scan_topk import scan_topk
from milnce_tpu.parallel.mesh import batch_sharding, replicated
from milnce_tpu.serving.batcher import pad_rows
from milnce_tpu.serving.engine import device_dispatch


def make_topk_fn(mesh: Mesh, data_axis: str, k: int):
    """The jitted sharded top-k program (the ``serve_index_topk`` trace
    invariant's subject): each data shard scans its local corpus rows
    against the replicated query block and keeps a LOCAL top-k in one
    Pallas kernel (``ops/scan_topk.scan_topk``: the parent's masked
    product + ``lax.top_k`` without the (Q, R_local) scores, ties to the
    lower row), and the per-shard (Q, k) candidate lists ride ONE
    all_gather each for scores and indices before an exact global top-k.
    Shared by the frozen :class:`DeviceRetrievalIndex` and the
    generation-swapped
    :class:`~milnce_tpu.serving.live_index.LiveRetrievalIndex` — one
    program, one set of pinned collectives, however the corpus is
    managed.  The program's name, ``local_topk``, is what the benchmark's
    ``index_scan_roofline`` finds it by in a trace (``jit_local_topk``)."""

    def local_topk(corpus_l, valid_l, queries):
        s, i = scan_topk(queries, corpus_l, valid_l, k)  # local winners
        gidx = i + lax.axis_index(data_axis) * corpus_l.shape[0]
        s_all = lax.all_gather(s, data_axis, axis=1, tiled=True)
        i_all = lax.all_gather(gidx, data_axis, axis=1, tiled=True)
        s_top, j = lax.top_k(s_all, k)                   # exact global
        return s_top, jnp.take_along_axis(i_all, j, axis=1)

    return jax.jit(jax.shard_map(
        local_topk, mesh=mesh,
        in_specs=(P(data_axis), P(data_axis), P()),
        out_specs=(P(), P()), check_vma=False))


def shard_corpus(emb: np.ndarray, n_data: int, rows: int
                 ) -> tuple[np.ndarray, np.ndarray]:
    """Pad ``(size, D)`` embeddings to ``rows`` rows per data shard ->
    (``(rows * n_data, D)`` padded corpus, ``(n_data,)`` int32 per-shard
    valid-row counts).  Pad rows are zeros and masked to -inf inside the
    top-k program, so they can never be retrieved."""
    size, dim = emb.shape
    corpus = np.zeros((rows * n_data, dim), np.float32)
    corpus[:size] = emb
    valid = np.asarray(
        [max(0, min(size, (s + 1) * rows) - s * rows)
         for s in range(n_data)], np.int32)
    return corpus, valid


class DeviceRetrievalIndex:
    """Immutable sharded corpus + fixed-k jitted top-k retrieval.

    - ``embeddings``: (N, D) float32 video-corpus embeddings (built from
      ``InferenceEngine.embed_video`` or an offline extraction);
    - ``k``: retrieval depth, static in the traced program;
    - ``query_buckets``: the query-batch ladder to pre-trace (share the
      engine's so batcher output feeds straight through).
    """

    def __init__(self, mesh: Mesh, embeddings: np.ndarray, *, k: int = 10,
                 query_buckets: Sequence[int] = (8,), data_axis: str = "data",
                 precompile: bool = True):
        emb = np.ascontiguousarray(embeddings, dtype=np.float32)
        if emb.ndim != 2:
            raise ValueError(f"expected (N, D) embeddings, got {emb.shape}")
        self.size, self.dim = emb.shape
        self.k = int(k)
        if not 1 <= self.k <= self.size:
            raise ValueError(f"k={k} outside [1, corpus size {self.size}]")
        self.query_buckets = tuple(sorted(int(b) for b in query_buckets))
        self.data_axis = data_axis
        # geometry follows the DATA axis extent, not the total device
        # count: P(data) shards rows over data and replicates over any
        # model axis, so each data shard holds rows (not rows/model) —
        # sizing by the product would mis-mask most of the corpus on a
        # (data, model) mesh
        n_data = int(mesh.shape[data_axis])

        # Pad the corpus so rows split evenly AND every shard holds at
        # least k rows (a shard's top-k needs k <= local extent).
        rows = max(-(-self.size // n_data), self.k)
        self._query_sh = replicated(mesh)
        self._fn = make_topk_fn(mesh, data_axis, self.k)
        # call accounting is hit straight off concurrent request threads
        # — its own lock, never the dispatch lock (graftlint GL010: the
        # bare `_calls += 1` here lost increments under contention)
        self._stats_lock = make_lock("serving.index.stats")
        self._calls = 0
        self._baseline_cache = None
        # query rung -> the pass's device time, ms (warm-up; empty
        # before): what the device worker's turn order compares
        self.device_ms: dict[int, float] = {}
        # the boot log's split of the build (the live index records a
        # span of the same name per generation)
        with obs_spans.get_recorder().span("index.build",
                                           rows=self.size) as span:
            t0 = obs_spans.now()
            corpus, valid = shard_corpus(emb, n_data, rows)
            span["shard_ms"] = obs_spans.ms_since(t0)
            t0 = obs_spans.now()
            sh_rows = batch_sharding(mesh, data_axis)
            self._corpus = jax.device_put(corpus, sh_rows)   # device-resident
            self._valid = jax.device_put(valid, sh_rows)
            # host time of the two puts: they return before the copy is
            # done, and the first scan of the warm-up waits for it
            span["upload_ms"] = obs_spans.ms_since(t0)
            t0 = obs_spans.now()
            if precompile:
                self.warmup()
            span["warmup_ms"] = obs_spans.ms_since(t0)

    # ---- query path ------------------------------------------------------

    def bucket_for(self, n: int) -> int:
        for b in self.query_buckets:
            if n <= b:
                return b
        raise ValueError(f"{n} queries exceeds the top query bucket "
                         f"{self.query_buckets[-1]}")

    def topk(self, queries: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """(n, D) query embeddings -> ((n, k) scores, (n, k) corpus row
        indices), ranked best-first.  Ties broken by lower index, the
        same order ``np.argsort(-sim)`` yields on distinct scores."""
        q = np.ascontiguousarray(queries, dtype=np.float32)
        if q.ndim != 2 or q.shape[1] != self.dim:
            raise ValueError(f"expected (n, {self.dim}) queries, got "
                             f"{q.shape}")
        n = q.shape[0]
        bucket = self.bucket_for(n)
        q = pad_rows(q, bucket)
        # serialized dispatch: see DEVICE_DISPATCH_LOCK in engine.py —
        # index queries come straight off request threads
        with device_dispatch("index.topk", rows=n, bucket=bucket) as hold:
            scores, idx = hold.round_trip(self._fn, q, self._query_sh,
                                          self._corpus, self._valid)
        with self._stats_lock:
            self._calls += 1
        return np.asarray(scores)[:n], np.asarray(idx)[:n]

    def topk_program(self) -> tuple:
        """``(jitted_fn, (corpus, valid))`` — the compiled retrieval
        program plus its committed operand arrays, the supported surface
        for the analysis passes (trace invariants pin its collectives,
        the Pass 4 planner walks its jaxpr) instead of reaching into
        ``_fn``/``_corpus``/``_valid``.  Callers append a query batch
        committed to :attr:`query_sharding`."""
        return self._fn, (self._corpus, self._valid)

    @property
    def query_sharding(self):
        """The replicated sharding query batches must be committed to
        before calling the program from :meth:`topk_program` directly
        (an uncommitted host array would key a separate jit-cache
        entry)."""
        return self._query_sh

    # ---- warmup + observability -----------------------------------------

    def warmup(self) -> None:
        """Every query rung compiled and run, then run once more timed on
        the device (:attr:`device_ms`)."""
        for b in self.query_buckets:
            self.topk(np.zeros((b, self.dim), np.float32))
        timed = {}
        for b in self.query_buckets:
            with device_dispatch("index.topk", rows=0, bucket=b) as hold:
                timed[b] = hold.device_time(
                    self._fn, np.zeros((b, self.dim), np.float32),
                    self._query_sh, self._corpus, self._valid)
        self.device_ms = timed
        size = getattr(self._fn, "_cache_size", None)
        baseline = int(size()) if size is not None else None
        with self._stats_lock:
            self._baseline_cache = baseline

    def recompiles(self) -> int:
        with self._stats_lock:
            baseline = self._baseline_cache
        if baseline is None:
            return -1
        size = getattr(self._fn, "_cache_size", None)
        if size is None:
            return -1
        return max(0, int(size()) - baseline)

    def stats(self) -> dict:
        with self._stats_lock:
            calls = self._calls
        return {"size": self.size, "dim": self.dim, "k": self.k,
                "query_buckets": list(self.query_buckets),
                "calls": calls, "recompiles": self.recompiles()}
