"""Frozen-param inference engine: pre-traced bucket ladder, transfer-
guarded steady state.

What JAX/XLA rewards at serve time is exactly what Neodragon and
On-device Sora (PAPERS.md) report for video-model serving: fixed-shape
pre-traced execution and aggressive reuse — never a runtime recompile,
never an accidental host round-trip.  This engine packages the repo's
existing embed towers (train/step.py ``make_text_embed_fn`` /
``make_video_embed_fn`` — the same jitted shard_map programs offline
eval uses, so served numbers ARE eval numbers) behind that discipline:

- **bucket ladder**: batch entries exist only at a power-of-two ladder
  of batch sizes (each a multiple of the mesh's data-axis extent, so
  every bucket shards).  Requests are padded UP to the smallest bucket
  that fits; the jit cache therefore holds exactly
  ``len(buckets) x 2`` executables forever.
- **pre-trace at startup**: every (entry, bucket) pair is compiled and
  executed once in ``__init__`` — first-request latency is steady-state
  latency, and a compile storm can only happen where it belongs: at
  boot, visibly.
- **steady state under ``jax.transfer_guard("disallow")``**: inputs go
  up via explicit ``device_put`` against the batch sharding, results
  come back via explicit ``device_get``; anything else — a smuggled
  implicit H2D in a future edit — raises instead of silently stalling
  the dispatch pipeline (same contract as the train loop,
  tests/test_transfer_guard.py).
- **recompile accounting**: jit cache sizes are snapshotted after the
  warmup sweep; :meth:`recompiles` must stay 0 for the life of the
  process (pinned by the ``serve_embed_ladder`` trace invariant and
  surfaced by the service health endpoint).

Frozen params: the engine holds ``{'params', 'batch_stats'}`` only (no
optimizer state — see serving/export.py), replicated onto the mesh once
at construction, optionally cast to bf16 for MXU-rate inference.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from milnce_tpu.analysis.lockrt import make_lock
from milnce_tpu.obs import spans as obs_spans
from milnce_tpu.parallel.mesh import batch_sharding, replicated
from milnce_tpu.resilience import faults
from milnce_tpu.serving.batcher import pad_rows
from milnce_tpu.train.step import make_text_embed_fn, make_video_embed_fn


class ReplicaDead(RuntimeError):
    """The engine has been force-killed (``serve.replica_dead`` fault or
    :meth:`InferenceEngine.kill`) — every dispatch fails instantly until
    the process restarts.  The replica pool treats this as a permanent
    condition: the replica quarantines and its probes keep failing."""

# One device-dispatch queue per process, shared by every serving
# component that executes on the mesh (engine entries AND index.topk).
# Two reasons, one per backend: the multi-device XLA:CPU client
# DEADLOCKS when multi-device executions + transfers are issued
# concurrently from several host threads (observed: N request threads
# wedged in device_get while the batcher worker wedges in execute); and
# on TPU, concurrent host threads racing enqueues just interleave into
# the single per-device execution queue anyway — serialized dispatch is
# the semantics the hardware gives you, made explicit and deadlock-free.
# Request-level concurrency belongs ABOVE this lock, in the batcher.
# Created through make_lock so MILNCE_LOCK_SANITIZE=1 (set before
# import) swaps in the order-checking SanitizedLock; the "dispatch" in
# its name is what exempts device work under it from graftlint GL012.
DEVICE_DISPATCH_LOCK = make_lock("serving.device_dispatch")

# Host work a thread hands to its next round trip (:func:`defer`): the
# device worker's held scatter of a pass, run while the next program
# computes.  Thread-local, so whatever stands between the worker and the
# hold (a wrapper that replaces ``index.topk`` on the instance) carries
# it without knowing.
_DEFERRED = threading.local()


def defer(work, rows: int) -> None:
    """The calling thread's next :meth:`_Hold.round_trip` runs
    ``work(site)`` between its program's enqueue and the blocking fetch
    of its result; ``rows``: the rows it answers (``overlap_rows``).  One
    at a time: the owner takes back what no round trip ran
    (:func:`take_deferred`) and runs it itself."""
    _DEFERRED.work = (work, int(rows))


def take_deferred():
    """-> ``(work, rows)`` that the calling thread deferred and no round
    trip has run, now taken back; None when there is none."""
    work = getattr(_DEFERRED, "work", None)
    _DEFERRED.work = None
    return work


class _Hold:
    """What a site gets from :func:`device_dispatch`: :meth:`phase`
    times one leg of the hold into the ``dispatch`` record."""

    __slots__ = ("site", "record")

    def __init__(self, site: str, record: dict):
        self.site, self.record = site, record

    @contextlib.contextmanager
    def phase(self, name: str):
        """``put`` / ``call`` / ``get``: ``<name>_ms`` on the record, and
        a ``<site>.<name>`` annotation for a running profiler."""
        t0 = obs_spans.now()
        try:
            with obs_spans.annotation(f"{self.site}.{name}"):
                yield
        finally:
            self.record[f"{name}_ms"] = obs_spans.ms_since(t0)

    def round_trip(self, fn, rows, sharding, *resident):
        """``fn(*resident, rows)`` as every query site runs it, each leg
        a phase: the explicit ``device_put`` of the host ``rows``, the
        jitted call's return (the enqueue), the blocking ``device_get``
        of its result.  Written out, not three :meth:`phase` blocks: this
        is host time inside the hold, with the device idle.

        Between the enqueue and the fetch, the work this thread deferred
        (:func:`defer`) runs, while the program computes: ``overlap_ms``
        and ``overlap_rows`` on the record.  What it raises is its own
        (``overlap_error``): the program it rode goes on."""
        site, now = self.site, obs_spans.now
        t0 = now()
        with obs_spans.annotation(site + ".put"):
            x = jax.device_put(rows, sharding)
        t1 = now()
        with obs_spans.annotation(site + ".call"):
            out = fn(*resident, x)
        t2 = now()
        deferred = take_deferred()
        if deferred is not None:
            work, answered = deferred
            try:
                work(site)
            except Exception as exc:    # its callers' to see, not ours
                self.record["overlap_error"] = type(exc).__name__
            self.record["overlap_rows"] = answered
        t3 = now()
        with obs_spans.annotation(site + ".get"):
            out = jax.device_get(out)
        t4 = now()
        self.record.update(put_ms=round((t1 - t0) * 1e3, 4),
                           call_ms=round((t2 - t1) * 1e3, 4),
                           get_ms=round((t4 - t3) * 1e3, 4))
        if deferred is not None:
            self.record["overlap_ms"] = round((t3 - t2) * 1e3, 4)
        return out

    def device_time(self, fn, rows, sharding, *resident) -> float:
        """``fn(*resident, rows)`` once, its input on the device first
        and its output waited for there -> ms from the call to the
        output's readiness: the program's device time and one enqueue
        (``device_ms`` on the record).  Warm-up only: a compiled program,
        no fetch."""
        x = jax.block_until_ready(jax.device_put(rows, sharding))
        t0 = obs_spans.now()
        jax.block_until_ready(fn(*resident, x))
        self.record["device_ms"] = took = obs_spans.ms_since(t0)
        return took


@contextlib.contextmanager
def device_dispatch(site: str, *, lock=None, recorder=None, **attrs):
    """THE way serving code takes a dispatch lock: ``lock`` (default
    :data:`DEVICE_DISPATCH_LOCK`) with ``jax.transfer_guard("disallow")``
    inside it, and ONE ``dispatch`` span per hold on ``recorder``
    (default: the process recorder) carrying ``site``, the caller's
    ``attrs`` (``rows``, ``bucket``), ``lock_wait_ms`` (call to lock
    acquired), ``hold_ms`` (acquired to released), ``cpu_ms`` (the
    holder's ``time.thread_time`` over the hold: a hold far longer than
    that and the program's device time waited for the interpreter inside
    ``put`` / ``get``; one near it is Python) and the legs the site marks
    through the yielded :class:`_Hold`.  The record is written
    after the release, so the lock still nests over nothing; clock reads
    only — no device sync is added (OBSERVABILITY.md).  In a profiler
    session the waiter shows as ``<site>.lock_wait`` and the holder as
    its phases, so an idle gap of the device names what the holder was
    doing, not how many were waiting."""
    lock = DEVICE_DISPATCH_LOCK if lock is None else lock
    rec = recorder if recorder is not None else obs_spans.get_recorder()
    with rec.span("dispatch", site=site, **attrs) as record:
        t0 = obs_spans.now()
        with obs_spans.annotation(f"{site}.lock_wait"):
            lock.acquire()
        record["lock_wait_ms"] = obs_spans.ms_since(t0)
        t1, c1 = obs_spans.now(), time.thread_time()
        try:
            with jax.transfer_guard("disallow"):
                yield _Hold(site, record)
        finally:
            lock.release()
            record["hold_ms"] = obs_spans.ms_since(t1)
            record["cpu_ms"] = round((time.thread_time() - c1) * 1e3, 4)


def bucket_ladder(n_dev: int, min_bucket: int, max_batch: int) -> tuple:
    """Power-of-two batch buckets, each divisible by the mesh size.

    Starts at the smallest power of two >= max(min_bucket, n_dev) and
    doubles up to ``max_batch`` inclusive.  On a power-of-two mesh (the
    only kind this repo runs) every rung then shards evenly."""
    start = max(int(min_bucket) or n_dev, n_dev)
    b = 1
    while b < start:
        b *= 2
    if b % n_dev:
        raise ValueError(
            f"bucket {b} is not divisible by the {n_dev}-way data axis — "
            "pick min_bucket as a multiple of the mesh size")
    if b > max_batch:
        raise ValueError(f"max_batch={max_batch} is below the smallest "
                         f"shardable bucket {b} on a {n_dev}-device mesh")
    out = []
    while b <= max_batch:
        out.append(b)
        b *= 2
    return tuple(out)


def place_leaves(tree, sharding, cast_dtype: Optional[str] = None):
    """The frozen tree onto the mesh, leaf by leaf: each leaf goes up in
    its own type and, where it is a float of another type than
    ``cast_dtype``, is cast there and its first copy dropped — no second
    copy of the TREE exists on the host or the device at any time (a
    tower of billions of bfloat16 parameters would not fit one).  Integer
    leaves (int8 weights, ids baked into stats) pass through."""
    dt = jnp.dtype(cast_dtype) if cast_dtype else None

    def place(x):
        x = jax.device_put(x, sharding)
        if (dt is not None and jnp.issubdtype(x.dtype, jnp.floating)
                and x.dtype != dt):
            x = x.astype(dt)
        return x

    return jax.tree_util.tree_map(place, tree)


def load_serving_model(export_dir: str, dtype: str = ""):
    """Load any ``milnce-export``-family artifact -> ``(model,
    variables, metadata)`` ready for an :class:`InferenceEngine`.

    Format detection is metadata-driven: a quantized edge-tier
    artifact (export.QUANT_FORMAT_VERSION) loads through
    ``load_quantized_checkpoint`` and returns a
    :class:`~milnce_tpu.quant.quantize.QuantizedModel` wrapper — int8
    weights resident, dequantize inside the jitted entries, f32
    accumulation.  ``dtype`` overrides are refused for quantized
    artifacts (the stored precision IS the artifact's contract)."""
    from milnce_tpu import config as program
    from milnce_tpu.models.build import build_model
    from milnce_tpu.serving.export import (QUANT_FORMAT_VERSION,
                                           load_inference_checkpoint,
                                           load_quantized_checkpoint,
                                           read_export_metadata)

    quantized = (read_export_metadata(export_dir).get("format_version")
                 == QUANT_FORMAT_VERSION)
    if quantized:
        if dtype:
            raise ValueError(
                "dtype override is not supported for quantized exports "
                "— int8 weights + f32 scales are the artifact's "
                "precision contract")
        meta, variables = load_quantized_checkpoint(export_dir)
    else:
        meta, variables = load_inference_checkpoint(export_dir)
    model_cfg = program.ModelConfig(**meta["model"])
    if dtype:
        model_cfg.dtype = dtype
    # the language model's group, where the sentence tower is one
    groups = {name: group(**meta[name]) for name, group in (
        ("text_lm", program.TextLMConfig),
        ("text_hybrid", program.TextHybridConfig),
        ("text_dlm", program.TextDLMConfig)) if name in meta}
    model = build_model(model_cfg, **groups)
    if quantized:
        from milnce_tpu.quant.quantize import QuantizedModel

        model = QuantizedModel(model)
    return model, variables, meta


class InferenceEngine:
    """Bucketed, pre-traced, transfer-guarded embed entries over frozen
    params.

    - ``variables``: ``{'params': ..., 'batch_stats': ...}`` (params-only
      inference checkpoint — serving/export.py round-trips one).
    - ``text_words`` / ``video_shape``: the fixed per-row input shapes
      ((W,) token ids / (T, H, W, 3) uint8 frames) the entries are traced
      at; requests with any other trailing shape are rejected, they would
      otherwise silently compile a new program.
    - ``cast_dtype``: optional float dtype ('bfloat16') the frozen params
      are cast to at load — the model itself must be built with the
      matching compute dtype (``InferenceEngine.from_export`` wires both).
    - ``dispatch_lock``: the lock serializing this engine's device work.
      Default is the process-wide :data:`DEVICE_DISPATCH_LOCK`; the
      replica pool (serving/pool.py) passes each replica its OWN lock so
      one wedged replica cannot stall the others' dispatch queues (the
      lock's name must contain "dispatch" — the GL012 exemption).
    """

    def __init__(self, model, variables, mesh: Mesh, *, text_words: int,
                 video_shape: Sequence[int], max_batch: int = 64,
                 min_bucket: int = 0, data_axis: str = "data",
                 cast_dtype: Optional[str] = None, precompile: bool = True,
                 dispatch_lock=None):
        self.mesh = mesh
        self.data_axis = data_axis
        self._dispatch_lock = (dispatch_lock if dispatch_lock is not None
                               else DEVICE_DISPATCH_LOCK)
        # batch divisibility is governed by the DATA axis extent alone:
        # on a (data, model) mesh the embed programs shard rows over
        # data and replicate over model (P(data) in/out specs)
        n_dev = int(mesh.shape[data_axis])
        self.buckets = bucket_ladder(n_dev, min_bucket, max_batch)
        self.max_batch = self.buckets[-1]
        self.text_words = int(text_words)
        self.video_shape = tuple(int(d) for d in video_shape)
        # one explicit replication at boot; steady state never moves params
        self._variables = place_leaves(variables, replicated(mesh),
                                       cast_dtype)
        self._batch_sh = batch_sharding(mesh, data_axis)
        self._text_fn = make_text_embed_fn(model, mesh, data_axis)
        self._video_fn = make_video_embed_fn(model, mesh, data_axis)
        # Bookkeeping shared by the batcher worker, request threads
        # (video/index paths) and /healthz readers — guarded by its own
        # tiny lock, NEVER the dispatch lock (stats reads must not
        # contend with device work).  The unlocked dict update here was
        # a real lost-increment race (graftlint GL010, ISSUE 7).
        self._stats_lock = make_lock("serving.engine.stats")
        self._calls: dict[tuple, int] = {}     # (entry, bucket) -> calls
        self._baseline_cache: Optional[dict] = None
        self.embed_dim: Optional[int] = None   # known after the first call
        self._dead = False                     # guarded-by: _stats_lock
        # rung -> the text program's device time, ms (warm-up; empty
        # before): what the device worker's turn order compares
        self.text_device_ms: dict[int, float] = {}
        if precompile:
            self.warmup()

    # ---- bucket ladder ---------------------------------------------------

    def bucket_for(self, n: int) -> int:
        """Smallest bucket that fits ``n`` rows."""
        if n < 1:
            raise ValueError(f"batch of {n} rows")
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"{n} rows exceeds max_batch={self.max_batch} "
                         "(split upstream, or rebuild with a taller ladder)")

    # ---- entries ---------------------------------------------------------

    def embed_text(self, token_ids: np.ndarray) -> np.ndarray:
        """(n, W) int32 token ids -> (n, D) float embeddings; n is padded
        to the bucket internally and unpadded on return."""
        rows = np.ascontiguousarray(token_ids, dtype=np.int32)
        if rows.ndim != 2 or rows.shape[1] != self.text_words:
            raise ValueError(f"expected (n, {self.text_words}) token ids, "
                             f"got {rows.shape}")
        tokens = int(np.count_nonzero(rows))
        slots = self.bucket_for(rows.shape[0]) * self.text_words
        return self._run("text", self._text_fn, rows, tokens=tokens,
                         pad_tokens=slots - tokens)

    def embed_video(self, video_u8: np.ndarray) -> np.ndarray:
        """(n, T, H, W, 3) uint8 frames -> (n, D) float embeddings."""
        clips = np.ascontiguousarray(video_u8, dtype=np.uint8)
        if clips.shape[1:] != self.video_shape:
            raise ValueError(f"expected (n,) + {self.video_shape} uint8 "
                             f"video, got {clips.shape}")
        return self._run("video", self._video_fn, clips)

    def _run(self, entry: str, fn, rows: np.ndarray,
             **attrs) -> np.ndarray:
        """``attrs``: what the entry adds to its ``dispatch`` record (the
        text entry: the real tokens and the slots of the bucket they leave
        empty)."""
        n = rows.shape[0]
        bucket = self.bucket_for(n)
        rows = pad_rows(rows, bucket)
        # Serving-path fault sites (resilience/faults.py; chaos tests
        # kill/hang/flake individual replicas through here).  Checked
        # BEFORE the dispatch lock: a dead replica fails instantly and a
        # hang wedges only this engine's callers, never the lock queue
        # of a pool sibling.
        if self.dead:
            raise ReplicaDead("replica is dead (serve.replica_dead / "
                              "kill()) — restart the process to revive it")
        faults.maybe_raise("serve.dispatch_raise")
        faults.maybe_hang("serve.dispatch_hang")
        if faults.fire_site("serve.replica_dead"):
            self.kill()
            raise ReplicaDead("injected fault at serve.replica_dead — "
                              "this replica is now permanently dead")
        # Steady state: implicit transfers are bugs (they stall the async
        # dispatch pipeline); both legs of the request are explicit.
        with device_dispatch(f"engine.{entry}", lock=self._dispatch_lock,
                             rows=n, bucket=bucket, **attrs) as hold:
            out = hold.round_trip(fn, rows, self._batch_sh, self._variables)
            if isinstance(out, tuple):
                # a program that counts what it did (a tower with routed
                # layers): the rows, and name -> scalar for the record
                out, counters = out
                hold.record.update(
                    {name: int(v) for name, v in counters.items()})
        out = np.asarray(out)
        with self._stats_lock:
            self._calls[(entry, bucket)] = \
                self._calls.get((entry, bucket), 0) + 1
            self.embed_dim = int(out.shape[-1])
        return out[:n]

    def jit_entries(self) -> dict:
        """The engine's jitted programs by entry name — the supported
        surface for the analysis passes (trace invariants pin their
        collectives; the graftlint Pass 4 planner walks their jaxprs at
        every ladder rung) instead of reaching into ``_text_fn``/
        ``_video_fn``.  Tracing these does NOT require a warmed engine:
        build with ``precompile=False`` for planning-only use."""
        return {"text": self._text_fn, "video": self._video_fn}

    def program_text(self, entry: str, bucket: int) -> str:
        """The compiled program of ``entry`` ('text' | 'video') at the
        ``bucket``-row rung, as HLO text: every instruction with the
        ``op_name`` that holds its ``jax.named_scope``s.  A device trace
        names operations by their instruction alone; this is how a reader
        finds the scope one ran under.  Compiled ahead of time from
        shapes: neither the jit cache nor :meth:`recompiles` sees it."""
        shape, dtype = {
            "text": ((self.text_words,), np.int32),
            "video": (self.video_shape, np.uint8)}[entry]
        avals = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding),
            self._variables)
        rows = jax.ShapeDtypeStruct((int(bucket),) + tuple(shape), dtype,
                                    sharding=self._batch_sh)
        return self.jit_entries()[entry].lower(avals, rows).compile() \
            .as_text()

    # ---- warmup + recompile accounting -----------------------------------

    def warmup(self) -> None:
        """Sweep BOTH entries over the full bucket ladder so every
        executable the engine will ever run exists before the first
        request, then snapshot the jit cache sizes — any later growth is
        a recompile (:meth:`recompiles`).  The text entry runs once more
        a rung, timed on the device (:attr:`text_device_ms`, and
        ``text_device_ms`` on the span)."""
        with obs_spans.get_recorder().span(
                "ladder.warmup", buckets=list(self.buckets)) as span:
            for b in self.buckets:
                self.embed_text(np.zeros((b, self.text_words), np.int32))
                self.embed_video(np.zeros((b,) + self.video_shape, np.uint8))
            timed = {}
            for b in self.buckets:
                with device_dispatch("engine.text", lock=self._dispatch_lock,
                                     rows=0, bucket=b) as hold:
                    timed[b] = hold.device_time(
                        self._text_fn,
                        np.zeros((b, self.text_words), np.int32),
                        self._batch_sh, self._variables)
            span["text_device_ms"] = {str(b): ms for b, ms in timed.items()}
        self.text_device_ms = timed
        baseline = self._cache_sizes()
        with self._stats_lock:
            self._baseline_cache = baseline

    def _cache_sizes(self) -> dict:
        out = {}
        for name, fn in (("text", self._text_fn), ("video", self._video_fn)):
            size = getattr(fn, "_cache_size", None)
            out[name] = int(size()) if size is not None else -1
        return out

    def recompiles(self) -> int:
        """Jit-cache entries created SINCE the warmup sweep — 0 in a
        healthy steady state (pinned by the serve_embed_ladder trace
        invariant).  -1 when this jax build has no cache introspection."""
        with self._stats_lock:
            baseline = self._baseline_cache
        if baseline is None:
            return -1
        now = self._cache_sizes()
        if -1 in now.values() or -1 in baseline.values():
            return -1
        return sum(max(0, now[k] - baseline[k]) for k in now)

    # ---- liveness (pool failure isolation) -------------------------------

    @property
    def dead(self) -> bool:
        with self._stats_lock:
            return self._dead

    def kill(self) -> None:
        """Force-kill this engine: every subsequent dispatch raises
        :class:`ReplicaDead` instantly.  The ``serve.replica_dead`` fault
        site and chaos drills use this to simulate a replica whose
        device/process is gone; there is no un-kill — recovery is a
        process restart (the pool keeps it QUARANTINED forever)."""
        with self._stats_lock:
            self._dead = True

    def stats(self) -> dict:
        with self._stats_lock:
            calls = dict(self._calls)
            dead = self._dead
        return {
            "buckets": list(self.buckets),
            "max_batch": self.max_batch,
            "recompiles": self.recompiles(),
            "dead": dead,
            "calls": {f"{entry}@{bucket}": n
                      for (entry, bucket), n in sorted(calls.items())},
        }

    # ---- construction from a frozen export -------------------------------

    @classmethod
    def from_export(cls, export_dir: str, mesh: Mesh, *, dtype: str = "",
                    max_batch: int = 64, min_bucket: int = 0,
                    data_axis: str = "data", precompile: bool = True
                    ) -> "InferenceEngine":
        """Build model + engine from a ``milnce-export`` directory.

        ``dtype`` overrides the exported compute dtype ('bfloat16' casts
        the frozen params AND builds the model at bf16 — the MXU-rate
        deployment mode; '' keeps the exported dtype).

        Format detection is metadata-driven: a quantized edge-tier
        artifact (export.QUANT_FORMAT_VERSION) loads through
        ``load_quantized_checkpoint`` and serves behind a
        :class:`~milnce_tpu.quant.quantize.QuantizedModel` wrapper —
        int8 weights resident, dequantize inside the jitted entries,
        f32 accumulation; same ladder, same recompiles=0 contract.
        ``dtype`` overrides are refused for quantized artifacts (the
        stored precision IS the artifact's contract)."""
        model, variables, meta = load_serving_model(export_dir, dtype)
        return cls(model, variables, mesh,
                   text_words=meta["tokenizer"]["max_words"],
                   video_shape=meta["video_shape"],
                   max_batch=max_batch, min_bucket=min_bucket,
                   data_axis=data_axis,
                   cast_dtype=(dtype or None), precompile=precompile)
