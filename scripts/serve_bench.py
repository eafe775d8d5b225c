#!/usr/bin/env python
"""Serving load generator: open/closed-loop driver over the full
batcher -> engine -> index path, emitting a ``SERVE_BENCH_*.json``
report (latency percentiles, QPS, batch-occupancy histogram, cache hit
rate).

Usage::

    python scripts/serve_bench.py --backend cpu --preset tiny      # smoke
    python scripts/serve_bench.py --preset tiny --mode open --qps 200
    python scripts/serve_bench.py --export_dir export/run1 ...     # real params

Modes:

- **closed** (default): ``--concurrency`` workers each issue the next
  query the moment the previous one completes — measures the service's
  self-paced throughput and the latency it costs.
- **open**: queries arrive on a Poisson clock at ``--qps`` regardless of
  completions (the honest SLO view: latency under an offered load that
  does not politely wait for the server).
- **tiered** (``--tiers interactive:80,batch:200``): one open-loop
  Poisson driver PER SLO tier, concurrently, each request stamped with
  its tier — the per-tenant view.  The report gains a ``tiers`` block
  (per-tier p50/p99, qps, refusal taxonomy, ``error_rate``) and
  ``obs_report --check`` gates per-tier p99 + error_rate.  ``--knee``
  sweeps the offered load (doubling per round) and reports each tier's
  QPS knee — the last load the service cleared inside
  ``--knee_slo_ms``.
- **tier-class** (``--tier-class``): bench each serving replica class
  (f32 / int8 / distilled student — SERVING.md "Edge tier")
  sequentially at the SAME offered load, one
  ``SERVE_BENCH_<preset>_class_<class>.json`` record per class.  Each
  record carries ``recall_at_10`` (top-10 overlap against the f32
  class's rankings on a fixed query pool; an ``obs_report --check``
  gate metric) and the program's ``dtype_census_hash``, so gating an
  edge class against the committed f32 baseline pins the quality floor
  while latency drift stays attributable to the precision change.

Live-index options: ``--live_index`` serves through the
generation-swapped ``LiveRetrievalIndex`` and ``--ingest_rows N
--ingest_interval_s S`` runs a background ingest job (N random rows
every S seconds through ``service.index_add``), so a chaos spec like
``--faults 'index.swap_raise@%3'`` exercises swap failures UNDER load.

Queries are drawn from a ``--distinct``-sized pool with a Zipf-ish
(1/rank) distribution, so the text-embedding cache sees a realistic
heavy-tailed hit pattern; ``--distinct 0`` disables reuse (pure-miss).

Timing honesty: every recorded latency spans submit -> numpy result on
host (the service API materializes results), so there is no async-
dispatch mirage to correct for; the engine warmup (compiles) happens
before the measurement window and is reported separately as
``warmup_s``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def build_service(args, tier_class=""):
    """Tiny-preset service stack: random frozen params (or an export),
    synthetic video corpus, programmatic API only.  ``--replicas N``
    builds a ReplicaPool (N single-device engines on the CPU backend)
    instead of one engine — the chaos-bench configuration.

    ``tier_class`` swaps the random-init tower for its edge-tier
    counterpart before the engine is built: ``"int8"`` quantizes the
    frozen tree (weight-only symmetric int8, per-channel where the
    readiness rule demands — quant/quantize.py) and serves it through
    ``QuantizedModel``; ``"student"`` distils the text tower
    (quant/distill.py) and serves the grafted student variables."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from milnce_tpu.config import PRESETS
    from milnce_tpu.models.build import build_model
    from milnce_tpu.obs import metrics as obs_metrics
    from milnce_tpu.parallel.mesh import build_mesh
    from milnce_tpu.serving.cache import EmbeddingLRUCache
    from milnce_tpu.serving.engine import InferenceEngine
    from milnce_tpu.serving.index import DeviceRetrievalIndex
    from milnce_tpu.serving.service import RetrievalService

    cfg = PRESETS[args.preset]()
    mesh = build_mesh(cfg.parallel)
    video_shape = (cfg.data.num_frames, cfg.data.video_size,
                   cfg.data.video_size, 3)
    registry = obs_metrics.MetricsRegistry()
    pool_kwargs = dict(
        queue_depth=args.replica_queue_depth,
        error_threshold=args.error_threshold,
        probe_interval_s=args.probe_interval_s,
        hedge_quantile=args.hedge_quantile,
        hedge_min_ms=args.hedge_min_ms,
        max_requeues=args.max_requeues, registry=registry)
    if args.export_dir:
        if args.replicas > 1:
            from milnce_tpu.serving.pool import ReplicaPool

            engine = ReplicaPool.from_export(
                args.export_dir, args.replicas, max_batch=args.max_batch,
                min_bucket=args.min_bucket, **pool_kwargs)
        else:
            engine = InferenceEngine.from_export(args.export_dir, mesh,
                                                 max_batch=args.max_batch,
                                                 min_bucket=args.min_bucket)
    else:
        model = build_model(cfg.model)
        variables = model.init(
            jax.random.PRNGKey(0),
            jnp.zeros((1,) + video_shape, jnp.float32),
            jnp.zeros((1, cfg.data.max_words), jnp.int32))
        frozen = {"params": variables["params"],
                  "batch_stats": variables.get("batch_stats", {})}
        if tier_class == "int8":
            from milnce_tpu.quant.quantize import (
                QuantizedModel, per_channel_keys_from_weights,
                quantize_variables)

            frozen = quantize_variables(
                frozen, per_channel_keys=per_channel_keys_from_weights(
                    frozen["params"]))
            model = QuantizedModel(model)
        elif tier_class == "student":
            from milnce_tpu.quant.distill import (
                build_student_variables, distill_text_student,
                student_model_config)

            sparams, sinfo = distill_text_student(
                model, frozen, max_words=cfg.data.max_words)
            model = build_model(student_model_config(cfg.model,
                                                     sinfo["hidden_dim"]))
            frozen = build_student_variables(frozen, sparams)
        elif tier_class:
            raise ValueError(f"unknown tier class {tier_class!r}")
        if args.replicas > 1:
            from milnce_tpu.serving.pool import ReplicaPool

            engine = ReplicaPool.build(
                model, frozen, args.replicas,
                text_words=cfg.data.max_words, video_shape=video_shape,
                max_batch=args.max_batch, min_bucket=args.min_bucket,
                **pool_kwargs)
        else:
            engine = InferenceEngine(
                model, frozen, mesh, text_words=cfg.data.max_words,
                video_shape=video_shape, max_batch=args.max_batch,
                min_bucket=args.min_bucket)

    # synthetic corpus, embedded through the engine in bucket-sized chunks
    rng = np.random.default_rng(0)
    corpus_emb = []
    top = engine.buckets[-1]
    for lo in range(0, args.corpus, top):
        n = min(top, args.corpus - lo)
        clips = rng.integers(0, 255, (n,) + video_shape, dtype=np.uint8)
        corpus_emb.append(engine.embed_video(clips))
    corpus_emb = np.concatenate(corpus_emb, axis=0)
    k = min(args.topk, args.corpus)
    if args.live_index:
        from milnce_tpu.serving.live_index import LiveRetrievalIndex

        index = LiveRetrievalIndex(mesh, corpus_emb, k=k,
                                   query_buckets=engine.buckets,
                                   registry=registry)
    else:
        index = DeviceRetrievalIndex(mesh, corpus_emb, k=k,
                                     query_buckets=engine.buckets)
    service = RetrievalService(
        engine, index, cache=EmbeddingLRUCache(args.cache_capacity),
        default_timeout_ms=args.timeout_ms, registry=registry,
        max_inflight=args.max_inflight, tiers=args.tier_shares)
    return cfg, service


def make_query_draw(cfg, distinct: int):
    """-> ``draw(rng) -> (W,) int32 token row``.

    ``distinct > 0``: rows come from a fixed pool with 1/rank (Zipf-ish)
    weights — the heavy-tailed repeat pattern the cache exists for.
    ``distinct <= 0``: every draw is a FRESH random row (pure-miss mode;
    the cache never helps)."""
    import numpy as np

    vocab, words = cfg.model.vocab_size, cfg.data.max_words
    if distinct <= 0:
        def draw(rng):
            return rng.integers(1, vocab, (words,)).astype(np.int32)

        return draw
    pool_rng = np.random.default_rng(7)
    pool = pool_rng.integers(1, vocab, (distinct, words)).astype(np.int32)
    ranks = np.arange(1, len(pool) + 1, dtype=np.float64)
    probs = (1.0 / ranks) / (1.0 / ranks).sum()

    def draw(rng):
        return pool[rng.choice(len(pool), p=probs)]

    return draw


def _make_issue(service, lats: list, counters: dict,
                lock: threading.Lock, tier=None):
    """-> ``issue(row)``: one query with the full refusal taxonomy
    counted — expired (504), shed (429), degraded (503) are STRUCTURED
    refusals, ``errors`` is everything unstructured.  Every branch
    returns; nothing can hang a worker.  ``tier`` stamps the request's
    SLO class (tiered mode)."""
    from milnce_tpu.serving.batcher import DeadlineExpired
    from milnce_tpu.serving.pool import PoolSaturated, PoolUnavailable
    from milnce_tpu.serving.service import DegradedError, ShedError

    def issue(row) -> None:
        t0 = time.perf_counter()
        try:
            service.query_ids(row[None, :], tier=tier)
        except DeadlineExpired:
            with lock:
                counters["deadline_expired"] += 1
        except (ShedError, PoolSaturated):
            with lock:
                counters["shed"] += 1
        except (DegradedError, PoolUnavailable):
            with lock:
                counters["degraded"] += 1
        except Exception:
            with lock:
                counters["errors"] += 1
        else:
            dt = time.perf_counter() - t0
            with lock:
                lats.append(dt)

    return issue


def new_counters() -> dict:
    return {"errors": 0, "deadline_expired": 0, "shed": 0, "degraded": 0}


def run_closed_loop(service, draw, duration: float,
                    concurrency: int):
    """Each worker issues the next query on completion; returns
    (latencies_s, counters)."""
    import numpy as np

    lats: list[float] = []
    counters = new_counters()
    lock = threading.Lock()
    issue = _make_issue(service, lats, counters, lock)
    t_end = time.monotonic() + duration

    def worker(wid: int):
        rng = np.random.default_rng(1000 + wid)
        while time.monotonic() < t_end:
            issue(draw(rng))

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return lats, counters


def _open_loop_drive(issue, draw, duration: float, qps: float,
                     seed: int = 11) -> None:
    """Poisson arrivals at ``qps``; each arrival runs on its own thread
    (requests keep arriving whether or not earlier ones finished)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    inflight: list[threading.Thread] = []
    t_end = time.monotonic() + duration
    next_arrival = time.monotonic()
    while time.monotonic() < t_end:
        now = time.monotonic()
        if now < next_arrival:
            time.sleep(min(next_arrival - now, 0.01))
            continue
        next_arrival += rng.exponential(1.0 / qps)
        t = threading.Thread(target=issue, args=(draw(rng),), daemon=True)
        t.start()
        inflight.append(t)
    for t in inflight:
        t.join(timeout=30.0)


def run_open_loop(service, draw, duration: float, qps: float):
    lats: list[float] = []
    counters = new_counters()
    lock = threading.Lock()
    _open_loop_drive(_make_issue(service, lats, counters, lock),
                     draw, duration, qps)
    return lats, counters


def run_tiered_open_loop(service, draw, duration: float, tier_qps: dict):
    """One open-loop Poisson driver per SLO tier, concurrently; returns
    ``{tier: (lats, counters, qps_offered)}``."""
    results = {}
    drivers = []
    for i, (tier, qps) in enumerate(tier_qps.items()):
        lats: list[float] = []
        counters = new_counters()
        lock = threading.Lock()
        results[tier] = (lats, counters, qps)
        issue = _make_issue(service, lats, counters, lock, tier=tier)
        drivers.append(threading.Thread(
            target=_open_loop_drive,
            args=(issue, draw, duration, qps, 100 + i), daemon=True))
    for t in drivers:
        t.start()
    for t in drivers:
        t.join()
    return results


def parse_tier_qps(spec: str) -> dict:
    """'interactive:80,batch:200' -> ordered {tier: offered qps}.
    Duplicate names are an error (same contract as the service's
    parse_tier_spec) — a typo'd mix must not silently collapse."""
    out = {}
    for item in filter(None, (c.strip() for c in spec.split(","))):
        name, _, qps = item.partition(":")
        name = name.strip()
        if not name or not qps or name in out:
            raise ValueError(f"tier item {item!r}: expected a UNIQUE "
                             "name:qps")
        out[name] = float(qps)
    if not out:
        raise ValueError("--tiers given but names no tier")
    return out


# serving replica classes the --tier-class comparison knows how to
# build (SERVING.md "Edge tier"); f32 is the recall baseline
TIER_CLASSES = ("f32", "int8", "student")


def _tier_class_rankings(service, cfg, k: int):
    """Top-``k`` corpus ids for a FIXED deterministic query pool — the
    cross-class recall probe.  Same seed for every class, so overlap
    against the f32 class's rankings is attributable to the tower swap
    alone, not query drift."""
    import numpy as np

    rng = np.random.default_rng(17)
    pool = rng.integers(1, cfg.model.vocab_size,
                        (16, cfg.data.max_words)).astype(np.int32)
    top = service.engine.buckets[-1]
    idx = []
    for lo in range(0, len(pool), top):
        _scores, ids = service.query_ids(pool[lo:lo + top])
        idx.append(np.asarray(ids))
    return np.concatenate(idx, axis=0)[:, :k]


def recall_at_k(idx, base_idx) -> float:
    """Mean top-k overlap fraction against the baseline rankings."""
    k = idx.shape[1]
    return float(sum(len(set(a) & set(b)) for a, b in zip(idx, base_idx))
                 / (len(idx) * k))


def _dtype_census_hash(service, cfg) -> str:
    """Precision fingerprint of the service's text embed program at the
    bottom bucket (analysis/numerics.py) — stamped into each class
    record so ``obs_report --check`` marks cross-class gates as
    cross-precision compares instead of plain regressions."""
    import numpy as np

    from milnce_tpu.analysis import numerics

    engine = service.engine
    tokens = np.zeros((engine.buckets[0], cfg.data.max_words), np.int32)
    # the engine's device-resident tree IS the program's weight operand
    audit = numerics.audit_fn(engine.jit_entries()["text"],
                              (engine._variables, tokens),
                              argnames=("variables", "tokens"),
                              entry="serve_bench_text")
    return audit.census_hash()


def run_tier_class(args) -> int:
    """``--tier-class``: bench every class in ``--classes``
    sequentially at the SAME offered load, one milnce.obs/v1 record per
    class.  The f32 class runs first and its top-10 rankings are the
    recall baseline; the exit gate requires recompiles == 0 for every
    class — an edge class that re-traces under the f32 bucket ladder is
    a fail, not a footnote."""
    classes = [c.strip() for c in args.classes.split(",") if c.strip()]
    bad = sorted(set(classes) - set(TIER_CLASSES))
    if bad:
        raise SystemExit(f"serve_bench: unknown --classes {bad}; "
                         f"known classes: {', '.join(TIER_CLASSES)}")
    if not classes or classes[0] != "f32":
        raise SystemExit("serve_bench: --tier-class needs f32 FIRST in "
                         "--classes — it is the recall@10 baseline")
    k = min(10, args.corpus)
    args.topk = max(args.topk, k)   # the index must answer top-10
    base_idx = None
    outputs = []
    ok = True
    for cls in classes:
        t0 = time.monotonic()
        cfg, service = build_service(
            args, tier_class="" if cls == "f32" else cls)
        warmup_s = time.monotonic() - t0
        idx = _tier_class_rankings(service, cfg, k)
        if base_idx is None:
            base_idx = idx
        recall = recall_at_k(idx, base_idx)
        census = _dtype_census_hash(service, cfg)
        draw = make_query_draw(cfg, args.distinct)
        t_run = time.monotonic()
        if args.mode == "closed":
            lats, counters = run_closed_loop(
                service, draw, args.duration, args.concurrency)
        else:
            lats, counters = run_open_loop(
                service, draw, args.duration, args.qps)
        elapsed = time.monotonic() - t_run
        errors = counters["errors"]
        expired = counters["deadline_expired"]
        health = service.health()
        service.close()
        if args.replicas > 1:
            service.engine.close()
        extra = {
            "generator": "scripts/serve_bench.py",
            "mode": f"tier-class/{args.mode}",
            "backend": args.backend,
            "preset": args.preset,
            "tier_class": cls,
            "config": {key: v for key, v in vars(args).items()
                       if key != "out"},
            "warmup_s": round(warmup_s, 3),
            "elapsed_s": round(elapsed, 3),
            "requests": len(lats),
            "errors": errors,
            "deadline_expired": expired,
            "resilience": {key: counters[key]
                           for key in ("shed", "degraded")},
            "error_rate": round(
                errors / max(1, len(lats) + errors + expired
                             + counters["shed"] + counters["degraded"]),
                5),
            "qps": round(len(lats) / elapsed, 2) if elapsed > 0 else 0.0,
            "latency_ms": _lat_summary(lats),
            # the edge-tier quality gate (obs_report: higher is better)
            "recall_at_10": round(recall, 4),
            "dtype_census_hash": census,
            "cache": health["cache"],
            "engine": health["engine"],
            "index": health["index"],
        }
        from milnce_tpu.obs import export as obs_export
        from milnce_tpu.obs.runctx import auto_run_id

        report = obs_export.snapshot(service.registry, kind="serve_bench",
                                     extra=extra,
                                     run_id=auto_run_id("sbench-"),
                                     process_index=0)
        out = os.path.join(
            _REPO, f"SERVE_BENCH_{args.preset}_class_{cls}.json")
        with open(out, "w") as fh:
            json.dump(report, fh, indent=2, sort_keys=True)
        outputs.append((cls, report, out))
        ok = ok and report["engine"]["recompiles"] in (0, -1)
    print(f"serve_bench --tier-class: {len(outputs)} classes at the "
          f"same offered load (mode={args.mode}, "
          f"duration={args.duration}s)")
    for cls, report, out in outputs:
        print(f"  class {cls:<8} qps={report['qps']:<8g} "
              f"p50={report['latency_ms']['p50']}ms "
              f"p99={report['latency_ms']['p99']}ms "
              f"recall@10={report['recall_at_10']} "
              f"census={report['dtype_census_hash']} "
              f"recompiles={report['engine']['recompiles']} "
              f"-> {os.path.basename(out)}")
    return 0 if ok else 1


def knee_from_rounds(rounds: list, slo_ms: float,
                     min_served_frac: float = 0.9):
    """The QPS knee from an open-loop sweep: the highest offered load
    whose round held p99 <= ``slo_ms`` AND served at least
    ``min_served_frac`` of its offered requests (refusals and errors
    count against it).  None when even the first round blew through —
    the knee is below the sweep's floor, a finding in itself."""
    knee = None
    for r in rounds:
        ok = (r["p99_ms"] <= slo_ms
              and r["served_frac"] >= min_served_frac)
        if ok and (knee is None or r["qps_offered"] > knee):
            knee = r["qps_offered"]
    return knee


def _lat_summary(lats: list) -> dict:
    import numpy as np

    lat_ms = np.asarray(sorted(lats), np.float64) * 1e3
    pct = (lambda q: float(np.percentile(lat_ms, q))) if len(lat_ms) \
        else (lambda q: float("nan"))
    return {
        "p50": round(pct(50), 3), "p95": round(pct(95), 3),
        "p99": round(pct(99), 3),
        "mean": round(float(lat_ms.mean()), 3) if len(lat_ms)
        else float("nan"),
        "max": round(float(lat_ms.max()), 3) if len(lat_ms)
        else float("nan"),
    }


def _tier_block(results: dict, elapsed: float) -> dict:
    """Per-tier report block: latency summary + refusal taxonomy +
    the per-tier ``error_rate`` / ``qps`` gate metrics."""
    out = {}
    for tier, (lats, counters, offered) in results.items():
        total = (len(lats) + counters["errors"]
                 + counters["deadline_expired"] + counters["shed"]
                 + counters["degraded"])
        out[tier] = {
            "qps_offered": offered,
            "qps": round(len(lats) / elapsed, 2) if elapsed > 0 else 0.0,
            "requests": len(lats),
            "latency_ms": _lat_summary(lats),
            "error_rate": round(counters["errors"] / max(1, total), 5),
            "served_frac": round(len(lats) / max(1, total), 5),
            **counters,
        }
    return out


def start_ingest(service, rows: int, interval_s: float,
                 stop: threading.Event, seed: int = 99):
    """Background ingest job: ``rows`` random embedding rows through
    ``service.index_add`` every ``interval_s`` — the write-path load for
    live-index benches (ingest errors are counted, never raised into
    the bench)."""
    import numpy as np

    counters = {"ingests": 0, "ingest_errors": 0}
    dim = service.engine.embed_dim
    rng = np.random.default_rng(seed)

    def loop():
        while not stop.wait(interval_s):
            try:
                service.index_add(embeddings=rng.standard_normal(
                    (rows, dim)).astype(np.float32))
                counters["ingests"] += 1
            except Exception:
                counters["ingest_errors"] += 1

    t = threading.Thread(target=loop, daemon=True)
    t.start()
    return t, counters


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="serving load generator (scripts/serve_bench.py)")
    ap.add_argument("--backend", choices=("cpu", "default"), default="cpu",
                    help="'cpu' pins JAX_PLATFORMS=cpu (hermetic smoke); "
                         "'default' uses whatever accelerator jax finds")
    ap.add_argument("--preset", choices=("tiny", "small", "full"),
                    default="tiny")
    ap.add_argument("--mode", choices=("closed", "open"), default="closed")
    ap.add_argument("--duration", type=float, default=3.0,
                    help="measurement window seconds")
    ap.add_argument("--concurrency", type=int, default=4,
                    help="closed-loop workers")
    ap.add_argument("--qps", type=float, default=100.0,
                    help="open-loop offered load")
    ap.add_argument("--corpus", type=int, default=64,
                    help="synthetic video corpus size")
    ap.add_argument("--distinct", type=int, default=32,
                    help="distinct query pool, Zipf-weighted (repeats hit "
                         "the cache); 0 = fresh random row per request "
                         "(pure-miss)")
    ap.add_argument("--topk", type=int, default=5)
    ap.add_argument("--max_batch", type=int, default=16,
                    help="top bucket (taller ladders compile longer)")
    ap.add_argument("--min_bucket", type=int, default=0,
                    help="bottom bucket (0 = mesh/replica-group size; "
                         "raise it to shrink the ladder's compile bill — "
                         "single-device pool replicas otherwise start "
                         "their ladder at 1)")
    ap.add_argument("--timeout_ms", type=float, default=0.0)
    ap.add_argument("--cache_capacity", type=int, default=4096)
    ap.add_argument("--export_dir", default="",
                    help="serve a milnce-export instead of random params")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replica pool size (>1 = ReplicaPool; on "
                         "the cpu backend the virtual device count is "
                         "forced to match)")
    ap.add_argument("--replica_queue_depth", type=int, default=16)
    ap.add_argument("--error_threshold", type=int, default=3)
    ap.add_argument("--probe_interval_s", type=float, default=0.5)
    ap.add_argument("--hedge_quantile", type=float, default=0.0,
                    help="hedge dispatches past this latency quantile to "
                         "a second replica (0 = off)")
    ap.add_argument("--hedge_min_ms", type=float, default=20.0)
    ap.add_argument("--max_requeues", type=int, default=1,
                    help="failed dispatches retried on another replica "
                         "before the caller sees the error")
    ap.add_argument("--max_inflight", type=int, default=0,
                    help="admission bound: rows in flight before requests "
                         "shed with 429 (0 = unbounded)")
    ap.add_argument("--live_index", action="store_true",
                    help="serve through the generation-swapped "
                         "LiveRetrievalIndex (ingest-capable)")
    ap.add_argument("--ingest_rows", type=int, default=0,
                    help="live-index background ingest: rows per ingest "
                         "(0 = no ingest job; needs --live_index)")
    ap.add_argument("--ingest_interval_s", type=float, default=0.5,
                    help="seconds between background ingests")
    ap.add_argument("--tiers", default="",
                    help="tiered open-loop mode: 'name:qps[,name:qps...]' "
                         "— one Poisson driver per SLO tier (overrides "
                         "--mode; first tier = highest priority)")
    ap.add_argument("--tier_shares", default="",
                    help="admission tier spec 'name:share[,...]' "
                         "(service.parse_tier_spec grammar); '' with "
                         "--tiers = first tier 1.0, the rest 0.5")
    ap.add_argument("--tier-class", dest="tier_class",
                    action="store_true",
                    help="per-replica-class comparison: bench every "
                         "class in --classes sequentially at the same "
                         "offered load, one SERVE_BENCH_<preset>_class_"
                         "<class>.json record per class with recall@10 "
                         "vs the f32 rankings + the program's "
                         "dtype_census_hash (SERVING.md 'Edge tier')")
    ap.add_argument("--classes", default="f32,int8,student",
                    help="--tier-class roster (f32 must come first: it "
                         "is the recall@10 baseline)")
    ap.add_argument("--knee", action="store_true",
                    help="with --tiers: sweep offered load (doubling per "
                         "round) and report each tier's QPS knee")
    ap.add_argument("--knee_rounds", type=int, default=3,
                    help="sweep rounds (offered load x1, x2, x4, ...)")
    ap.add_argument("--knee_slo_ms", type=float, default=500.0,
                    help="p99 bound a round must hold to count toward "
                         "the knee")
    ap.add_argument("--faults", default="",
                    help="fault-injection spec (resilience/faults.py "
                         "grammar, e.g. 'serve.dispatch_raise@%%5;"
                         "serve.replica_dead@40').  Armed AFTER warmup — "
                         "the measurement window is the chaos window — "
                         "and exported as MILNCE_FAULTS for any child")
    ap.add_argument("--out", default="",
                    help="report path (default "
                         "SERVE_BENCH_<preset>_<mode>.json at repo root)")
    args = ap.parse_args(argv)

    if args.backend == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if (args.replicas > 1
                and "xla_force_host_platform_device_count" not in flags):
            # a pool needs one device per replica on the CPU backend;
            # must land before jax initializes its backends
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count="
                f"{args.replicas}").strip()
    import numpy as np

    if args.ingest_rows and not args.live_index:
        ap.error("--ingest_rows needs --live_index")
    if args.tier_class:
        if args.tiers or args.export_dir or args.live_index or args.faults:
            ap.error("--tier-class is a self-contained comparison: drop "
                     "--tiers/--export_dir/--live_index/--faults")
        return run_tier_class(args)
    tier_qps = parse_tier_qps(args.tiers) if args.tiers else None
    if tier_qps and not args.tier_shares:
        # default shares: the first (highest-priority) tier may use the
        # whole admission budget, every later tier half of it
        args.tier_shares = ",".join(
            f"{name}:{1.0 if i == 0 else 0.5}"
            for i, name in enumerate(tier_qps))

    t0 = time.monotonic()
    cfg, service = build_service(args)     # includes engine+index warmup
    warmup_s = time.monotonic() - t0
    draw = make_query_draw(cfg, args.distinct)

    if args.faults:
        # armed AFTER build/warmup: occurrences count from the first
        # measured request, so a spec like @%5 is reproducible and the
        # compile sweep can't eat scheduled occurrences
        from milnce_tpu.resilience import faults

        os.environ[faults.ENV_VAR] = args.faults
        faults.arm(args.faults)

    ingest_stop = threading.Event()
    ingest_counters = None
    if args.ingest_rows:
        _ingest_thread, ingest_counters = start_ingest(
            service, args.ingest_rows, args.ingest_interval_s, ingest_stop)

    tier_results = None
    knee_report = None
    t_run = time.monotonic()
    if tier_qps:
        rounds_by_tier = {t: [] for t in tier_qps}
        factors = ([2 ** r for r in range(max(1, args.knee_rounds))]
                   if args.knee else [1])
        round_elapsed = args.duration
        for factor in factors:
            scaled = {t: q * factor for t, q in tier_qps.items()}
            t_round = time.monotonic()
            res = run_tiered_open_loop(service, draw, args.duration,
                                       scaled)
            round_elapsed = time.monotonic() - t_round
            tier_results = res          # the LAST round feeds the report
            block = _tier_block(res, round_elapsed)
            for t, td in block.items():
                rounds_by_tier[t].append({
                    "qps_offered": td["qps_offered"],
                    "p99_ms": td["latency_ms"]["p99"],
                    "served_frac": td["served_frac"]})
        if args.knee:
            knee_report = {
                t: {"knee_qps": knee_from_rounds(rounds, args.knee_slo_ms),
                    "slo_ms": args.knee_slo_ms, "rounds": rounds}
                for t, rounds in rounds_by_tier.items()}
        lats, counters = [], new_counters()
        for t_lats, t_counters, _ in tier_results.values():
            lats.extend(t_lats)
            for key in counters:
                counters[key] += t_counters[key]
    elif args.mode == "closed":
        lats, counters = run_closed_loop(
            service, draw, args.duration, args.concurrency)
    else:
        lats, counters = run_open_loop(
            service, draw, args.duration, args.qps)
    elapsed = time.monotonic() - t_run
    if tier_qps:
        # lats/counters hold the LAST round only — qps (top-level and
        # per-tier) must divide by that round's measured window, not the
        # whole sweep (a --knee run's elapsed spans every round)
        elapsed = round_elapsed
    ingest_stop.set()
    errors, expired = counters["errors"], counters["deadline_expired"]
    health = service.health()
    service.close()
    if args.live_index:
        service.index.close()
    if args.replicas > 1:
        service.engine.close()

    extra = {
        "generator": "scripts/serve_bench.py",
        "mode": "tiers" if tier_qps else args.mode,
        "backend": args.backend,
        "preset": args.preset,
        "config": {k: v for k, v in vars(args).items() if k != "out"},
        "warmup_s": round(warmup_s, 3),
        "elapsed_s": round(elapsed, 3),
        "requests": len(lats),
        "errors": errors,
        "deadline_expired": expired,
        # the chaos-bench taxonomy: shed (429) / degraded (503) are
        # structured refusals, requeued/hedged/quarantines/recoveries
        # come from the pool's resilience counters; error_rate is the
        # UNSTRUCTURED failure fraction and an obs_report gate metric
        # (lower is better) so chaos runs can gate error-rate drift
        "resilience": {
            **{k: counters[k] for k in ("shed", "degraded")},
            **(service.engine.counts() if args.replicas > 1 else {}),
        },
        "error_rate": round(
            errors / max(1, len(lats) + errors + expired
                         + counters["shed"] + counters["degraded"]), 5),
        "qps": round(len(lats) / elapsed, 2) if elapsed > 0 else 0.0,
        "latency_ms": _lat_summary(lats),
        "batch_occupancy": health["batcher"]["occupancy"],
        "batcher": {k: v for k, v in health["batcher"].items()
                    if k != "occupancy"},
        "cache": health["cache"],
        "engine": health["engine"],
        "index": health["index"],
        "admission": health["admission"],
        "pool": health.get("pool"),
    }
    if tier_results is not None:
        # per-tier gate metrics: obs_report reads latency_ms_p99@<tier>
        # and error_rate@<tier> out of this block
        extra["tiers"] = _tier_block(tier_results, elapsed)
    if knee_report is not None:
        extra["knee"] = knee_report
    if ingest_counters is not None:
        idx_stats = health["index"]
        extra["ingest"] = {
            **ingest_counters,
            "generation": idx_stats.get("generation"),
            "swaps": idx_stats.get("swaps"),
            "swap_failures": idx_stats.get("swap_failures"),
            "pending_rows": idx_stats.get("pending_rows"),
            "corpus_size": idx_stats.get("size"),
        }
    # the versioned obs snapshot (OBSERVABILITY.md): registry metrics
    # (request counters, per-bucket occupancy, collect-time gauges) plus
    # the report keys above as extras — SERVE_BENCH_*.json and train
    # bench records are now diffable by one tool (scripts/obs_report.py).
    # run_id/process_index tag the report like every other artifact
    # (obs/runctx.py).
    from milnce_tpu.obs import export as obs_export
    from milnce_tpu.obs.runctx import auto_run_id

    report = obs_export.snapshot(service.registry, kind="serve_bench",
                                 extra=extra,
                                 run_id=auto_run_id("sbench-"),
                                 process_index=0)
    out = args.out or os.path.join(
        _REPO, f"SERVE_BENCH_{args.preset}_"
               f"{'tiers' if tier_qps else args.mode}.json")
    with open(out, "w") as fh:
        json.dump(report, fh, indent=2, sort_keys=True)
    res = report["resilience"]
    print(f"serve_bench: {report['requests']} requests in {elapsed:.2f}s "
          f"({report['qps']} QPS), p50={report['latency_ms']['p50']}ms "
          f"p99={report['latency_ms']['p99']}ms, cache hit rate "
          f"{report['cache']['hit_rate']:.2f}, "
          f"errors={report['errors']} expired={report['deadline_expired']} "
          f"shed={res['shed']} degraded={res['degraded']} "
          f"requeued={res.get('requeued', 0)} hedged={res.get('hedged', 0)} "
          f"quarantines={res.get('quarantines', 0)}, "
          f"recompiles={report['engine']['recompiles']} -> {out}")
    if report.get("tiers"):
        for t, td in report["tiers"].items():
            print(f"  tier {t}: offered {td['qps_offered']} qps, served "
                  f"{td['qps']} qps, p50={td['latency_ms']['p50']}ms "
                  f"p99={td['latency_ms']['p99']}ms, shed={td['shed']} "
                  f"errors={td['errors']} error_rate={td['error_rate']}")
    if report.get("knee"):
        for t, kd in report["knee"].items():
            print(f"  knee {t}: {kd['knee_qps']} qps @ p99<="
                  f"{kd['slo_ms']}ms ({len(kd['rounds'])} rounds)")
    if report.get("ingest"):
        ing = report["ingest"]
        print(f"  ingest: {ing['ingests']} ingests -> generation "
              f"{ing['generation']} ({ing['corpus_size']} rows live, "
              f"{ing['swaps']} swaps, {ing['swap_failures']} swap "
              f"failures, {ing['pending_rows']} pending)")
    index_recompiles = (report["index"] or {}).get("recompiles", 0)
    ok = (report["engine"]["recompiles"] in (0, -1)
          and index_recompiles in (0, -1, None))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
