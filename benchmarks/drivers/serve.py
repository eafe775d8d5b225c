"""Drives ``milnce_tpu.serving.service.build_server``'s
``RetrievalService`` — the path of ``milnce-serve`` — for one measured
window, and compares a sample of the answers the timed callers got with
the plain reference.

The run, in order (everything before the window is set-up):

1. weights from the seed (``benchmarks/weights.py``), written with the
   program's ``export_inference_checkpoint``; the corpus from the seed
   (``traffic_gen.corpus_block``), written as the ``emb`` array of an
   ``.npz`` — what a user hands ``milnce-serve``;
2. ``build_server`` (engine ladder precompiled, index uploaded and warmed
   up).  The HTTP server it returns is bound and never serves: the
   callers call ``RetrievalService.query_ids``, the method the handler
   calls;
3. the callers of the traffic file start; ``warmup_s`` later the window
   opens (caches and batcher in their steady state), ``--seconds`` later
   it closes: no caller sends after that, and every query sent inside is
   waited for.  In a traced run the profiler runs for ``trace_s``
   seconds inside the window;
4. the service is closed and freed, then the reference embeds a sample
   of the window's queries and scans the corpus, made again from the
   seed, block by block.
"""

from __future__ import annotations

import gc
import os
import shutil
import threading
import time

import numpy as np

from benchmarks import compare, flops, harness, trace_reduce, traffic_gen
from benchmarks import weights
from benchmarks.reference import retrieval as reference

SPAN_NAMES = ("query", "index.topk")
TRACE_START_S = 1.0


def build_argv(cell, work: str, platform: str) -> list:
    cfg = cell.config
    argv = ["--preset", cfg.get("preset", "full")]
    argv += harness.group_flags(cfg, ("model", "data", "serve"))
    argv += ["--parallel.platform", platform,
             "--serve.export_dir", os.path.join(work, "export"),
             "--serve.corpus_npz", os.path.join(work, "corpus.npz"),
             "--serve.port", "0"]
    return argv


def write_inputs(cell, cfg, seed: int, work: str) -> None:
    """The export and the corpus file, from the seed."""
    import jax

    from milnce_tpu.serving.export import export_inference_checkpoint

    flat = weights.make_weights(seed, weights.weight_shapes(
        cell.config["model"]))
    host = jax.device_get(flat)
    stats = jax.device_get(weights.batch_stats_for(flat))
    del flat
    export_inference_checkpoint(
        cfg.serve.export_dir, weights.nest(host), weights.nest(stats),
        cfg.model, max_words=cfg.data.max_words,
        video_shape=(cfg.data.num_frames, cfg.data.video_size,
                     cfg.data.video_size, 3),
        source="benchmarks/weights.py")
    del host, stats
    index = cell.config["index"]
    rows, dim = int(index["rows"]), int(index["dim"])
    emb = np.empty((rows, dim), np.dtype(index["stored_dtype"]))
    for block, (first, n) in enumerate(traffic_gen.corpus_blocks(rows)):
        emb[first:first + n] = jax.device_get(traffic_gen.corpus_block(
            seed, block, n, dim, index["stored_dtype"]))
    np.savez(cfg.serve.corpus_npz, emb=emb)


class Callers:
    """The closed loop: ``callers`` threads, each sending its next call of
    ``rows`` queries when the last is answered.  Every call is kept: when
    it was sent, when the answer came, what it asked and the answer."""

    def __init__(self, service, pool: np.ndarray, draws: np.ndarray,
                 rows: int, annotate: bool):
        self.service, self.pool, self.draws = service, pool, draws
        self.rows, self.annotate = rows, annotate
        self.stop = threading.Event()
        self.records = [[] for _ in range(draws.shape[0])]
        self.threads = [threading.Thread(target=self._loop, args=(c,),
                                         name=f"bench-caller-{c}",
                                         daemon=True)
                        for c in range(draws.shape[0])]

    def start(self):
        for t in self.threads:
            t.start()

    def finish(self, wait_s: float) -> int:
        """No caller sends after this; -> callers still waiting for an
        answer ``wait_s`` later (their query never came)."""
        self.stop.set()
        deadline = time.monotonic() + wait_s
        for t in self.threads:
            t.join(timeout=max(0.0, deadline - time.monotonic()))
        return sum(t.is_alive() for t in self.threads)

    def _loop(self, c: int):
        import contextlib

        import jax

        out, mine, i, n = self.records[c], self.draws[c], 0, self.rows
        while not self.stop.is_set():
            q = mine[np.arange(i, i + n) % len(mine)]
            i += n
            span = (jax.profiler.TraceAnnotation("query") if self.annotate
                    else contextlib.nullcontext())
            t0 = time.monotonic()
            try:
                with span:
                    scores, idx = self.service.query_ids(self.pool[q])
                out.append((t0, time.monotonic(), q, np.asarray(scores),
                            np.asarray(idx)))
            except Exception as exc:        # refused or failed: counted
                out.append((t0, time.monotonic(), q, None, repr(exc)))


def _annotate_index(index):
    """In a traced run: ``index.topk`` under a ``TraceAnnotation`` of
    the benchmark's own, so that idle gaps can be named."""
    import jax

    real = index.topk

    def topk(queries):
        with jax.profiler.TraceAnnotation("index.topk"):
            return real(queries)

    index.topk = topk


def reference_numbers(cell, seed: int, tokens, served_idx, served_scores,
                      precision: str = "float32") -> dict:
    """The reference over the sampled queries -> what is compared."""
    import jax

    model, index = cell.config["model"], cell.config["index"]
    rows, dim = int(index["rows"]), int(index["dim"])
    shapes = {n: s for n, s in weights.weight_shapes(model).items()
              if n.startswith("text_module/")}
    w = weights.make_weights(seed, shapes)
    q = reference.query_embeddings(w, tokens, precision)
    del w
    k = served_idx.shape[1]

    def blocks():
        for b, (first, n) in enumerate(traffic_gen.corpus_blocks(rows)):
            yield first, traffic_gen.corpus_block(seed, b, n, dim,
                                                  index["stored_dtype"])

    got = reference.scan(q, blocks(), served_idx, k, precision)
    q_norm = np.linalg.norm(np.asarray(jax.device_get(q), np.float64),
                            axis=1)
    got["q_norm"] = q_norm
    got["numbers"] = compare.retrieval_numbers(
        served_idx, served_scores, got["at_served"], got["top_scores"],
        q_norm, rows)
    return got


def run(cell, *, seed: int, seconds: float, trace: bool, work: str,
        platform: str = "", t_start: float | None = None,
        fault=None) -> dict:
    """One run of a serving cell.  ``fault`` (tests only): a function of
    the built service, applied before the callers start, that breaks the
    timed path underneath."""
    import jax

    from milnce_tpu.config import parse_cli
    from milnce_tpu.obs import spans as obs_spans
    from milnce_tpu.serving import service as serving
    from milnce_tpu.utils.compile_cache import configure_compile_cache

    t_start = time.monotonic() if t_start is None else t_start
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    configure_compile_cache()
    devices = jax.devices()[:cell.chips]
    traffic = cell.traffic
    cfg = parse_cli(build_argv(cell, work, platform))
    write_inputs(cell, cfg, seed, work)

    recorder = obs_spans.SpanRecorder(ring=1 << 20)
    prev_recorder = obs_spans.install(recorder)
    server, svc, index, engine = serving.build_server(cfg)
    os.remove(cfg.serve.corpus_npz)
    try:
        if fault is not None:
            fault(svc)
        if trace:
            _annotate_index(index)
        pool = traffic_gen.query_pool(seed, traffic, cfg.model.vocab_size,
                                      cfg.data.max_words)
        draws = traffic_gen.caller_draws(seed, traffic,
                                         per_caller=traffic.get(
                                             "draws_per_caller", 50_000))
        rows_per_call = int(traffic.get("rows_per_query", 1))
        callers = Callers(svc, pool, draws, rows_per_call, annotate=trace)
        callers.start()
        time.sleep(traffic["warmup_s"])
        t_open = time.monotonic()
        t_close = t_open + seconds
        trace_dir = os.path.join(work, "trace")
        trace_window = None
        if trace:
            time.sleep(TRACE_START_S)
            trace_reduce.start_trace(trace_dir)
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
                t_from = time.monotonic()
                time.sleep(min(traffic.get("trace_s", 3.0),
                               max(0.5, t_close - t_from - 1.0)))
                t_to = time.monotonic()
            jax.profiler.stop_trace()
            trace_window = (t_from, t_to)
        time.sleep(max(0.0, t_close - time.monotonic()))
        never_came = callers.finish(traffic["answer_wait_s"])
        peak = harness.peak_bytes_in_use(devices)
        recompiles = {"engine": engine.recompiles(),
                      "index": index.recompiles()}
        cache_stats = svc.cache.stats()
        scan_queries = index.bucket_for(rows_per_call)
    finally:
        serving.close_server(cfg, server, svc, index, engine)
        obs_spans.install(prev_recorder)
    events = recorder.tail()
    del server, svc, index, engine
    gc.collect()
    jax.clear_caches()

    # ---- the window: calls of ``rows_per_call`` queries each -------------
    sent = [r for rec in callers.records for r in rec
            if t_open <= r[0] < t_close]
    answered = [r for r in sent if r[3] is not None]
    failed = (len(sent) - len(answered) + never_came) * rows_per_call
    inside = [r for r in answered if r[1] <= t_close]
    if len(answered) < 20:
        raise RuntimeError(f"{len(answered)} calls answered in the "
                           "window: it is too short for this cell")
    lat_ms = [(r[1] - r[0]) * 1e3 for r in answered]
    metrics = {"queries_per_s": len(inside) * rows_per_call / seconds,
               "query_p95_ms": harness.percentile(lat_ms, 95),
               "setup_s": t_open - t_start}
    rows, dim = int(cell.config["index"]["rows"]), int(
        cell.config["index"]["dim"])
    text_flops = flops.text_fwd_flops(
        1, cfg.data.max_words, cfg.model.word_embedding_dim,
        cfg.model.text_hidden_dim, cfg.model.embedding_dim)
    record = harness.RunRecord(
        cell=cell, peaks=None, window_s=seconds,
        events=[e for e in events
                if t_open <= e.get("mono", 0.0) <= t_close],
        extra={"index_rows": rows, "index_dim": dim,
               "scan_queries": scan_queries,
               "work_per_item_flops": 2.0 * rows * dim + text_flops})
    if trace:
        red = trace_reduce.reduce_trace(
            trace_reduce.find_xplane(trace_dir),
            layout=(trace_reduce.TPU_LAYOUT if devices[0].platform == "tpu"
                    else trace_reduce.CPU_LAYOUT),
            span_names=SPAN_NAMES,
            chips=cell.chips if devices[0].platform == "tpu" else None)
        record.trace = red
        record.traced_work = float(rows_per_call * sum(
            1 for r in answered
            if trace_window[0] <= r[1] <= trace_window[1]))

    # ---- correct: a sample of the answers against the reference ----------
    asked = np.concatenate([r[2] for r in answered])
    longest = int(np.argmax((pool[asked] != 0).sum(axis=1)))
    picks = traffic_gen.compare_sample(seed, len(asked),
                                       traffic["compare_sample"],
                                       always=(longest,))
    tokens = pool[asked[picks]]
    served_scores = np.concatenate([r[3] for r in answered])[picks]
    served_idx = np.concatenate([r[4] for r in answered])[picks]
    ref = reference_numbers(cell, seed, tokens, served_idx, served_scores)
    numbers = ref["numbers"]
    compared = {k: {"value": numbers[k], "limit": cell.limits[k]}
                for k in ("rank_gap", "score_err")}
    compared["unanswered"] = {"value": float(failed), "limit": 0.0}
    return {"metrics": metrics, "attempted": len(sent) * rows_per_call,
            "failed": failed,
            "record": record, "compared": compared, "peak_bytes": peak,
            "numbers": numbers,
            "notes": {"recompiles": recompiles, "cache": cache_stats,
                      "compared_answers": int(len(picks)),
                      "answered_in_window": len(inside) * rows_per_call,
                      "latency_p50_ms": harness.percentile(lat_ms, 50)}}

