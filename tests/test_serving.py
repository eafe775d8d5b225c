"""Serving subsystem gates (ISSUE 4): bucketed engine, device-resident
index, cache, service, HTTP front — and the served-vs-offline parity
pin: top-k through the full batcher -> engine -> index path must equal
the offline eval/retrieval.py ranking exactly.

Everything runs on the hermetic 8-virtual-CPU mesh (conftest.py); one
module-scoped stack keeps the compile bill to one warmup sweep."""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

_FRAMES, _SIZE, _WORDS = 4, 32, 6
_CORPUS = 21


@pytest.fixture(scope="module")
def stack():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from milnce_tpu.data.tokenizer import Tokenizer, synthetic_vocab
    from milnce_tpu.models import S3D
    from milnce_tpu.serving.cache import EmbeddingLRUCache
    from milnce_tpu.serving.engine import InferenceEngine
    from milnce_tpu.serving.index import DeviceRetrievalIndex
    from milnce_tpu.serving.service import RetrievalService

    model = S3D(num_classes=16, vocab_size=64, word_embedding_dim=8,
                text_hidden_dim=16, inception_blocks=1)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, _FRAMES, _SIZE, _SIZE, 3)),
                           jnp.zeros((1, _WORDS), jnp.int32))
    mesh = Mesh(np.array(jax.devices()), ("data",))
    engine = InferenceEngine(model, dict(variables), mesh,
                             text_words=_WORDS,
                             video_shape=(_FRAMES, _SIZE, _SIZE, 3),
                             max_batch=16)
    rng = np.random.default_rng(0)
    clips = rng.integers(0, 255, (_CORPUS, _FRAMES, _SIZE, _SIZE, 3),
                         dtype=np.uint8)
    corpus_emb = np.concatenate(
        [engine.embed_video(clips[:16]), engine.embed_video(clips[16:])])
    index = DeviceRetrievalIndex(mesh, corpus_emb, k=5,
                                 query_buckets=engine.buckets)
    service = RetrievalService(
        engine, index, tokenizer=Tokenizer(synthetic_vocab(63), _WORDS),
        cache=EmbeddingLRUCache(128))
    yield dict(model=model, variables=variables, mesh=mesh, engine=engine,
               clips=clips, corpus_emb=corpus_emb, index=index,
               service=service)
    service.close()


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

class TestEngine:
    def test_bucket_ladder_on_the_test_mesh(self, stack):
        # 8 virtual devices -> ladder starts at the mesh size
        assert stack["engine"].buckets == (8, 16)

    @pytest.mark.parametrize("n,bucket", [(1, 8), (8, 8), (9, 16), (16, 16)])
    def test_bucket_for_boundaries(self, stack, n, bucket):
        assert stack["engine"].bucket_for(n) == bucket

    def test_oversize_batch_rejected(self, stack):
        with pytest.raises(ValueError, match="max_batch"):
            stack["engine"].bucket_for(17)

    def test_wrong_trailing_shape_rejected(self, stack):
        eng = stack["engine"]
        with pytest.raises(ValueError, match="token ids"):
            eng.embed_text(np.zeros((2, _WORDS + 1), np.int32))
        with pytest.raises(ValueError, match="uint8 video"):
            eng.embed_video(np.zeros((2, _FRAMES, _SIZE, 16, 3), np.uint8))

    def test_pad_unpad_identity(self, stack):
        """Rows of a padded partial batch == the same rows embedded in a
        full bucket: padding slots never leak into real rows."""
        eng = stack["engine"]
        rng = np.random.default_rng(1)
        ids = rng.integers(1, 64, (5, _WORDS)).astype(np.int32)
        five = eng.embed_text(ids)                     # pads 5 -> 8
        singles = np.stack([eng.embed_text(ids[i:i + 1])[0]  # pads 1 -> 8
                            for i in range(5)])
        np.testing.assert_allclose(five, singles, rtol=1e-5, atol=1e-6)

    def test_ladder_sweep_causes_zero_recompiles(self, stack):
        eng = stack["engine"]
        rng = np.random.default_rng(2)
        for n in (1, 3, 8, 11, 16):
            eng.embed_text(rng.integers(1, 64, (n, _WORDS)).astype(np.int32))
            eng.embed_video(rng.integers(
                0, 255, (n, _FRAMES, _SIZE, _SIZE, 3), dtype=np.uint8))
        assert eng.recompiles() == 0

    def test_concurrent_call_accounting_is_exact(self, stack):
        """ISSUE 7 regression: the engine's per-(entry, bucket) call
        dict is written from the batcher worker AND request threads
        while /healthz readers iterate it — the old unlocked
        read-modify-write lost increments under contention (graftlint
        GL010).  N threads x K embeds must land EXACTLY N*K counts,
        with stats() readers racing the whole time."""
        eng = stack["engine"]
        key = "text@8"
        before = eng.stats()["calls"].get(key, 0)
        n_threads, k = 6, 4
        ids = np.ones((1, _WORDS), np.int32)
        stop = threading.Event()
        errors = []

        def embedder():
            try:
                for _ in range(k):
                    eng.embed_text(ids)
            except Exception as exc:  # pragma: no cover - the assert
                errors.append(exc)    # below is the real check

        def reader():
            while not stop.is_set():
                s = eng.stats()
                assert s["calls"].get(key, 0) >= before

        threads = [threading.Thread(target=embedder)
                   for _ in range(n_threads)]
        readers = [threading.Thread(target=reader) for _ in range(2)]
        for t in readers + threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        stop.set()
        for t in readers:
            t.join(timeout=30)
        assert not errors, errors
        assert eng.stats()["calls"][key] == before + n_threads * k


# ---------------------------------------------------------------------------
# index
# ---------------------------------------------------------------------------

class TestIndex:
    def test_topk_matches_numpy_ranking(self, stack):
        index, corpus_emb = stack["index"], stack["corpus_emb"]
        rng = np.random.default_rng(3)
        q = rng.standard_normal((5, corpus_emb.shape[1])).astype(np.float32)
        scores, idx = index.topk(q)
        ref = np.argsort(-(q @ corpus_emb.T), axis=1)[:, :index.k]
        assert np.array_equal(idx, ref)
        np.testing.assert_allclose(
            scores, np.take_along_axis(q @ corpus_emb.T, ref, axis=1),
            rtol=1e-5, atol=1e-5)

    def test_pad_rows_never_retrieved(self, stack):
        # every returned index addresses a REAL corpus row (pads are -inf)
        index = stack["index"]
        rng = np.random.default_rng(4)
        q = rng.standard_normal((3, index.dim)).astype(np.float32)
        _, idx = index.topk(q)
        assert idx.max() < index.size

    def test_query_bucket_overflow_rejected(self, stack):
        index = stack["index"]
        with pytest.raises(ValueError, match="query bucket"):
            index.topk(np.zeros((17, index.dim), np.float32))

    def test_k_bounds_validated(self, stack):
        from milnce_tpu.serving.index import DeviceRetrievalIndex

        with pytest.raises(ValueError, match="outside"):
            DeviceRetrievalIndex(stack["mesh"], stack["corpus_emb"],
                                 k=_CORPUS + 1, precompile=False)

    def test_concurrent_topk_call_accounting_is_exact(self, stack):
        """ISSUE 7 regression: `self._calls += 1` straight off request
        threads lost increments (graftlint GL010) — N threads x K
        queries must count exactly."""
        index = stack["index"]
        before = index.stats()["calls"]
        n_threads, k = 6, 4
        q = np.zeros((1, index.dim), np.float32)
        threads = [threading.Thread(
            target=lambda: [index.topk(q) for _ in range(k)])
            for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert index.stats()["calls"] == before + n_threads * k

    def test_geometry_follows_data_axis_on_a_model_parallel_mesh(self,
                                                                 stack):
        """On a (data, model) mesh, rows shard over DATA only (P(data)
        replicates over model) — geometry sized by the total device
        count would mask most of every shard's corpus to -inf and
        silently drop it from retrieval."""
        import jax
        from jax.sharding import Mesh

        from milnce_tpu.serving.index import DeviceRetrievalIndex

        mesh2d = Mesh(np.array(jax.devices()).reshape(4, 2),
                      ("data", "model"))
        corpus_emb = stack["corpus_emb"]
        index = DeviceRetrievalIndex(mesh2d, corpus_emb, k=5,
                                     query_buckets=(4,))
        rng = np.random.default_rng(6)
        q = rng.standard_normal((4, corpus_emb.shape[1])).astype(np.float32)
        _, idx = index.topk(q)
        ref = np.argsort(-(q @ corpus_emb.T), axis=1)[:, :5]
        assert np.array_equal(idx, ref)

    def test_engine_bucket_ladder_follows_data_axis(self, stack):
        import jax
        from jax.sharding import Mesh

        from milnce_tpu.serving.engine import InferenceEngine

        mesh2d = Mesh(np.array(jax.devices()).reshape(4, 2),
                      ("data", "model"))
        eng = InferenceEngine(
            stack["model"], dict(stack["variables"]), mesh2d,
            text_words=_WORDS, video_shape=(_FRAMES, _SIZE, _SIZE, 3),
            max_batch=16, precompile=False)
        assert eng.buckets == (4, 8, 16)   # data extent 4, not 8 devices


# ---------------------------------------------------------------------------
# service (cache + batcher + engine + index) and the parity pin
# ---------------------------------------------------------------------------

class TestService:
    def test_served_topk_equals_offline_eval_ranking(self, stack):
        """ISSUE 4 acceptance: a synthetic corpus queried through the
        FULL serve path (token rows -> dynamic batcher -> bucketed
        engine -> sharded device index) ranks exactly as the offline
        eval/retrieval.py extraction + argsort."""
        from milnce_tpu.eval.retrieval import extract_retrieval_embeddings

        clips, service = stack["clips"], stack["service"]
        rng = np.random.default_rng(5)
        texts = rng.integers(1, 64, (_CORPUS, _WORDS)).astype(np.int32)

        class _Source:
            def __len__(self):
                return _CORPUS

            def sample(self, i, rng=None):
                return {"video": clips[i:i + 1], "text": texts[i:i + 1]}

        t_emb, v_emb = extract_retrieval_embeddings(
            stack["model"], dict(stack["variables"]), _Source(),
            stack["mesh"], batch_size=8)
        offline = np.argsort(-(t_emb @ v_emb.T), axis=1)[:, :5]

        # serve the same corpus: many threads, one row each, so the
        # batcher actually batches (not one pre-formed request)
        results = [None] * _CORPUS

        def one(i):
            _, idx = service.query_ids(texts[i:i + 1])
            results[i] = idx[0]

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(_CORPUS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        served = np.stack(results)
        assert np.array_equal(served, offline), (
            "served top-k diverged from the offline eval ranking")
        # the batcher actually coalesced: fewer flushes than requests
        flushes = service.health()["batcher"]["flushes"]
        assert flushes < _CORPUS

    def test_pooled_serving_matches_single_engine_and_offline_ranking(
            self, stack):
        """ISSUE 10 parity pin: pooled serving (2 single-device replicas,
        concurrent request threads, hedge/requeue machinery in place)
        returns rankings EXACTLY equal to the single-engine path and the
        offline argsort for the same queries — replicas are exact peers
        of the 8-device engine (the embed programs are collective-free
        row-wise maps, so device-group shape cannot change the math)."""
        from milnce_tpu.obs import metrics as obs_metrics
        from milnce_tpu.serving.cache import EmbeddingLRUCache
        from milnce_tpu.serving.pool import ReplicaPool
        from milnce_tpu.serving.service import RetrievalService

        engine, index = stack["engine"], stack["index"]
        rng = np.random.default_rng(9)
        texts = rng.integers(1, 64, (_CORPUS, _WORDS)).astype(np.int32)
        t_emb = np.concatenate([engine.embed_text(texts[:16]),
                                engine.embed_text(texts[16:])])
        offline = np.argsort(-(t_emb @ stack["corpus_emb"].T),
                             axis=1)[:, :5]
        single = np.stack([stack["service"].query_ids(texts[i:i + 1])[1][0]
                           for i in range(_CORPUS)])
        pool = ReplicaPool.build(
            stack["model"], dict(stack["variables"]), 2,
            text_words=_WORDS, video_shape=(_FRAMES, _SIZE, _SIZE, 3),
            max_batch=8, min_bucket=4,
            registry=obs_metrics.MetricsRegistry())
        service = RetrievalService(pool, index,
                                   cache=EmbeddingLRUCache(0))
        try:
            results = [None] * _CORPUS

            def one(i):
                _, idx = service.query_ids(texts[i:i + 1])
                results[i] = idx[0]

            threads = [threading.Thread(target=one, args=(i,))
                       for i in range(_CORPUS)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            served = np.stack(results)
            assert np.array_equal(served, offline), (
                "pooled top-k diverged from the offline eval ranking")
            assert np.array_equal(served, single), (
                "pooled top-k diverged from the single-engine path")
            assert pool.recompiles() == 0
        finally:
            service.close()
            pool.close()

    def test_cache_hits_skip_the_device(self, stack):
        service = stack["service"]
        ids = np.full((1, _WORDS), 7, np.int32)
        service.embed_text_ids(ids)
        calls_before = dict(service.engine.stats()["calls"])
        before_hits = service.cache.stats()["hits"]
        out = service.embed_text_ids(ids)
        assert service.cache.stats()["hits"] == before_hits + 1
        assert service.engine.stats()["calls"] == calls_before  # no dispatch
        assert out.shape == (1, service.engine.embed_dim)

    def test_query_k_validation(self, stack):
        with pytest.raises(ValueError, match="outside"):
            stack["service"].query_ids(np.ones((1, _WORDS), np.int32), k=99)

    def test_health_surfaces_resilience_counters(self, stack):
        h = stack["service"].health()
        assert h["status"] == "ok"
        assert h["engine"]["recompiles"] == 0
        assert h["index"]["recompiles"] == 0
        for key in ("requests", "flushes", "deadline_expired",
                    "batch_errors", "occupancy"):
            assert key in h["batcher"]
        assert 0.0 <= h["cache"]["hit_rate"] <= 1.0


# ---------------------------------------------------------------------------
# LRU cache (host-only)
# ---------------------------------------------------------------------------

class TestCache:
    def test_lru_eviction_order(self):
        from milnce_tpu.serving.cache import EmbeddingLRUCache

        c = EmbeddingLRUCache(capacity=2)
        c.put((1,), np.array([1.0]))
        c.put((2,), np.array([2.0]))
        assert c.get((1,)) is not None      # refresh 1 -> 2 is now LRU
        c.put((3,), np.array([3.0]))
        assert c.get((2,)) is None
        assert c.get((1,)) is not None and c.get((3,)) is not None

    def test_disabled_cache_never_stores(self):
        from milnce_tpu.serving.cache import EmbeddingLRUCache

        c = EmbeddingLRUCache(capacity=0)
        c.put((1,), np.array([1.0]))
        assert c.get((1,)) is None and len(c) == 0

    def test_stored_rows_are_immutable(self):
        from milnce_tpu.serving.cache import EmbeddingLRUCache

        c = EmbeddingLRUCache(capacity=4)
        c.put((1,), np.array([1.0, 2.0]))
        row = c.get((1,))
        with pytest.raises(ValueError):
            row[0] = 99.0


# ---------------------------------------------------------------------------
# HTTP front
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def http_server(stack):
    from milnce_tpu.serving.service import serve_http

    server = serve_http(stack["service"], port=0)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    yield f"http://127.0.0.1:{server.server_address[1]}"
    server.shutdown()
    server.server_close()


def _post(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as r:
        return r.status, json.loads(r.read())


class TestHTTP:
    def test_healthz(self, http_server):
        with urllib.request.urlopen(f"{http_server}/healthz",
                                    timeout=30) as r:
            body = json.loads(r.read())
        assert r.status == 200 and body["status"] == "ok"
        assert body["engine"]["recompiles"] == 0

    def test_query_by_sentences(self, stack, http_server):
        status, body = _post(f"{http_server}/v1/query",
                             {"sentences": ["word1 word2"], "k": 3})
        assert status == 200
        (res,) = body["results"]
        assert len(res["indices"]) == 3 == len(res["scores"])
        assert all(0 <= i < stack["index"].size for i in res["indices"])

    def test_query_by_token_ids_matches_programmatic(self, stack,
                                                     http_server):
        ids = [[1, 2, 3, 0, 0, 0]]
        status, body = _post(f"{http_server}/v1/query", {"token_ids": ids})
        assert status == 200
        _, idx = stack["service"].query_ids(np.asarray(ids, np.int32))
        assert body["results"][0]["indices"] == idx[0].tolist()

    def test_embed_endpoint(self, stack, http_server):
        status, body = _post(f"{http_server}/v1/embed_text",
                             {"token_ids": [[1, 2, 3, 0, 0, 0]]})
        assert status == 200
        assert np.asarray(body["embeddings"]).shape == (
            1, stack["service"].engine.embed_dim)

    def test_bad_request_is_400(self, http_server):
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post(f"{http_server}/v1/query", {"nonsense": True})
        assert exc.value.code == 400

    def test_unknown_route_is_404(self, http_server):
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post(f"{http_server}/v1/nope", {})
        assert exc.value.code == 404

    def test_metrics_prometheus_exposition(self, stack, http_server):
        """ISSUE 5 acceptance: GET /metrics on a live service returns
        valid Prometheus text — request counters, batcher occupancy
        histogram, cache hit rate, recompile gauge."""
        # guarantee traffic has flowed through the request path
        stack["service"].query_ids(
            np.zeros((1, stack["service"].engine.text_words), np.int32))
        with urllib.request.urlopen(f"{http_server}/metrics",
                                    timeout=30) as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith(
                "text/plain; version=0.0.4")
            text = r.read().decode()
        assert "# TYPE milnce_serve_requests_total counter" in text
        assert "# TYPE milnce_serve_batch_occupancy histogram" in text
        assert 'milnce_serve_batch_occupancy_bucket{batcher="text",' in text
        assert "# TYPE milnce_serve_cache_hit_rate gauge" in text
        assert "milnce_serve_engine_recompiles 0" in text
        assert "milnce_serve_queries_total" in text
        # /healthz keys stay backward-compatible AND agree with the
        # exposition (one source of truth for both surfaces)
        health = stack["service"].health()
        assert (f"milnce_serve_queries_total {health['queries']}"
                in text)
        assert (f"milnce_serve_requests_total{{batcher=\"text\"}} "
                f"{health['batcher']['requests']}" in text)

    def test_obs_events_ring_over_http(self, stack, http_server):
        stack["service"].query_ids(
            np.zeros((1, stack["service"].engine.text_words), np.int32))
        with urllib.request.urlopen(f"{http_server}/obs/events?n=50",
                                    timeout=30) as r:
            body = json.loads(r.read())
        events = body["events"]
        assert isinstance(events, list) and len(events) <= 50
        # the batcher worker's flush spans land on the process recorder
        assert any(e.get("name") == "batcher.flush" for e in events)

    def test_obs_events_bad_n_is_400(self, http_server):
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(f"{http_server}/obs/events?n=abc",
                                   timeout=30)
        assert exc.value.code == 400

    def test_obs_events_since_filters_incrementally(self, stack,
                                                    http_server):
        """ISSUE 9 satellite: ?since=<mono> returns only records
        appended after that cursor, so pollers stop re-downloading the
        whole ring."""
        words = stack["service"].engine.text_words
        stack["service"].query_ids(
            np.zeros((1, words), np.int32))
        with urllib.request.urlopen(f"{http_server}/obs/events",
                                    timeout=30) as r:
            events = json.loads(r.read())["events"]
        assert events and all("mono" in e for e in events)
        cursor = events[-1]["mono"]
        with urllib.request.urlopen(
                f"{http_server}/obs/events?since={cursor}",
                timeout=30) as r:
            assert json.loads(r.read())["events"] == []
        # new traffic -> only the new records come back.  The row must
        # be a row no test has embedded before: a repeat is a CACHE HIT
        # answered on host — no flush, no new events (that's the cache
        # working, not the filter failing)
        fresh = (np.arange(words, dtype=np.int32)[None, :] % 50) + 11
        stack["service"].query_ids(fresh)
        with urllib.request.urlopen(
                f"{http_server}/obs/events?since={cursor}",
                timeout=30) as r:
            newer = json.loads(r.read())["events"]
        assert newer and all(e["mono"] > cursor for e in newer)

    def test_obs_events_bad_since_is_400(self, http_server):
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(
                f"{http_server}/obs/events?since=yesterday", timeout=30)
        assert exc.value.code == 400

    def test_obs_capture_404_without_capture(self, http_server):
        # this module's service is built without a ProfilerCapture
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post(f"{http_server}/obs/capture", {})
        assert exc.value.code == 404

    def test_obs_capture_arms_injected_capture(self, stack, tmp_path):
        """POST /obs/capture arms the bounded one-shot capture; the
        budget's refusal reason comes back as JSON (ISSUE 9)."""
        from milnce_tpu.obs.capture import ProfilerCapture
        from milnce_tpu.serving.service import serve_http

        calls = {"start": 0, "stop": 0}
        cap = ProfilerCapture(
            str(tmp_path), duration_s=1000.0, max_captures=1,
            start_fn=lambda d: calls.__setitem__("start",
                                                calls["start"] + 1),
            stop_fn=lambda: calls.__setitem__("stop", calls["stop"] + 1))
        service = stack["service"]
        old_cap = service.capture
        service.capture = cap
        server = serve_http(service, port=0)
        thread = threading.Thread(target=server.serve_forever, daemon=True)
        thread.start()
        base = f"http://127.0.0.1:{server.server_address[1]}"
        try:
            status, body = _post(f"{base}/obs/capture",
                                 {"reason": "drill"})
            assert status == 200 and body["armed"]
            assert "capture_001-drill" in body["trace_dir"]
            assert calls["start"] == 1
            # active -> refused with a reason, not double-started
            status, body = _post(f"{base}/obs/capture", {})
            assert status == 200 and not body["armed"]
            assert "reason" in body
            cap.stop()
            assert calls["stop"] == 1
        finally:
            service.capture = old_cap
            server.shutdown()
            server.server_close()

# ---------------------------------------------------------------------------
# the request path's own clock (ISSUE 26): dispatch / query / queue wait
# ---------------------------------------------------------------------------

@pytest.fixture
def ring():
    """A fresh process-default recorder for one test."""
    from milnce_tpu.obs import spans

    rec = spans.SpanRecorder(ring=4096)
    prev = spans.install(rec)
    yield rec
    spans.install(prev)


def _named(rec, name, **attrs):
    return [r for r in rec.tail() if r.get("name") == name
            and all(r.get(k) == v for k, v in attrs.items())]


def _fresh_rows(seed, n=1):
    return np.random.default_rng(seed).integers(
        1, 64, (n, _WORDS)).astype(np.int32)


# a kernel that accounts CPU time by the tick (the chip's host: 10 ms)
# reads a thread's CPU up to one tick above its wall time
_TICK_MS = 10.5


class TestDispatchSpans:
    def _drive(self, stack, site):
        """One hold of the lock at ``site`` -> (rows, bucket) it carried."""
        eng = stack["engine"]
        if site == "engine.text":
            eng.embed_text(np.ones((3, _WORDS), np.int32))
            return 3, 8
        if site == "engine.video":
            eng.embed_video(stack["clips"][:9])
            return 9, 16
        if site == "index.topk":
            stack["index"].topk(stack["corpus_emb"][:2])
            return 2, 8
        raise AssertionError(site)

    @pytest.mark.parametrize("site", ["engine.text", "engine.video",
                                      "index.topk"])
    def test_one_dispatch_record_per_hold(self, stack, ring, site):
        rows, bucket = self._drive(stack, site)
        (rec,) = _named(ring, "dispatch")
        assert rec["kind"] == "span" and rec["site"] == site
        assert (rec["rows"], rec["bucket"]) == (rows, bucket)
        assert rec["lock_wait_ms"] >= 0 and rec["hold_ms"] > 0
        # wait and hold lie inside the span, the three legs inside the hold
        assert rec["lock_wait_ms"] + rec["hold_ms"] <= rec["dur_ms"] + 0.01
        legs = rec["put_ms"] + rec["call_ms"] + rec["get_ms"]
        assert 0 < legs <= rec["hold_ms"] + 0.01

    @pytest.mark.parametrize("site", ["engine.text", "engine.video",
                                      "index.topk"])
    def test_a_hold_carries_its_holders_cpu_time(self, stack, ring, site):
        """``cpu_ms``: the holder's thread's CPU time from acquire to
        release, beside ``hold_ms`` (a hold far longer than that and the
        program's device time waited for the interpreter)."""
        self._drive(stack, site)
        (rec,) = _named(ring, "dispatch")
        assert 0 <= rec["cpu_ms"] <= rec["hold_ms"] + _TICK_MS

    def test_a_waiting_hold_has_little_cpu_time(self):
        """A holder that blocks inside its hold: wall, no CPU."""
        import time

        from milnce_tpu.analysis.lockrt import make_lock
        from milnce_tpu.obs.spans import SpanRecorder
        from milnce_tpu.serving.engine import device_dispatch

        rec = SpanRecorder()
        with device_dispatch("index.upload", lock=make_lock("test.cpu"),
                             recorder=rec):
            time.sleep(0.05)
        (r,) = _named(rec, "dispatch")
        assert r["hold_ms"] >= 50.0 and r["cpu_ms"] <= 10.0

    def test_live_index_sites_upload_and_topk(self, stack, ring):
        from milnce_tpu.obs.spans import SpanRecorder
        from milnce_tpu.serving.live_index import LiveRetrievalIndex

        mine = SpanRecorder()       # the injected recorder gets them
        live = LiveRetrievalIndex(stack["mesh"], stack["corpus_emb"], k=5,
                                  query_buckets=(8,), recorder=mine)
        try:
            (up,) = _named(mine, "dispatch", site="index.upload")
            assert up["rows"] == _CORPUS and up["bucket"] >= 5
            assert 0 <= up["cpu_ms"] <= up["hold_ms"] + _TICK_MS
            assert up["put_ms"] <= up["hold_ms"] + 0.01
            warm = len(_named(mine, "dispatch", site="index.topk"))
            assert warm == 1            # the one query bucket, warmed
            live.topk(stack["corpus_emb"][:3])
            recs = _named(mine, "dispatch", site="index.topk")
            assert len(recs) == warm + 1
            assert (recs[-1]["rows"], recs[-1]["bucket"]) == (3, 8)
            assert recs[-1]["lock_wait_ms"] + recs[-1]["hold_ms"] \
                <= recs[-1]["dur_ms"] + 0.01
            assert not _named(ring, "dispatch")
        finally:
            live.close()

    def test_second_contender_waits_out_the_first_hold(self):
        """Two threads ask for one lock while a third holds it: whoever
        gets it second waited at least as long as the first held it.
        The lock says who waits in it, so the holder lets go only once
        both do; it lets in the one that asked LAST first, so that the
        first to get it did not start asking later than the other."""
        import time

        from milnce_tpu.obs.spans import SpanRecorder
        from milnce_tpu.serving.engine import device_dispatch

        class NewestFirst:
            """A lock that counts who waits in ``acquire`` and lets the
            newest of them in first."""

            def __init__(self):
                self._cv = threading.Condition()
                self.waiting, self._held = [], False

            def acquire(self):
                me = object()
                with self._cv:
                    self.waiting.append(me)
                    self._cv.notify_all()
                    self._cv.wait_for(lambda: not self._held
                                      and self.waiting[-1] is me)
                    self.waiting.remove(me)
                    self._held = True

            def release(self):
                with self._cv:
                    self._held = False
                    self._cv.notify_all()

            def asking(self, n):
                with self._cv:
                    assert self._cv.wait_for(
                        lambda: len(self.waiting) == n, timeout=10)

        rec, lock = SpanRecorder(), NewestFirst()

        def contender(i):
            with device_dispatch("test.site", lock=lock, recorder=rec,
                                 rows=i):
                time.sleep(0.03)

        threads = [threading.Thread(target=contender, args=(i,))
                   for i in range(2)]
        lock.acquire()
        for n, t in enumerate(threads, 1):
            t.start()
            lock.asking(n)              # inside acquire() now, seen
        lock.release()
        for t in threads:
            t.join(timeout=10)
            assert not t.is_alive()
        first, second = sorted(_named(rec, "dispatch"),
                               key=lambda r: r["lock_wait_ms"])
        assert first["hold_ms"] >= 30.0
        assert second["lock_wait_ms"] >= first["lock_wait_ms"] \
            + first["hold_ms"] - 1.0
        assert second["lock_wait_ms"] >= first["hold_ms"]

    def test_a_failing_hold_still_records_and_frees_the_lock(self):
        from milnce_tpu.obs.spans import SpanRecorder
        from milnce_tpu.serving.engine import device_dispatch

        rec, lock = SpanRecorder(), threading.Lock()
        with pytest.raises(KeyError):
            with device_dispatch("test.site", lock=lock, recorder=rec):
                raise KeyError("boom")
        (r,) = _named(rec, "dispatch")
        assert r["error"] == "KeyError" and "hold_ms" in r
        assert not lock.locked()

    # ---- deferred host work inside a hold ------------------------------

    @staticmethod
    def _program(order, value):
        """A program for ``round_trip`` that lists its call and its fetch
        (``jax.device_get`` calls ``copy_to_host_async`` first)."""

        class Fetch:
            def copy_to_host_async(self):
                order.append("get")

            def __array__(self, dtype=None, copy=None):
                return value

        def call(x):
            order.append("call")
            return Fetch()

        return call

    def test_deferred_work_runs_between_the_call_and_the_get(self):
        import time

        from milnce_tpu.obs.spans import SpanRecorder
        from milnce_tpu.serving.engine import (defer, device_dispatch,
                                               take_deferred)

        rec, lock, order = SpanRecorder(), threading.Lock(), []

        def work(site):
            order.append(("work", site))
            time.sleep(0.02)

        rows = np.arange(6, dtype=np.float32)
        defer(work, 3)
        with device_dispatch("index.topk", lock=lock, recorder=rec) as hold:
            out = hold.round_trip(self._program(order, rows * 2), rows,
                                  None)
        np.testing.assert_array_equal(out, rows * 2)
        assert order == ["call", ("work", "index.topk"), "get"]
        (r,) = _named(rec, "dispatch")
        assert r["overlap_rows"] == 3 and r["overlap_ms"] >= 20.0
        assert "overlap_error" not in r
        # the legs and the overlap lie inside the hold, one after another
        legs = r["put_ms"] + r["call_ms"] + r["overlap_ms"] + r["get_ms"]
        assert legs <= r["hold_ms"] + 0.01
        assert r["call_ms"] < 20.0 and r["get_ms"] < 20.0
        assert take_deferred() is None          # it ran once, and is gone
        with device_dispatch("index.topk", lock=lock, recorder=rec) as hold:
            hold.round_trip(self._program(order, rows), rows, None)
        assert "overlap_ms" not in _named(rec, "dispatch")[-1]

    def test_deferred_work_that_raises_fails_neither_the_hold_nor_its_lock(
            self):
        from milnce_tpu.obs.spans import SpanRecorder
        from milnce_tpu.serving.engine import defer, device_dispatch

        rec, lock, order = SpanRecorder(), threading.Lock(), []

        def work(site):
            raise KeyError("its callers' to see")

        rows = np.ones(4, np.float32)
        defer(work, 2)
        with device_dispatch("engine.text", lock=lock, recorder=rec) as hold:
            out = hold.round_trip(self._program(order, rows), rows, None)
        np.testing.assert_array_equal(out, rows)
        assert order == ["call", "get"] and not lock.locked()
        (r,) = _named(rec, "dispatch")
        assert r["overlap_error"] == "KeyError" and r["overlap_rows"] == 2
        assert "error" not in r and "get_ms" in r

    def test_a_wrapper_that_replaces_index_topk_still_carries_it(self, stack,
                                                                ring):
        """The deferred work is the calling thread's: a one-argument
        wrapper on the instance (the benchmark's traced-run annotation
        and fault hook do this) passes it on without knowing."""
        import jax

        from milnce_tpu.serving.engine import defer, take_deferred

        index, ran = stack["index"], []
        real = index.topk

        def wrapped(queries):
            with jax.profiler.TraceAnnotation("index.topk"):
                return real(queries)

        index.topk = wrapped
        try:
            defer(ran.append, 2)
            scores, idx = index.topk(stack["corpus_emb"][:2])
        finally:
            del index.topk                      # the class's method again
        assert ran == ["index.topk"] and take_deferred() is None
        assert idx.shape == (2, 5)
        (r,) = _named(ring, "dispatch", site="index.topk")
        assert r["overlap_rows"] == 2 and r["overlap_ms"] >= 0

    def test_warm_up_times_every_rung_of_both_programs(self, stack):
        """What the device worker's order compares: the text program's
        and the pass's device time at every rung, from warm-up."""
        engine, index = stack["engine"], stack["index"]
        assert sorted(engine.text_device_ms) == list(engine.buckets)
        assert sorted(index.device_ms) == list(index.query_buckets)
        assert all(ms > 0 for ms in engine.text_device_ms.values())
        assert all(ms > 0 for ms in index.device_ms.values())
        assert stack["service"]._device_ms == (engine.text_device_ms,
                                               index.device_ms)
        assert engine.recompiles() == 0 and index.recompiles() == 0

    def test_the_lock_is_taken_only_through_device_dispatch(self):
        """A grep: under milnce_tpu/serving/ nothing enters a dispatch
        lock or the transfer guard on its own."""
        import os
        import re

        import milnce_tpu.serving as serving

        root = os.path.dirname(serving.__file__)
        lock = r"(?:[\w.]*\.)?(?:DEVICE_DISPATCH_LOCK|_dispatch_lock)\b"
        taken = re.compile(rf"with\s+{lock}|{lock}\.acquire\("
                           r"|transfer_guard\(")
        hits = []
        for name in sorted(os.listdir(root)):
            if not name.endswith(".py"):
                continue
            with open(os.path.join(root, name)) as fh:
                src = fh.read()
            if name == "engine.py":     # the one place that may
                head, rest = src.split("def device_dispatch(", 1)
                body, tail = rest.split("\ndef ", 1)
                assert "lock.acquire()" in body
                assert 'transfer_guard("disallow")' in body
                src = head + tail
            code = "\n".join(line.split("#", 1)[0]
                             for line in src.splitlines())
            code = re.sub(r'"""(?s:.*?)"""', "", code)
            hits += [(name, m.group(0)) for m in taken.finditer(code)]
        assert not hits, hits


class TestQuerySpan:
    def test_miss_then_hit_then_mixed(self, stack, ring):
        svc = stack["service"]
        new = _fresh_rows(2601, 2)
        svc.query_ids(new)
        svc.query_ids(new)                          # both cached now
        svc.query_ids(np.concatenate([new[:1], _fresh_rows(2602, 1)]))
        miss, hit, mixed = _named(ring, "query")
        assert [r["rows"] for r in (miss, hit, mixed)] == [2, 2, 2]
        assert [r["cache_hits"] for r in (miss, hit, mixed)] == [0, 2, 1]
        assert miss["embed_wait_ms"] > 0 and mixed["embed_wait_ms"] > 0
        assert hit["embed_wait_ms"] == 0
        for r in (miss, hit, mixed):
            assert 0 < r["topk_ms"] <= r["dur_ms"] and "error" not in r
            assert r["embed_wait_ms"] + r["topk_ms"] <= r["dur_ms"] + 0.01
        # a call of hits only holds the lock once (its scan); a miss
        # twice (flush and scan)
        sites = [r["site"] for r in _named(ring, "dispatch")]
        assert sites.count("index.topk") == 3
        assert sites.count("engine.text") == 2

    def test_a_shed_call_records_its_error(self, stack, ring):
        from milnce_tpu.serving.service import RetrievalService, ShedError

        svc = RetrievalService(stack["engine"], stack["index"],
                               max_inflight=1)
        try:
            with pytest.raises(ShedError):
                svc.query_ids(_fresh_rows(2603, 2))
        finally:
            svc.close()
        (r,) = _named(ring, "query")
        assert r["error"] == "ShedError" and r["rows"] == 2
        assert "topk_ms" not in r and not _named(ring, "dispatch")

    def test_a_failing_scan_records_its_error(self, stack, ring,
                                              monkeypatch):
        svc = stack["service"]

        def broken(queries):
            raise RuntimeError("scan failed")

        monkeypatch.setattr(svc.index, "topk", broken)
        with pytest.raises(RuntimeError):
            svc.query_ids(_fresh_rows(2604, 1))
        (r,) = _named(ring, "query")
        assert r["error"] == "RuntimeError" and r["cache_hits"] == 0


class TestScanCoalescer:
    """The coalescer over the real engine and index (its semantics on
    stubs: tests/test_scan_coalescer.py)."""

    def test_callers_that_wait_share_a_pass_and_keep_their_own_answers(
            self, stack, ring):
        from milnce_tpu.serving.engine import DEVICE_DISPATCH_LOCK

        svc, index = stack["service"], stack["index"]
        n = 12                              # under the top bucket of 16
        rows = _fresh_rows(2701, n)
        emb = svc.embed_text_ids(rows)                  # all cached now
        want = [index.topk(emb[i:i + 1]) for i in range(n)]
        before = svc.health()["scans"]["requests"]
        alone = len(_named(ring, "dispatch", site="index.topk"))
        assert alone == n
        answers = [None] * n

        def call(i):
            answers[i] = svc.query_ids(rows[i:i + 1])

        threads = [threading.Thread(target=call, args=(i,), daemon=True)
                   for i in range(n)]
        DEVICE_DISPATCH_LOCK.acquire()      # the device is busy elsewhere
        try:
            for t in threads:
                t.start()
            deadline = time.monotonic() + 10.0
            while svc.health()["scans"]["requests"] < before + n:
                assert time.monotonic() < deadline
                time.sleep(0.002)
        finally:
            DEVICE_DISPATCH_LOCK.release()
        for t in threads:
            t.join(30)
        scans = _named(ring, "dispatch", site="index.topk")[alone:]
        assert len(scans) <= 2 and sum(r["rows"] for r in scans) == n
        assert all(r["bucket"] == index.bucket_for(r["rows"])
                   for r in scans)
        for i in range(n):
            np.testing.assert_array_equal(answers[i][1], want[i][1])
            np.testing.assert_allclose(answers[i][0], want[i][0],
                                       rtol=1e-5, atol=1e-6)
        flushes = _named(ring, "topk.flush")
        assert [r["rows"] for r in flushes] == [r["rows"] for r in scans]
        assert index.recompiles() == 0

    def test_a_sixteen_row_call_rides_one_scan(self, stack, ring):
        svc = stack["service"]
        svc.query_ids(_fresh_rows(2702, 16))
        (scan,) = _named(ring, "dispatch", site="index.topk")
        assert (scan["rows"], scan["bucket"]) == (16, 16)
        with pytest.raises(ValueError, match="top query bucket"):
            svc.query_ids(_fresh_rows(2703, 17))

    def test_health_counts_the_scans_beside_the_text_flushes(self, stack):
        svc = stack["service"]
        before = svc.health()
        svc.query_ids(_fresh_rows(2704, 3))
        after = svc.health()
        assert after["scans"]["requests"] - before["scans"]["requests"] == 3
        assert after["scans"]["flushes"] - before["scans"]["flushes"] == 1
        assert after["batcher"]["requests"] \
            - before["batcher"]["requests"] == 3
        assert "topk" in svc.metrics_text()


class TestQueueWait:
    @pytest.mark.parametrize("mode", ["sync", "async"])
    def test_flush_record_carries_the_rows_queue_wait(self, mode):
        """Two rows 40 ms apart into one flush, taken when the second has
        arrived: the oldest row's wait is those 40 ms, the mean about 20
        ms less — on the span of a synchronous flush and on the event of
        a pipelined one."""
        import time
        from concurrent.futures import Future

        from milnce_tpu.obs.spans import SpanRecorder
        from milnce_tpu.serving.batcher import DynamicBatcher

        def run_async(rows):
            fut: Future = Future()
            fut.set_result(rows * 2.0)
            return fut

        rec = SpanRecorder()
        b = DynamicBatcher(lambda rows: rows * 2.0, lambda n: 4,
                           max_batch=4, wake=lambda: None, recorder=rec,
                           run_batch_async=(run_async if mode == "async"
                                            else None))
        first = b.submit(np.ones((3,), np.float32))
        time.sleep(0.04)                # the owner holds off its take
        second = b.submit(np.ones((3,), np.float32))
        b.flush(b.take())
        first.result(timeout=0), second.result(timeout=0)
        b.close()
        (flush,) = _named(rec, "batcher.flush")
        assert flush["kind"] == ("event" if mode == "async" else "span")
        assert flush["rows"] == 2
        assert 40.0 <= flush["queue_wait_ms"] < 1000.0
        assert flush["queue_wait_mean_ms"] <= flush["queue_wait_ms"] - 15.0
        assert flush["queue_wait_mean_ms"] >= flush["queue_wait_ms"] / 2


# ---------------------------------------------------------------------------
# build_server / close_server: the boot log, the collector hook, and the
# program's annotations in a profiler session with no flag set
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """``milnce-serve``'s own construction at the tiny preset, over a
    recorder of its own -> what the tests below look at."""
    import gc

    import jax
    import jax.numpy as jnp

    from milnce_tpu.config import parse_cli
    from milnce_tpu.models.build import build_model
    from milnce_tpu.obs import spans
    from milnce_tpu.serving import service as serving
    from milnce_tpu.serving.export import export_inference_checkpoint

    work = tmp_path_factory.mktemp("served")
    cfg = parse_cli([
        "--preset", "tiny", "--model.inception_blocks", "1",
        "--parallel.platform", "cpu", "--serve.max_batch", "8",
        "--serve.topk", "3", "--serve.port", "0",
        "--serve.export_dir", str(work / "export"),
        "--serve.corpus_npz", str(work / "corpus.npz")])
    d = cfg.data
    shape = (d.num_frames, d.video_size, d.video_size, 3)
    model = build_model(cfg.model)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1,) + shape),
                           jnp.zeros((1, d.max_words), jnp.int32))
    export_inference_checkpoint(
        cfg.serve.export_dir, jax.device_get(variables["params"]),
        jax.device_get(variables["batch_stats"]), cfg.model,
        max_words=d.max_words, video_shape=shape)
    corpus = np.random.default_rng(5).standard_normal(
        (40, cfg.model.embedding_dim)).astype(np.float32)
    np.savez(cfg.serve.corpus_npz, emb=corpus)

    rec = spans.SpanRecorder(ring=1 << 14)
    prev = spans.install(rec)
    hooks_before = list(gc.callbacks)
    built = serving.build_server(cfg)
    out = dict(cfg=cfg, rec=rec, built=built, work=work,
               hooks_before=hooks_before, closed=False)
    yield out
    if not out["closed"]:
        serving.close_server(cfg, *built)
    spans.install(prev)


class TestBuiltServer:
    def test_boot_log_splits_the_set_up(self, served):
        rec = served["rec"]
        (load,) = _named(rec, "engine.load")
        (warm,) = _named(rec, "ladder.warmup")
        (corpus,) = _named(rec, "corpus.load")
        (build,) = _named(rec, "index.build")
        assert warm["dur_ms"] <= load["dur_ms"] and corpus["rows"] == 40
        assert build["rows"] == 40
        parts = build["shard_ms"] + build["upload_ms"] + build["warmup_ms"]
        assert 0 < parts <= build["dur_ms"] + 0.01
        # the index's warm-up scans are dispatch records of their own
        assert _named(rec, "dispatch", site="index.topk")

    def test_a_profiler_session_shows_the_programs_annotations(self, served):
        """No flag, no option: start a trace around one served query and
        the program's own names are on /host:CPU."""
        import glob
        import os

        import jax

        _server, service, _index, _engine = served["built"]
        trace_dir = str(served["work"] / "trace")
        jax.profiler.start_trace(trace_dir)
        try:
            rows = np.random.default_rng(77).integers(
                1, 100, (1, served["cfg"].data.max_words)).astype(np.int32)
            service.query_ids(rows)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True)
        data = jax.profiler.ProfileData.from_file(path)
        names = {ev.name for plane in data.planes
                 if plane.name == "/host:CPU"
                 for line in plane.lines for ev in line.events}
        want = {"query", "dispatch", "batcher.flush"}
        for site in ("engine.text", "index.topk"):
            want |= {f"{site}.{leg}"
                     for leg in ("lock_wait", "put", "call", "get")}
        assert want <= names, sorted(want - names)

    def test_a_profiler_session_shows_the_workers_phases_on_its_thread(
            self, served):
        """No flag: the device worker's line on /host:CPU is tiled by
        ``worker.<phase>``, the holds' legs nested inside ``worker.run``;
        a caller's ``query`` lies on another line."""
        import glob
        import os

        import jax

        _server, service, _index, _engine = served["built"]
        trace_dir = str(served["work"] / "trace_worker")
        jax.profiler.start_trace(trace_dir)
        try:
            for seed in (78, 79):       # the first wakes the worker: its
                rows = np.random.default_rng(seed).integers(    # sleep began
                    1, 100, (1, served["cfg"].data.max_words)   # untraced
                ).astype(np.int32)
                service.query_ids(rows)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True)
        data = jax.profiler.ProfileData.from_file(path)
        lines = [[(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                  for ev in line.events]
                 for plane in data.planes if plane.name == "/host:CPU"
                 for line in plane.lines]
        (worker,) = [ln for ln in lines
                     if any(n == "worker.scatter" for n, _, _ in ln)]
        names = {n for n, _, _ in worker}
        assert {"worker.sleep", "worker.take", "worker.prepare",
                "worker.run", "worker.scatter", "worker.account",
                "batcher.flush", "topk.flush",
                "engine.text.get", "index.topk.get"} <= names
        assert "query" not in names         # the callers' line is another
        runs = [(s, e) for n, s, e in worker if n == "worker.run"]
        for n, s, e in worker:
            if n in ("engine.text.get", "index.topk.get"):
                assert any(rs <= s and e <= re_ for rs, re_ in runs), n
        # the phases tile the line: no two of them overlap
        phases = sorted((s, e) for n, s, e in worker
                        if n.startswith("worker."))
        for (_, e0), (s1, _) in zip(phases, phases[1:]):
            assert e0 <= s1
        # and nobody else's line carries them
        assert sum(any(n.startswith("worker.") for n, _, _ in ln)
                   for ln in lines) == 1

    def test_the_idle_split_puts_every_idle_second_under_one_name(
            self, served):
        """``scripts/idle_by_worker_phase.py`` on a CPU trace of a few
        served queries: the gaps, cut at the worker's phases' edges, add
        up to the idle time, and the holds' legs are told from the rest
        of ``worker.run``."""
        import glob
        import importlib.util
        import os

        import jax

        from benchmarks import trace_reduce

        spec = importlib.util.spec_from_file_location(
            "idle_by_worker_phase", os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "scripts", "idle_by_worker_phase.py"))
        tool = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(tool)
        _server, service, _index, _engine = served["built"]
        trace_dir = str(served["work"] / "trace_split")
        trace_reduce.start_trace(trace_dir)     # no Python frames: they
        #                           would count as operations on the CPU
        try:
            service.query_ids(np.ones((1, served["cfg"].data.max_words),
                                      np.int32))
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
                for seed in range(80, 86):
                    service.query_ids(np.random.default_rng(seed).integers(
                        1, 100, (1, served["cfg"].data.max_words)
                    ).astype(np.int32))
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True)
        split = tool.split_idle(path, trace_reduce.CPU_LAYOUT, trace_reduce)
        assert 0 < split["idle_s"] < split["window_s"]
        assert sum(split["by_phase"].values()) == pytest.approx(
            split["idle_s"], rel=1e-6)
        assert all(sec >= -1e-9 for sec in split["by_phase"].values())
        names = set(split["by_phase"])
        assert {"text.scatter", "topk.scatter", "sleep"} <= names
        assert names & {"engine.text.put", "engine.text.get",
                        "index.topk.put", "index.topk.get"}
        assert not any(n.startswith("worker.") for n in names)

    def test_one_watcher_thread_while_the_server_is_up(self, served):
        import threading

        _server, service, _index, _engine = served["built"]
        assert not served["closed"]
        watchers = [t for t in threading.enumerate()
                    if t.name == "obs-runtime-watch"]
        assert len(watchers) == 1 and watchers[0].daemon
        assert service.runtime_watch._thread is watchers[0]
        assert sum(t.name == "device-worker"
                   for t in threading.enumerate()) >= 1

    def test_collector_pause_recorded_and_hook_gone_after_close(self,
                                                                 served):
        import gc
        import time

        from milnce_tpu.serving import service as serving

        _server, service, _index, _engine = served["built"]
        assert service.runtime_watch is not None
        assert len(gc.callbacks) == len(served["hooks_before"]) + 1
        junk = []
        for _ in range(400_000):
            a = []
            a.append(a)
            junk.append(a)
        del junk, a
        gc.collect()

        def mine():     # building the junk sets off passes of its own
            return [e for e in _named(served["rec"], "runtime.gc")
                    if e["collected"] >= 400_000]

        deadline = time.monotonic() + 5.0
        while not mine() and time.monotonic() < deadline:
            time.sleep(0.01)            # the writer thread's 50 ms
        (ev,) = mine()
        assert ev["generation"] == 2 and ev["dur_ms"] >= 5.0
        assert ev["end_mono"] <= ev["mono"]
        serving.close_server(served["cfg"], *served["built"])
        served["closed"] = True
        assert gc.callbacks == served["hooks_before"]
        assert service.runtime_watch is None

    def test_no_watcher_thread_after_close(self, served):
        import threading

        from milnce_tpu.serving import service as serving

        if not served["closed"]:
            serving.close_server(served["cfg"], *served["built"])
            served["closed"] = True
        assert not [t for t in threading.enumerate()
                    if t.name == "obs-runtime-watch"]
