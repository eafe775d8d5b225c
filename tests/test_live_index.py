"""Live retrieval index gates (ISSUE 14): generation-swapped corpus
shards, online ingest through the serve path, swap chaos, snapshot
round trip, and the ingest-while-query hammer.

The freshness parity pin is the tentpole acceptance: after
``POST /v1/index/add`` + swap, a served query ranks the GROWN corpus
exactly like the offline ``eval/retrieval.py`` argsort, queries answer
from exactly one generation, and the query path never recompiles across
swaps.  Model/engine dimensions match tests/test_serving.py's stack so
the persistent compile cache keeps this module seconds-scale.
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from milnce_tpu.resilience import faults

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_FRAMES, _SIZE, _WORDS = 4, 32, 6
_BOOT, _GROW = 12, 9            # corpus: 12 at boot, 9 ingested -> 21


@pytest.fixture(scope="module")
def stack():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from milnce_tpu.models import S3D
    from milnce_tpu.serving.cache import EmbeddingLRUCache
    from milnce_tpu.serving.engine import InferenceEngine
    from milnce_tpu.serving.live_index import LiveRetrievalIndex
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
    clips = rng.integers(0, 255, (_BOOT + _GROW, _FRAMES, _SIZE, _SIZE, 3),
                         dtype=np.uint8)
    boot_emb = engine.embed_video(clips[:_BOOT])
    index = LiveRetrievalIndex(mesh, boot_emb, k=5,
                               query_buckets=engine.buckets)
    # cache off: ingest changes the right answer, a stale hit would
    # hide exactly the freshness this module pins
    service = RetrievalService(engine, index,
                               cache=EmbeddingLRUCache(0))
    yield dict(model=model, variables=variables, mesh=mesh, engine=engine,
               clips=clips, index=index, service=service)
    service.close()
    index.close()


def _mini_index(mesh, corpus, **kw):
    from milnce_tpu.serving.live_index import LiveRetrievalIndex

    kw.setdefault("k", 5)
    kw.setdefault("query_buckets", (8,))
    return LiveRetrievalIndex(mesh, corpus, **kw)


class TestFreshnessParity:
    def test_ingested_clips_rank_exactly_like_offline_eval(self, stack):
        """THE acceptance pin: raw clips through /v1/index/add's embed
        path + one generation swap, then every served query ranks the
        GROWN corpus exactly like the offline eval/retrieval.py
        extraction + argsort — freshly ingested rows are first-class
        corpus citizens, and the swap cost zero query-path recompiles."""
        from milnce_tpu.eval.retrieval import extract_retrieval_embeddings

        service, index, clips = stack["service"], stack["index"], \
            stack["clips"]
        rng = np.random.default_rng(5)
        texts = rng.integers(1, 64, (_BOOT + _GROW, _WORDS)).astype(np.int32)

        out = service.index_add(clips=clips[_BOOT:], wait=True)
        assert out["live"] and out["rows"] == _GROW
        assert out["generation"] >= 1
        assert index.size == _BOOT + _GROW

        class _Source:
            def __len__(self):
                return _BOOT + _GROW

            def sample(self, i, rng=None):
                return {"video": clips[i:i + 1], "text": texts[i:i + 1]}

        t_emb, v_emb = extract_retrieval_embeddings(
            stack["model"], dict(stack["variables"]), _Source(),
            stack["mesh"], batch_size=8)
        offline = np.argsort(-(t_emb @ v_emb.T), axis=1)[:, :5]

        gens = set()
        served = []
        for i in range(_BOOT + _GROW):
            scores, idx, gen = service.query_ids_with_gen(texts[i:i + 1])
            served.append(idx[0])
            gens.add(gen)
        assert np.array_equal(np.stack(served), offline), (
            "served top-k over the grown corpus diverged from the "
            "offline eval ranking")
        # every answer came from ONE generation (nothing ingested
        # mid-loop), and the swap never recompiled the query path
        assert len(gens) == 1 and gens.pop() == out["generation"]
        assert index.recompiles() == 0
        assert stack["engine"].recompiles() == 0

    def test_healthz_index_section_and_generation_stamp_over_http(
            self, stack):
        """Satellite: /healthz gains the additive index keys and
        /v1/query stamps index_generation so clients detect freshness."""
        from milnce_tpu.serving.service import serve_http

        service = stack["service"]
        server = serve_http(service, port=0)
        threading.Thread(target=server.serve_forever, daemon=True).start()
        base = f"http://127.0.0.1:{server.server_address[1]}"
        try:
            with urllib.request.urlopen(base + "/healthz", timeout=30) as r:
                h = json.loads(r.read())
            idx = h["index"]
            # byte-compatible frozen keys...
            for key in ("size", "dim", "k", "query_buckets", "calls",
                        "recompiles"):
                assert key in idx, f"frozen index key {key} missing"
            # ...plus the additive live keys
            for key in ("generation", "pending_rows", "last_swap_age_s",
                        "swaps", "swap_failures", "ingested_rows",
                        "builder_alive"):
                assert key in idx, f"live index key {key} missing"
            assert idx["builder_alive"] and idx["pending_rows"] == 0

            req = urllib.request.Request(
                base + "/v1/query",
                data=json.dumps({"token_ids": [[1, 2, 3, 0, 0, 0]],
                                 "k": 3}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as r:
                body = json.loads(r.read())
            assert body["index_generation"] == idx["generation"]

            # the HTTP write path: precomputed embeddings, wait for swap
            rows = np.random.default_rng(8).standard_normal(
                (2, service.engine.embed_dim)).astype(np.float32)
            req = urllib.request.Request(
                base + "/v1/index/add",
                data=json.dumps({"embeddings": rows.tolist(),
                                 "wait": True}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as r:
                out = json.loads(r.read())
            assert out["live"] and out["rows"] == 2
            assert out["generation"] > idx["generation"]
        finally:
            server.shutdown()
            server.server_close()

    def test_index_add_on_frozen_index_is_a_400_class_error(self, stack):
        from milnce_tpu.serving.index import DeviceRetrievalIndex
        from milnce_tpu.serving.service import RetrievalService

        frozen = DeviceRetrievalIndex(
            stack["mesh"],
            np.ones((8, stack["engine"].embed_dim), np.float32),
            k=3, query_buckets=stack["engine"].buckets, precompile=False)
        service = RetrievalService(stack["engine"], frozen)
        try:
            with pytest.raises(ValueError, match="not a live index"):
                service.index_add(embeddings=np.zeros(
                    (1, stack["engine"].embed_dim), np.float32))
        finally:
            service.close()


class TestSwapChaos:
    def test_failed_swap_keeps_old_generation_and_builder_retries(
            self, stack):
        """Satellite: under ``index.swap_raise@*`` every build fails —
        the old generation keeps serving bit-identically, rows are
        never lost, the builder thread never wedges; disarmed, the
        retry lands the rows."""
        mesh = stack["mesh"]
        rng = np.random.default_rng(11)
        corpus = rng.standard_normal((12, 16)).astype(np.float32)
        li = _mini_index(mesh, corpus)
        try:
            q = rng.standard_normal((3, 16)).astype(np.float32)
            s0, i0, g0 = li.topk_with_gen(q)
            with faults.armed("index.swap_raise@*"):
                li.add(rng.standard_normal((3, 16)).astype(np.float32))
                assert not li.flush(0.8), "swap 'succeeded' under @*"
                st = li.stats()
                assert st["swap_failures"] >= 1
                assert st["pending_rows"] == 3, "failed swap lost rows"
                assert st["builder_alive"], "builder thread wedged"
                s1, i1, g1 = li.topk_with_gen(q)
                assert g1 == g0 and np.array_equal(i1, i0) \
                    and np.array_equal(s1, s0), "old generation torn"
            # disarmed: the builder's retry publishes the held rows
            assert li.flush(10.0), li.stats()
            st = li.stats()
            assert st["generation"] == g0 + 1 and st["size"] == 15
            assert st["pending_rows"] == 0 and st["builder_alive"]
            assert li.recompiles() == 0
        finally:
            li.close()

    def test_transient_swap_failure_self_heals_without_flush(self, stack):
        """One scheduled failure (@1): the builder's own idle-backoff
        retry publishes the rows with no explicit flush() nudge."""
        rng = np.random.default_rng(12)
        li = _mini_index(stack["mesh"],
                         rng.standard_normal((12, 16)).astype(np.float32))
        try:
            with faults.armed("index.swap_raise@1"):
                li.add(rng.standard_normal((2, 16)).astype(np.float32))
                deadline = time.monotonic() + 10.0
                while time.monotonic() < deadline:
                    if li.stats()["generation"] == 1:
                        break
                    time.sleep(0.02)
            st = li.stats()
            assert st["generation"] == 1 and st["size"] == 14, st
            assert st["swap_failures"] == 1
        finally:
            li.close()

    def test_ingest_hang_does_not_block_queries(self, stack):
        rng = np.random.default_rng(13)
        li = _mini_index(stack["mesh"],
                         rng.standard_normal((12, 16)).astype(np.float32))
        try:
            q = rng.standard_normal((2, 16)).astype(np.float32)
            li.topk_with_gen(q)                      # warm the path
            done = threading.Event()

            def slow_add():
                li.add(rng.standard_normal((2, 16)).astype(np.float32))
                done.set()

            faults.arm("index.ingest_hang@1:x=0.8")
            try:
                t = threading.Thread(target=slow_add, daemon=True)
                t.start()
                time.sleep(0.05)                     # add is hanging now
                t0 = time.monotonic()
                li.topk_with_gen(q)
                dt = time.monotonic() - t0
                t.join(timeout=10)
            finally:
                faults.disarm()
            assert done.is_set()
            assert dt < 0.5, (f"query took {dt:.3f}s while an ingest "
                              "hung — the hang leaked into the query path")
        finally:
            li.close()

    def test_new_fault_sites_parse_and_unknown_rejected(self):
        spec = faults.parse_spec(
            "index.swap_raise@%3;index.ingest_hang@1:x=0.5")
        assert set(spec) == {"index.swap_raise", "index.ingest_hang"}
        assert spec["index.ingest_hang"].x == 0.5
        with pytest.raises(ValueError, match="unknown fault site"):
            faults.parse_spec("index.typo@*")


class TestCoalescedGeneration:
    def test_every_call_is_stamped_with_the_generation_that_ranked_it(
            self, stack):
        """Callers hammer the service through the scan coalescer while a
        swap publishes one new row per generation, planted to win one
        probe query: an answer that holds a row carries a generation in
        which that row was live, and no caller sees a generation go
        backwards."""
        import threading

        service, index, engine = (stack["service"], stack["index"],
                                  stack["engine"])
        probe = np.random.default_rng(77).integers(
            1, 64, (1, _WORDS)).astype(np.int32)
        q = engine.embed_text(probe)[0]
        base = index.size
        born = {}                           # row -> generation it came in
        stop = threading.Event()
        seen = [[] for _ in range(6)]
        errors = []

        def caller(c):
            try:
                while not stop.is_set():
                    _, idx, gen = service.query_ids_with_gen(probe)
                    seen[c].append((gen, idx[0].tolist()))
            except Exception as exc:                 # noqa: BLE001
                errors.append(exc)

        threads = [threading.Thread(target=caller, args=(c,), daemon=True)
                   for c in range(6)]
        for t in threads:
            t.start()
        try:
            for step in range(3):
                # a row far along the probe's direction ranks first
                service.index_add(embeddings=(q * (50.0 + step))[None],
                                  wait=True)
                born[base + step] = index.generation
        finally:
            stop.set()
            for t in threads:
                t.join(30)
        assert not errors, errors
        assert all(s for s in seen)
        for answers in seen:
            gens = [g for g, _ in answers]
            assert gens == sorted(gens)
            for gen, idx in answers:
                for row, gen_born in born.items():
                    if row in idx:
                        assert gen >= gen_born, (row, gen, gen_born)
                    elif gen >= gen_born:
                        # live in the generation that ranked this call:
                        # it wins, so it must be in the answer
                        raise AssertionError((row, gen, idx))
        _, idx, gen = service.query_ids_with_gen(probe)
        assert gen == index.generation and idx[0, 0] == base + 2


class TestSnapshotRestore:
    def test_snapshot_restore_query_bit_exact_round_trip(self, stack,
                                                         tmp_path):
        rng = np.random.default_rng(21)
        li = _mini_index(stack["mesh"],
                         rng.standard_normal((12, 16)).astype(np.float32))
        try:
            li.add(rng.standard_normal((5, 16)).astype(np.float32))
            assert li.flush(10.0)
            q = rng.standard_normal((4, 16)).astype(np.float32)
            s0, i0, g0 = li.topk_with_gen(q)
            li.snapshot(str(tmp_path / "snap"))
            from milnce_tpu.serving.live_index import LiveRetrievalIndex

            li2 = LiveRetrievalIndex.restore(str(tmp_path / "snap"),
                                             stack["mesh"],
                                             query_buckets=(8,))
            try:
                s1, i1, g1 = li2.topk_with_gen(q)
                assert np.array_equal(s0, s1), "scores not bit-exact"
                assert np.array_equal(i0, i1), "indices not bit-exact"
                assert g1 == g0, "generation counter lost in the round trip"
                assert li2.size == 17 and li2.k == 5
            finally:
                li2.close()
        finally:
            li.close()

    def test_snapshot_format_is_corpus_npz_compatible(self, stack,
                                                      tmp_path):
        """The snapshot's corpus.npz is the exact --serve.corpus_npz
        contract ('emb' key) — a cold DeviceRetrievalIndex boot off it
        serves the same corpus."""
        from milnce_tpu.serving.export import (INDEX_ARRAYS_FILE,
                                               INDEX_METADATA_FILE)
        from milnce_tpu.serving.index import DeviceRetrievalIndex

        rng = np.random.default_rng(22)
        corpus = rng.standard_normal((10, 16)).astype(np.float32)
        li = _mini_index(stack["mesh"], corpus)
        try:
            li.snapshot(str(tmp_path / "snap2"))
        finally:
            li.close()
        with np.load(str(tmp_path / "snap2" / INDEX_ARRAYS_FILE)) as z:
            np.testing.assert_array_equal(z["emb"], corpus)
        meta = json.loads(
            (tmp_path / "snap2" / INDEX_METADATA_FILE).read_text())
        assert meta["format_version"] == 1 and meta["size"] == 10
        frozen = DeviceRetrievalIndex(stack["mesh"], corpus, k=5,
                                      query_buckets=(8,))
        q = rng.standard_normal((2, 16)).astype(np.float32)
        _, idx = frozen.topk(q)
        ref = np.argsort(-(q @ corpus.T), axis=1)[:, :5]
        assert np.array_equal(idx, ref)


class TestRungRule:
    def test_growth_within_a_rung_reuses_shapes_across_rungs_rebaselines(
            self, stack):
        """The zero-recompile story end to end: swaps inside a rung are
        shape-identical (no compile at all); crossing a rung compiles
        ON THE BUILDER (counted as builder work) and the query path
        still reports 0."""
        rng = np.random.default_rng(31)
        li = _mini_index(stack["mesh"],
                         rng.standard_normal((12, 16)).astype(np.float32))
        try:
            q = rng.standard_normal((2, 16)).astype(np.float32)
            assert li.stats()["shard_rows"] == 8      # capacity 64
            full = li.stats()["size"]
            for n in (9, 10, 20):                     # stays under 64
                li.add(rng.standard_normal((n, 16)).astype(np.float32))
                assert li.flush(10.0)
                full += n
                li.topk_with_gen(q)
            st = li.stats()
            assert st["swaps"] == 3 and st["shard_rows"] == 8
            assert li.recompiles() == 0
            # cross the rung: capacity doubles, query path stays clean
            li.add(rng.standard_normal((40, 16)).astype(np.float32))
            assert li.flush(30.0)
            li.topk_with_gen(q)
            st = li.stats()
            assert st["shard_rows"] == 16 and st["size"] == full + 40
            assert li.recompiles() == 0, (
                "rung crossing leaked a compile into the query path")
        finally:
            li.close()

    def test_empty_boot_ingest_then_query(self, stack):
        rng = np.random.default_rng(32)
        li = _mini_index(stack["mesh"], None, dim=16)
        try:
            with pytest.raises(ValueError, match="ingest more"):
                li.topk(np.zeros((1, 16), np.float32))
            li.add(rng.standard_normal((8, 16)).astype(np.float32))
            assert li.flush(10.0)
            q = rng.standard_normal((2, 16)).astype(np.float32)
            _, idx, gen = li.topk_with_gen(q)
            assert gen == 1 and idx.max() < 8
        finally:
            li.close()

    def test_shard_rung_rule(self):
        from milnce_tpu.serving.live_index import shard_rung

        assert shard_rung(0, 8, 5) == 8        # k floor, then pow2
        assert shard_rung(12, 8, 5) == 8       # ceil(12/8)=2 < k=5 -> 8
        assert shard_rung(65, 8, 5) == 16      # 9 rows/shard -> rung 16
        assert shard_rung(12, 8, 5, floor=32) == 32

    def test_recommended_min_shard_rows_sizing_rule(self):
        """The ``--serve.index_min_shard_rows`` sizing helper (ISSUE 19
        small fix): plan the rung for the corpus's end-of-life size so
        growth to ``headroom`` x never re-traces the query program."""
        from milnce_tpu.serving.live_index import (
            recommended_min_shard_rows, shard_rung)

        # HowTo100M scale: ~1.2M videos, 8-way data axis, 2x headroom
        # -> 2**19 rows/shard (the documented config.py default)
        assert recommended_min_shard_rows(1_200_000, 8) == 524_288
        # and the rung is exactly what the ladder would pick at the
        # doubled corpus size, so the first swap lands in-rung
        assert shard_rung(2_400_000, 8, 1,
                          floor=recommended_min_shard_rows(
                              1_200_000, 8)) == 524_288
        assert recommended_min_shard_rows(100, 8, headroom=1) == 16
        for bad in ((0, 8, 2), (100, 0, 2), (100, 8, 0)):
            with pytest.raises(ValueError):
                recommended_min_shard_rows(*bad)


# ---------------------------------------------------------------------------
# ISSUE 14 satellite: the 16-thread ingest-while-query hammer under the
# runtime lock sanitizer (subprocess: MILNCE_LOCK_SANITIZE must be armed
# before the serving modules import — fast-child exemption in
# test_suite_hygiene.py; tiny dims + shared compile cache keep it
# seconds-scale)
# ---------------------------------------------------------------------------

def test_live_index_hammer_subprocess_under_sanitizer():
    env = dict(os.environ, MILNCE_LOCK_SANITIZE="1")
    env.pop("MILNCE_FAULTS", None)
    env.setdefault("JAX_COMPILATION_CACHE_DIR", "/tmp/jax_test_cache")
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.0")
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(_REPO, "tests", "live_index_hammer_child.py")],
        capture_output=True, text=True, timeout=420, env=env)
    assert proc.returncode == 0, (
        f"live-index hammer failed\nstdout:\n{proc.stdout}\n"
        f"stderr:\n{proc.stderr}")
    assert "HAMMER_OK" in proc.stdout
