"""Child process of the lockrt serving hammer (tests/test_lockrt.py).

Runs the FULL serving stack — a 2-replica engine POOL (per-replica
dispatch locks, pool state lock, probe thread — ISSUE 10), dynamic
batcher in pipelined mode, embedding cache, device-resident index
(still behind the module-level DEVICE_DISPATCH_LOCK), HTTP front,
Prometheus scrape — with ``MILNCE_LOCK_SANITIZE=1`` exported by the
parent BEFORE import, so every lock in the mesh is an order-checking
SanitizedLock.  16 threads mix query / embed / healthz / metrics /
events traffic; any lock-order cycle, self-deadlock or sanitizer
failure raises and fails the child.

Model/engine dimensions deliberately match tests/test_serving.py's
module stack so the persistent jax compilation cache (conftest wiring,
replicated below) turns the warmup sweep into disk hits.
"""

import json
import os
import sys
import threading
import urllib.request

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# Same hermetic platform the test suite uses; must precede jax import.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from milnce_tpu.analysis import lockrt  # noqa: E402

assert lockrt.sanitizing_enabled(), \
    "parent must export MILNCE_LOCK_SANITIZE=1"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
from milnce_tpu.utils.compile_cache import configure_compile_cache  # noqa: E402

configure_compile_cache("/tmp/jax_test_cache")

import numpy as np  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from milnce_tpu.models import S3D  # noqa: E402
from milnce_tpu.obs import metrics as obs_metrics  # noqa: E402
from milnce_tpu.serving import engine as engine_mod  # noqa: E402
from milnce_tpu.serving.cache import EmbeddingLRUCache  # noqa: E402
from milnce_tpu.serving.index import DeviceRetrievalIndex  # noqa: E402
from milnce_tpu.serving.pool import ReplicaPool  # noqa: E402
from milnce_tpu.serving.service import (RetrievalService,  # noqa: E402
                                        serve_http)

_FRAMES, _SIZE, _WORDS, _CORPUS = 4, 32, 6, 21
N_THREADS, OPS_PER_THREAD = 16, 6


def main() -> int:
    assert isinstance(engine_mod.DEVICE_DISPATCH_LOCK,
                      lockrt.SanitizedLock), (
        "DEVICE_DISPATCH_LOCK must be sanitized — env not seen at import?")

    model = S3D(num_classes=16, vocab_size=64, word_embedding_dim=8,
                text_hidden_dim=16, inception_blocks=1)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, _FRAMES, _SIZE, _SIZE, 3)),
                           jnp.zeros((1, _WORDS), jnp.int32))
    mesh = Mesh(np.array(jax.devices()), ("data",))
    # ISSUE 10: the hammer drives the POOL — 16 request threads against
    # 2 single-device replicas (own dispatch locks + workers + probe
    # thread) while the index still serializes on the process-wide
    # DEVICE_DISPATCH_LOCK; the whole lock mesh is sanitized
    pool = ReplicaPool.build(model, dict(variables), 2,
                             text_words=_WORDS,
                             video_shape=(_FRAMES, _SIZE, _SIZE, 3),
                             max_batch=16, min_bucket=8,
                             probe_interval_s=0.5,
                             registry=obs_metrics.registry())
    assert isinstance(pool._state_lock, lockrt.SanitizedLock)
    for r in pool.replicas:
        assert isinstance(r.engine._dispatch_lock, lockrt.SanitizedLock)
        assert isinstance(r.engine._stats_lock, lockrt.SanitizedLock)
    rng = np.random.default_rng(0)
    clips = rng.integers(0, 255, (_CORPUS, _FRAMES, _SIZE, _SIZE, 3),
                         dtype=np.uint8)
    corpus = np.concatenate(
        [pool.embed_video(clips[:16]), pool.embed_video(clips[16:])])
    index = DeviceRetrievalIndex(mesh, corpus, k=5,
                                 query_buckets=pool.buckets)
    service = RetrievalService(pool, index,
                               cache=EmbeddingLRUCache(128),
                               registry=obs_metrics.registry())
    server = serve_http(service, port=0)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{server.server_address[1]}"

    errors: list = []

    def post(route, payload):
        req = urllib.request.Request(
            base + route, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            assert r.status == 200, (route, r.status)
            return json.loads(r.read())

    def get(route):
        with urllib.request.urlopen(base + route, timeout=60) as r:
            assert r.status == 200, (route, r.status)
            return r.read()

    def hammer(tid):
        try:
            for i in range(OPS_PER_THREAD):
                ids = [[1 + (tid + i + j) % 60 for j in range(_WORDS)]]
                body = post("/v1/query", {"token_ids": ids, "k": 3})
                assert len(body["results"][0]["indices"]) == 3
                post("/v1/embed_text", {"token_ids": ids})
                health = json.loads(get("/healthz"))
                assert health["status"] == "ok"
                assert health["engine"]["recompiles"] == 0
                get("/metrics")
                get("/obs/events?n=20")
        except Exception as exc:  # noqa: BLE001 - child reports, parent asserts
            errors.append(f"thread {tid}: {type(exc).__name__}: {exc}")

    threads = [threading.Thread(target=hammer, args=(t,))
               for t in range(N_THREADS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    server.shutdown()
    server.server_close()
    service.close()
    pool.close()

    if errors:
        print("\n".join(errors), file=sys.stderr)
        return 1
    if pool.recompiles() != 0:
        print(f"pool recompiles={pool.recompiles()} != 0", file=sys.stderr)
        return 1
    edges = lockrt.GLOBAL_GRAPH.snapshot()["edges"]
    if not edges:
        print("sanitizer saw no lock edges — not actually engaged?",
              file=sys.stderr)
        return 1
    print(f"HAMMER_OK threads={N_THREADS} ops={OPS_PER_THREAD} "
          f"edges={len(edges)} replicas={len(pool.replicas)}")
    print(json.dumps({"edges": edges}, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
