"""Child process of the live-index ingest-while-query hammer
(tests/test_live_index.py).

16 threads — 12 issuing retrieval queries, 4 ingesting embedding rows —
against one :class:`~milnce_tpu.serving.live_index.LiveRetrievalIndex`
under ``MILNCE_LOCK_SANITIZE=1`` (exported by the parent BEFORE import,
so the state lock, dispatch lock, and every obs lock is an
order-checking SanitizedLock).  The pins (ISSUE 14 satellite):

- **exact-count accounting**: the final corpus size equals boot +
  every row every ingest thread added — no lost or double-counted rows
  under contention;
- **no torn generations**: every query result must equal the exact
  ``np.argsort`` ranking over SOME published corpus prefix (the ingest
  threads serialize their ``add`` calls through one lock while
  recording order, so the corpus at any generation is a known prefix);
  a result mixing two generations matches NO prefix and fails loudly.
  The generation→prefix association must also be consistent: one
  generation never answers with two different corpus sizes;
- **recompiles=0 across >= 3 swaps** on the query path;
- the sanitizer actually engaged (observed lock edges), and the builder
  thread survived the whole run.
"""

import os
import sys
import threading

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
from jax.sharding import Mesh  # noqa: E402

from milnce_tpu.serving.live_index import LiveRetrievalIndex  # noqa: E402

DIM, BOOT, K = 16, 12, 5
N_QUERY_THREADS, N_INGEST_THREADS = 12, 4
QUERIES_PER_THREAD, ADDS_PER_THREAD, ROWS_PER_ADD = 8, 3, 4
MIN_SWAPS = 3


def main() -> int:
    mesh = Mesh(np.array(jax.devices()), ("data",))
    rng = np.random.default_rng(0)
    boot = rng.standard_normal((BOOT, DIM)).astype(np.float32)
    index = LiveRetrievalIndex(mesh, boot, k=K, query_buckets=(8,))
    assert isinstance(index._state_lock, lockrt.SanitizedLock), \
        "live-index state lock must be sanitized"

    # ingest rows pre-generated; the add lock serializes the calls AND
    # records acceptance order, so the corpus at any instant is a known
    # prefix of `appended` — the torn-generation check's ground truth
    total_adds = N_INGEST_THREADS * ADDS_PER_THREAD
    pool = rng.standard_normal(
        (total_adds * ROWS_PER_ADD, DIM)).astype(np.float32)
    add_lock = threading.Lock()
    appended: list[np.ndarray] = []
    errors: list[str] = []
    observed: list[tuple] = []          # (gen, q_seed, idx_rows)
    obs_lock = threading.Lock()

    def ingester(tid: int) -> None:
        try:
            for j in range(ADDS_PER_THREAD):
                base = (tid * ADDS_PER_THREAD + j) * ROWS_PER_ADD
                rows = pool[base:base + ROWS_PER_ADD]
                with add_lock:          # serialize add + order record
                    index.add(rows)
                    appended.append(rows)
                # wait for THIS add to publish before the next one: a
                # thread's sequential adds then land in distinct swaps,
                # guaranteeing >= ADDS_PER_THREAD swaps however hard
                # the builder coalesces concurrent ingests
                assert index.flush(60.0), "mid-hammer flush timed out"
        except Exception as exc:  # noqa: BLE001 - child reports
            errors.append(f"ingest {tid}: {type(exc).__name__}: {exc}")

    def querier(tid: int) -> None:
        try:
            qrng = np.random.default_rng(1000 + tid)
            for _ in range(QUERIES_PER_THREAD):
                q = qrng.standard_normal((2, DIM)).astype(np.float32)
                scores, idx, gen = index.topk_with_gen(q)
                assert scores.shape == (2, K) and idx.shape == (2, K)
                with obs_lock:
                    observed.append((gen, q, idx.copy()))
        except Exception as exc:  # noqa: BLE001 - child reports
            errors.append(f"query {tid}: {type(exc).__name__}: {exc}")

    threads = [threading.Thread(target=ingester, args=(t,))
               for t in range(N_INGEST_THREADS)]
    threads += [threading.Thread(target=querier, args=(t,))
                for t in range(N_QUERY_THREADS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    if errors:
        print("\n".join(errors), file=sys.stderr)
        return 1
    if not index.flush(30.0):
        print("final flush timed out — pending rows never landed",
              file=sys.stderr)
        return 1

    st = index.stats()
    expect = BOOT + total_adds * ROWS_PER_ADD
    if st["size"] != expect or st["ingested_rows"] != expect - BOOT:
        print(f"count accounting broken: {st} != size {expect}",
              file=sys.stderr)
        return 1
    if st["swaps"] < MIN_SWAPS:
        print(f"only {st['swaps']} swaps < {MIN_SWAPS} — the hammer "
              "never exercised concurrent swapping", file=sys.stderr)
        return 1
    if index.recompiles() != 0:
        print(f"query-path recompiles={index.recompiles()} != 0 across "
              f"{st['swaps']} swaps", file=sys.stderr)
        return 1
    if not st["builder_alive"]:
        print("builder thread died during the hammer", file=sys.stderr)
        return 1

    # torn-generation audit: every observed ranking must equal the
    # argsort over a corpus PREFIX (the only corpora ever published —
    # a result mixing two generations matches none), and per generation
    # there must exist ONE corpus size consistent with every result it
    # answered (a ranking can legitimately match several prefixes when
    # the newer rows don't crack its top-k, so the pin is set
    # intersection, not first-match equality)
    full = np.concatenate([boot] + appended)
    sizes = [BOOT + sum(a.shape[0] for a in appended[:m])
             for m in range(len(appended) + 1)]
    gen_sets: dict[int, set] = {}
    for gen, q, idx in observed:
        matches = set()
        for size in sizes:
            if size < K:
                continue
            ref = np.argsort(-(q @ full[:size].T), axis=1)[:, :K]
            if np.array_equal(idx, ref):
                matches.add(size)
        if not matches:
            print(f"TORN GENERATION: a gen-{gen} result matches no "
                  "published corpus prefix", file=sys.stderr)
            return 1
        gen_sets[gen] = (gen_sets[gen] & matches
                         if gen in gen_sets else matches)
        if not gen_sets[gen]:
            print(f"generation {gen}: no single corpus size is "
                  "consistent with every result it answered",
                  file=sys.stderr)
            return 1
    edges = lockrt.GLOBAL_GRAPH.snapshot()["edges"]
    if not edges:
        print("sanitizer saw no lock edges — not actually engaged?",
              file=sys.stderr)
        return 1
    print(f"HAMMER_OK threads={len(threads)} queries={len(observed)} "
          f"swaps={st['swaps']} size={st['size']} edges={len(edges)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
