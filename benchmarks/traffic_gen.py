"""The one general generator: every traffic mix is a file of parameters
under ``benchmarks/traffic/`` that this module reads.  Everything is
drawn from the seed; the same seed gives the same inputs, and every seed
gives the same set of sizes and arrivals in another order.

Serving: a pool of distinct text queries (lengths heavy-tailed around a
median, tokens uniform, zero-padded), popularity 1 / rank ** exponent,
and for each caller its own pre-drawn sequence of pool entries.  The
corpus of the index is data too: unit-normal rows, made block by block
on the device so that the reference can make the same block again.
"""

from __future__ import annotations

import numpy as np

CORPUS_BLOCK_ROWS = 500_000


def _rng(seed: int, tag: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(tag)])


def query_pool(seed: int, traffic: dict, vocab_size: int,
               max_words: int) -> np.ndarray:
    """-> (pool, max_words) int32 token rows, zero-padded; row 0 is the
    most popular query."""
    rng = _rng(seed, 1)
    words = traffic["words"]
    n = int(traffic["pool"])
    hi = min(int(words["max"]), max_words)
    lengths = np.rint(rng.lognormal(np.log(words["median"]), words["sigma"],
                                    size=n))
    lengths = np.clip(lengths, words["min"], hi).astype(np.int64)
    lengths[0] = hi                 # the longest query is always there
    rows = rng.integers(1, vocab_size, size=(n, max_words), dtype=np.int64)
    rows[np.arange(max_words)[None, :] >= lengths[:, None]] = 0
    return rows.astype(np.int32)


def popularity(traffic: dict) -> np.ndarray:
    ranks = np.arange(1, int(traffic["pool"]) + 1, dtype=np.float64)
    p = ranks ** -float(traffic["rank_exponent"])
    return p / p.sum()


def caller_draws(seed: int, traffic: dict, per_caller: int) -> np.ndarray:
    """-> (callers, per_caller) pool indices: what each caller sends, in
    order.  A caller that runs out starts over (it does not in a run: the
    harness draws more than a window can send)."""
    rng = _rng(seed, 2)
    return rng.choice(int(traffic["pool"]),
                      size=(int(traffic["callers"]), per_caller),
                      p=popularity(traffic)).astype(np.int64)


def compare_sample(seed: int, n_answers: int, size: int,
                   always=()) -> np.ndarray:
    """Which answers of a window are compared: ``size`` of them drawn
    from the seed, with those of ``always`` among them."""
    size = min(size, n_answers)
    picks = _rng(seed, 3).choice(n_answers, size=size, replace=False)
    out = list(dict.fromkeys([int(i) for i in always]
                             + [int(i) for i in picks]))
    return np.asarray(out[:max(size, len(always))], np.int64)


def corpus_blocks(rows: int, block_rows: int = CORPUS_BLOCK_ROWS) -> list:
    """[(first row, rows in the block), ...]"""
    return [(lo, min(block_rows, rows - lo))
            for lo in range(0, rows, block_rows)]


def corpus_block(seed: int, block: int, rows: int, dim: int,
                 stored_dtype: str = "float16"):
    """Block ``block`` of the corpus, on the device, in the type the
    corpus file stores (the index holds the same values as float32)."""
    import jax
    import jax.numpy as jnp

    def make(key):
        return jax.random.normal(key, (rows, dim), jnp.float32).astype(
            jnp.dtype(stored_dtype))

    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)),
                             1_000_003 + block)
    return jax.jit(make)(key)
