"""``ops/scan_topk.py`` against what it replaced: the masked product and
``lax.top_k`` over it, bit for bit in scores and rows, interpreted on the
CPU; and the sharded program ``make_topk_fn`` builds on it against the
parent's program on the mesh the index tests use.

The operands are halves of small integers: every product and every sum
of them is exact in float32, so the kernel's tile-by-tile products and
the whole-shard product agree to the bit whatever order either sums in,
and equal scores are common — the ties are part of what is tested."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from milnce_tpu.ops import scan_topk as st
from milnce_tpu.serving.index import make_topk_fn, shard_corpus


def halves(rng, shape):
    return (rng.integers(-3, 4, shape) / 2).astype(np.float32)


def masked_top_k(queries, corpus, valid, k):
    """The parent's local top-k: the product, rows from ``valid`` on at
    -inf, ``lax.top_k``."""
    scores = jnp.asarray(queries) @ jnp.asarray(corpus).T
    col = lax.iota(jnp.int32, corpus.shape[0])
    return lax.top_k(jnp.where(col[None, :] < valid, scores, -jnp.inf), k)


def assert_same(got, want):
    (s, i), (ws, wi) = got, want
    np.testing.assert_array_equal(np.asarray(i), np.asarray(wi))
    np.testing.assert_array_equal(np.asarray(s).view(np.int32),
                                  np.asarray(ws).view(np.int32))


# name -> (queries, rows, dim, valid, k, tile or None = the rule's)
CASES = {
    "rows_the_tile_does_not_divide": (16, 1000, 64, 1000, 10, 256),
    "valid_short_of_the_rows": (16, 1000, 64, 611, 10, 256),
    "valid_short_of_k": (8, 300, 32, 5, 10, 128),
    "valid_inside_the_last_tile_short_of_k": (8, 300, 32, 260, 10, 128),
    "an_empty_shard": (8, 37, 16, 0, 10, None),
    "the_whole_shard_one_tile": (8, 3, 16, 3, 3, None),
    "k_past_one_row_of_lanes": (8, 400, 16, 390, 130, 128),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_is_the_masked_top_k(case):
    q, rows, dim, valid, k, tile = CASES[case]
    rng = np.random.default_rng(len(case))
    queries, corpus = halves(rng, (q, dim)), halves(rng, (rows, dim))
    got = st.scan_topk(jnp.asarray(queries), jnp.asarray(corpus),
                       jnp.asarray([valid], jnp.int32), k, tile=tile)
    assert_same(got, masked_top_k(queries, corpus, valid, k))


def test_planted_ties_go_to_the_lower_row():
    """Four copies of one row that outscores every other, in three tiles:
    they come first, lowest row first, at one score."""
    rng = np.random.default_rng(7)
    queries = (rng.integers(1, 4, (16, 32)) / 2).astype(np.float32)
    corpus = halves(rng, (600, 32))
    planted = [7, 250, 251, 599]
    corpus[planted] = 2.0
    got = st.scan_topk(jnp.asarray(queries), jnp.asarray(corpus),
                       jnp.asarray([600], jnp.int32), 10, tile=256)
    s, i = np.asarray(got[0]), np.asarray(got[1])
    assert (i[:, :4] == planted).all()
    assert (s[:, :4] == s[:, :1]).all()
    assert_same(got, masked_top_k(queries, corpus, 600, 10))


@pytest.mark.parametrize("q", [4, 8, 16, 32, 64])
def test_every_query_rung_of_both_ladders(q):
    """4 / 8 / 16 (the step cell's ladder) and 16 / 32 / 64 (the other
    three cells'), over three tiles, the last one ragged and past
    ``valid``."""
    rng = np.random.default_rng(q)
    queries, corpus = halves(rng, (q, 64)), halves(rng, (700, 64))
    got = st.scan_topk(jnp.asarray(queries), jnp.asarray(corpus),
                       jnp.asarray([650], jnp.int32), 10, tile=256)
    assert_same(got, masked_top_k(queries, corpus, 650, 10))


def test_tile_rule():
    # 8 MB of a 512-wide float32 index a step; the whole shard where it
    # fits; whole rows of lanes of the score block otherwise
    assert st.tile_rows(3_000_000, 64, 512, jnp.float32) == 4096
    assert st.tile_rows(1_062_500, 4, 512, jnp.float32) == 4096
    assert st.tile_rows(3_000, 64, 512, jnp.float32) == 3_000
    assert st.tile_rows(10 ** 6, 512, 512, jnp.float32) == 1024
    assert st.tile_rows(10 ** 6, 64, 4096, jnp.bfloat16) == 1024


def test_k_outside_the_shard_is_refused():
    with pytest.raises(ValueError, match="k=5 outside"):
        st.scan_topk(jnp.zeros((8, 16)), jnp.zeros((4, 16)),
                     jnp.asarray([4], jnp.int32), 5)


def parent_topk_fn(mesh, data_axis, k):
    """``make_topk_fn`` as it stood before the kernel (PR 37)."""

    def local_topk(corpus_l, valid_l, queries):
        scores = queries @ corpus_l.T
        col = lax.iota(jnp.int32, corpus_l.shape[0])
        scores = jnp.where(col[None, :] < valid_l[0], scores, -jnp.inf)
        s, i = lax.top_k(scores, k)
        gidx = i + lax.axis_index(data_axis) * corpus_l.shape[0]
        s_all = lax.all_gather(s, data_axis, axis=1, tiled=True)
        i_all = lax.all_gather(gidx, data_axis, axis=1, tiled=True)
        s_top, j = lax.top_k(s_all, k)
        return s_top, jnp.take_along_axis(i_all, j, axis=1)

    return jax.jit(jax.shard_map(
        local_topk, mesh=mesh,
        in_specs=(P(data_axis), P(data_axis), P()),
        out_specs=(P(), P()), check_vma=False))


@pytest.mark.parametrize("size", [8 * 300 - 17, 5])
def test_the_sharded_program_returns_the_parents(size):
    """On the index tests' mesh (every device on ``data``): a corpus that
    leaves the last shard short (or, at 5 rows, most shards empty), with
    copies of a winning row in several shards."""
    mesh = Mesh(np.array(jax.devices()), ("data",))
    n = len(jax.devices())
    rng = np.random.default_rng(size)
    emb = halves(rng, (size, 32))
    emb[[1, size // 2, size - 1]] = 2.0
    rows = max(-(-size // n), 3)
    corpus, valid = shard_corpus(emb, n, rows)
    queries = jnp.asarray((rng.integers(1, 4, (16, 32)) / 2)
                          .astype(np.float32))
    args = (jnp.asarray(corpus), jnp.asarray(valid), queries)
    assert_same(make_topk_fn(mesh, "data", 3)(*args),
                parent_topk_fn(mesh, "data", 3)(*args))
