"""``ops/grouped_matmul.py`` against a plain product a group (``for e:
rows[lo:hi] @ stack[e]``), interpreted on the CPU, and the walk it makes
over the row tiles (``tile_visits``: what ``moe_tile_rows`` counts)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from milnce_tpu.models import text_lm
from milnce_tpu.ops import grouped_matmul as gm


def plain(rows, stack, sizes):
    rows, stack = np.asarray(rows, np.float32), np.asarray(stack, np.float32)
    out = np.zeros((rows.shape[0], stack.shape[2]), np.float32)
    lo = 0
    for e, size in enumerate(sizes):
        out[lo:lo + size] = rows[lo:lo + size] @ stack[e]
        lo += size
    return out


# name -> (rows m, k, n, group sizes, (tm, tk, tn) or None = the rule's)
CASES = {
    "uneven_groups": (64, 48, 40, [10, 3, 20, 1, 5, 9], (16, 48, 40)),
    "an_empty_group": (64, 48, 40, [12, 0, 20, 0, 0, 9], (16, 48, 40)),
    "a_group_straddles_a_tile_edge": (64, 32, 24, [10, 30, 7], (16, 32, 24)),
    "one_group_holds_every_row": (64, 32, 24, [0, 64, 0], (16, 32, 24)),
    "rows_beyond_the_groups": (96, 32, 24, [5, 0, 9], (16, 32, 24)),
    "no_row_at_all": (32, 32, 24, [0, 0, 0], (16, 32, 24)),
    "k_and_n_the_tile_does_not_divide": (64, 200, 300, [30, 4, 25],
                                         (16, 128, 128)),
    "fewer_rows_than_a_tile": (24, 16, 8, [5, 7, 1], None),
    "rows_the_tile_does_not_divide": (40, 16, 8, [20, 0, 15], (16, 16, 8)),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_each_groups_rows_times_its_matrix(case, dtype):
    m, k, n, sizes, tiles = CASES[case]
    rng = np.random.default_rng(len(case))
    rows = jnp.asarray(rng.standard_normal((m, k)), dtype)
    stack = jnp.asarray(rng.standard_normal((len(sizes), k, n))
                        / np.sqrt(k), dtype)
    out = gm.grouped_matmul(rows, stack, jnp.asarray(sizes, jnp.int32),
                            tiles=tiles)
    assert out.shape == (m, n) and out.dtype == jnp.float32
    total = sum(sizes)
    # operands as they come, sums in float32: the plain product of the
    # same (rounded) operands, to float32's summation order
    np.testing.assert_allclose(np.asarray(out)[:total],
                               plain(rows, stack, sizes)[:total],
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_rows_beyond_the_groups_are_masked_before_any_sum(dtype):
    """The kernel leaves the rows beyond ``sum(group_sizes)`` unspecified
    (NaN when interpreted); ``held_expert_sum`` masks them ahead of the
    0/1 product that puts the pairs back, in which 0 x NaN would be NaN:
    5 of 24 tokens meet a held expert, the other rows of the turn are
    beyond the groups."""
    tokens, hidden, width = 24, 16, 8
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.standard_normal((tokens, hidden)), dtype)
    stacks = [jnp.asarray(rng.standard_normal(s) / 4, dtype)
              for s in ((3, hidden, width), (3, hidden, width),
                        (3, width, hidden))]
    beyond = np.asarray(gm.grouped_matmul(
        h, stacks[0], jnp.asarray([2, 0, 3], jnp.int32)))[5:]
    assert not np.isfinite(beyond).all()    # the premise, interpreted
    experts = np.full((tokens, 2), 7, np.int32)
    experts[[1, 4, 9, 9, 20], [0, 1, 0, 1, 0]] = [0, 2, 1, 2, 0]
    weights = jnp.asarray(rng.random((tokens, 2)), jnp.float32)
    out, n_held, _most, _rows = text_lm.held_expert_sum(
        h, jnp.asarray(experts), weights, jnp.ones((tokens,), bool),
        *stacks, first_expert=0, dtype=dtype)
    out = np.asarray(out)
    assert int(n_held) == 5 and np.isfinite(out).all()
    assert (out[[0, 2, 3, 23]] == 0).all() and (out[[1, 4, 9, 20]] != 0).any()


def visits_by_hand(sizes, tm):
    """(group, tile) pairs in which the group has a row."""
    lo, pairs = 0, []
    for g, size in enumerate(sizes):
        pairs += [(g, t) for t in range(lo // tm, -(-(lo + size) // tm))
                  if size]
        lo += size
    return pairs


@pytest.mark.parametrize("sizes,m,tm", [
    ([10, 0, 50, 3, 120, 9], 256, 128),
    ([10, 30, 7], 64, 16),
    ([0, 64, 0], 64, 16),
    ([0, 0, 0], 32, 16),
    ([16, 16, 16, 16], 64, 16),
    ([1] * 12, 2048, 128),
])
def test_the_walk_visits_the_tiles_that_hold_a_groups_row(sizes, m, tm):
    offsets, group_ids, tile_ids, visits = gm.tile_visits(
        jnp.asarray(sizes, jnp.int32), m, tm)
    want = visits_by_hand(sizes, tm)
    assert int(visits) == len(want) <= group_ids.shape[0]
    assert list(zip(np.asarray(group_ids)[:len(want)].tolist(),
                    np.asarray(tile_ids)[:len(want)].tolist())) == want
    assert np.asarray(offsets).tolist() == [0] + np.cumsum(sizes).tolist()


@pytest.mark.parametrize("tokens,same", [(24, False), (40, True)],
                         ids=["uneven_groups", "every_token_the_same_experts"])
def test_moe_tile_rows_is_what_the_tile_and_the_counts_imply(tokens, same):
    """The fourth value of ``held_expert_sum`` (the counter
    ``moe_tile_rows``): tile visits x the tile's rows, summed over the
    loop's turns, from the counts alone."""
    hidden, width, held, k = 16, 8, 4, 4
    rng = np.random.default_rng(tokens)
    h = jnp.asarray(rng.standard_normal((tokens, hidden)), jnp.float32)
    stacks = [jnp.asarray(rng.standard_normal(s) / 4, jnp.float32)
              for s in ((held, hidden, width), (held, hidden, width),
                        (held, width, hidden))]
    experts = (np.tile(np.arange(1, 5), (tokens, 1)) if same else
               np.stack([rng.permutation(8)[:k] for _ in range(tokens)]))
    real = np.ones((tokens,), bool)
    real[5] = False
    _out, n_held, _most, tile_rows = jax.jit(
        text_lm.held_expert_sum, static_argnames=("first_expert", "dtype"))(
        h, jnp.asarray(experts, jnp.int32),
        jnp.asarray(rng.random((tokens, k)), jnp.float32), jnp.asarray(real),
        *stacks, first_expert=1, dtype=jnp.float32)
    tile = gm.tiling(tokens // 4, hidden, width, jnp.float32)[0]
    chunk = -(-(tokens // 4) // tile) * tile
    local = experts[real] - 1
    counts = [int((local == e).sum()) for e in range(held)]
    assert int(n_held) == sum(counts)
    ends = np.cumsum(counts)
    want = 0
    for lo in range(0, sum(counts), chunk):       # a turn
        sizes = (np.clip(ends, lo, lo + chunk)
                 - np.clip(ends - counts, lo, lo + chunk))
        want += len(visits_by_hand(sizes.tolist(), tile)) * tile
    assert int(tile_rows) == want >= int(n_held)
    assert sum(counts) > chunk                    # more than one turn


def test_the_tile_follows_the_shapes():
    """At the served tower's shapes: 128 rows a visit and a 4 MB block of
    whole matrix rows (7168 x 2048 and 2048 x 7168, bfloat16); a test's
    shapes: every row in one tile, the matrices whole."""
    assert gm.tiling(2048, 7168, 2048, jnp.bfloat16) == (128, 1024, 2048)
    assert gm.tiling(512, 7168, 2048, jnp.bfloat16)[0] == 128
    tm, tk, tn = gm.tiling(2048, 2048, 7168, jnp.bfloat16)
    assert tm == 128 and 2048 % tk == 0 and 7168 % tn == 0
    assert tk * tn * 2 <= gm.BLOCK_BYTES
    assert gm.tiling(6, 16, 8, jnp.float32) == (16, 16, 8)
