"""One shard's scan of the retrieval index and its top-k, in one kernel:
``lax.top_k(where(col < valid, queries @ corpus.T, -inf), k)`` without the
(Q, rows) score matrix ever leaving VMEM.

Why a kernel of its own: the pass over a 3,000,000 x 512 float32 shard was
two programs' worth of work, a fusion that wrote (Q, 3 M) float32 scores
to HBM and the ``TopK`` custom call that read them back to keep 10 a row
(0.905 + 0.608 s of a traced 3 s, PERF.md section 5, PR 37).  Here the
grid walks the shard's rows a tile at a time (the tile's rows DMA'd from
HBM into VMEM, double-buffered; the query block stays resident), scores
the tile on the MXU and merges it into a running best-k a query kept in
VMEM scratch; the (Q, k) winners are written once, at the last tile.

The contract is the parent program's: the same winners in the same order,
ties to the LOWER row (``DeviceRetrievalIndex.topk``'s rule), rows at or
past ``valid`` scoring ``-inf`` and ranked after every real row by their
own row number (so where ``valid < k`` the tail is ``-inf`` at rows
``valid, valid + 1, ...``, as ``lax.top_k`` gives).  Real scores are
taken to be finite.

The merge, on every tile: ``k`` rounds, each of which takes the best
score left in the tile a query at its lowest column, puts it in place of
that query's running worst if it beats it (strictly: every row of the
tile comes after every row already held, so a tie keeps the held one),
and strikes it from the tile.  The running set is unsorted; it is sorted
once, at the last tile.  On the chip the merge hides under the tile's
DMA: a merge that stopped when no query could gain read the same 8.18 ms
over 3 M rows at 64 queries (PERF.md section 6, PR 38).

The product is the parent's: float32 operands at DEFAULT precision.  The
parent's compiled HLO holds an f32 ``convolution`` with no
``precision_config`` (DEFAULT), which the TPU runs as one pass of
bfloat16-rounded operands summed in float32; Mosaic runs DEFAULT the same
way (on the chip the kernel's scores equal the parent's bit for bit, and
XLA's DEFAULT product equals one of bfloat16 operands).  Interpreted on
the CPU both are float32.

On the CPU the kernel runs in Pallas interpret mode (ops/pallas_mode.py);
compiled, the tile is a multiple of 128 rows or the whole shard.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from milnce_tpu.ops import pallas_mode

BLOCK_BYTES = 8 << 20           # of the index, a grid step (double-buffered)
SCORE_BYTES = 2 << 20           # of the (Q, tile) float32 scores a step
_LANES = 128
_VMEM_LIMIT = 64 << 20
_NO_ROW = jnp.iinfo(jnp.int32).max


def _round_up(x: int, to: int) -> int:
    return -(-x // to) * to


def tile_rows(rows: int, queries: int, dim: int, dtype) -> int:
    """Rows of the index a grid step, for a shard of ``rows`` x ``dim``
    of ``dtype`` scanned by ``queries`` queries: as many as fit
    ``BLOCK_BYTES`` of the index and ``SCORE_BYTES`` of scores, in whole
    lanes of the score block (a multiple of 128); the whole shard where
    it fits."""
    itemsize = jnp.dtype(dtype).itemsize
    fit = min(BLOCK_BYTES // (dim * itemsize),
              SCORE_BYTES // (max(queries, 8) * 4))
    tile = max(_LANES, fit // _LANES * _LANES)
    return rows if rows <= tile else tile


def scan_topk(queries, corpus, valid, k: int, *, tile: int | None = None):
    """queries (Q, D) float32, corpus (R, D) float32, valid (1,) int32 ->
    ((Q, k) float32 scores, (Q, k) int32 rows of ``corpus``), best first,
    ties to the lower row; rows from ``valid[0]`` on score ``-inf``.
    ``tile``: rows a grid step, by default ``tile_rows`` of the shapes."""
    (q, dim), rows = queries.shape, corpus.shape[0]
    if not 1 <= k <= rows:
        raise ValueError(f"k={k} outside [1, {rows} rows]")
    tile = int(tile or tile_rows(rows, q, dim, corpus.dtype))
    return _scan_topk(queries, corpus, valid.astype(jnp.int32), k=int(k),
                      tile=tile, interpret=pallas_mode.interpret())


def _kernel(valid_ref, q_ref, c_ref, s_out, i_out, keys, best_s, best_i, *,
            k, tile, rows):
    t = pl.program_id(0)
    base = t * tile
    q_rows, lanes = best_s.shape
    lane = lax.broadcasted_iota(jnp.int32, (q_rows, lanes), 1)
    held = lane < k

    @pl.when(t == 0)
    def _():
        best_s[...] = jnp.full(best_s.shape, -jnp.inf, jnp.float32)
        # an empty slot: no row yet, each its own so that one is the worst
        best_i[...] = _NO_ROW - lane

    scores = lax.dot_general(q_ref[...], c_ref[...], (((1,), (1,)), ((), ())),
                             precision=lax.Precision.DEFAULT,
                             preferred_element_type=jnp.float32)
    col = lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    live = jnp.minimum(valid_ref[0], rows) - base
    keys[...] = jnp.where(col < live, scores, -jnp.inf)

    def worst():
        s = jnp.where(held, best_s[...], jnp.inf)
        w_s = jnp.min(s, axis=1, keepdims=True)
        # among the held scores equal to the worst, the latest row
        w_i = jnp.max(jnp.where(s == w_s, best_i[...], -1), axis=1,
                      keepdims=True)
        return w_s, w_i

    def round_(top):
        ks = keys[...]
        at = jnp.min(jnp.where(ks == top, col, tile), axis=1, keepdims=True)
        keys[...] = jnp.where(col == at, -jnp.inf, ks)
        w_s, w_i = worst()
        take = top > w_s
        out = take & (best_s[...] == w_s) & (best_i[...] == w_i) & held
        best_s[...] = jnp.where(out, top, best_s[...])
        best_i[...] = jnp.where(out, at + base, best_i[...])
        return jnp.max(keys[...], axis=1, keepdims=True)

    lax.fori_loop(0, k, lambda _, top: round_(top),
                  jnp.max(keys[...], axis=1, keepdims=True))

    @pl.when(t == pl.num_programs(0) - 1)
    def _():
        # the held set, best first: a score's row, the lowest on ties; a
        # slot no real row filled is a pad row, the lowest pad first
        s, i = jnp.where(held, best_s[...], -jnp.inf), best_i[...]

        def place(j, carry):
            s, out_s, out_i = carry
            m = jnp.max(s, axis=1, keepdims=True)
            at = jnp.min(jnp.where(s == m, i, _NO_ROW), axis=1,
                         keepdims=True)
            at = jnp.where(m == -jnp.inf, j, at)
            return (jnp.where((i == at) & (s == m), -jnp.inf, s),
                    jnp.where(lane == j, m, out_s),
                    jnp.where(lane == j, at, out_i))

        _, out_s, out_i = lax.fori_loop(
            0, k, place, (s, jnp.full(s.shape, -jnp.inf, jnp.float32),
                          jnp.zeros(i.shape, jnp.int32)))
        s_out[...] = out_s[:, :k]
        i_out[...] = out_i[:, :k]


@functools.partial(jax.jit, static_argnames=("k", "tile", "interpret"))
def _scan_topk(queries, corpus, valid, *, k, tile, interpret):
    (q, dim), rows = queries.shape, corpus.shape[0]
    lanes = _round_up(k, _LANES)
    return pl.pallas_call(
        functools.partial(_kernel, k=k, tile=tile, rows=rows),
        out_shape=(jax.ShapeDtypeStruct((q, k), jnp.float32),
                   jax.ShapeDtypeStruct((q, k), jnp.int32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(rows, tile),),
            in_specs=[pl.BlockSpec((q, dim), lambda t, v: (0, 0)),
                      pl.BlockSpec((tile, dim), lambda t, v: (t, 0))],
            out_specs=[pl.BlockSpec((q, k), lambda t, v: (0, 0)),
                       pl.BlockSpec((q, k), lambda t, v: (0, 0))],
            scratch_shapes=[pltpu.VMEM((q, tile), jnp.float32),
                            pltpu.VMEM((q, lanes), jnp.float32),
                            pltpu.VMEM((q, lanes), jnp.int32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * q * rows * dim, transcendentals=0,
            bytes_accessed=rows * dim * corpus.dtype.itemsize),
        name="scan_topk",
        interpret=interpret,
    )(valid, queries, corpus)
