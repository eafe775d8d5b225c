"""Grouped matrix product for the routed expert layers: rows sorted by
group, one matrix a group, ``out[lo_g:hi_g] = rows[lo_g:hi_g] @ stack[g]``.

Why a kernel of its own: an expert of the served sentence tower is fed a
dozen to a few dozen rows a flush, and its three matrices are 29 MB each.
The work is bound by the bytes of the matrices, as long as the rows
multiplied against each block of a matrix stay few: the kernel walks the
row tiles that HOLD rows of a group and nothing else, ``tm`` rows a visit,
streaming that group's matrix block by block under the visit
(``lax.ragged_dot``'s TPU kernel multiplied a 512-row tile a group: the
MXU, not the bytes, set its pace; PERF.md section 6, PR 31).

The walk (after the megablox ``gmm`` of ``jax.experimental.pallas.ops``):
the grid is (n tiles, visits, k tiles); a visit is a (group, row tile)
pair in which the group has a row, ordered by group and then by tile, so
a row tile that several groups share is visited by them one after the
other and its output block stays in VMEM between them; each visit stores
only its own group's rows.  The number of visits is a value of the
program (the grid's middle bound is dynamic), at most
``m / tm + groups - 1``.

Rows at and beyond ``sum(group_sizes)`` come back UNSPECIFIED (a row tile
without a group's row is never visited, and in a visited one only the
groups' rows are stored): the caller masks them before any sum over rows.

On the CPU the kernel runs in Pallas interpret mode (ops/pallas_mode.py),
which has no tile grid and takes any shape; compiled, ``tm`` is a multiple
of 16 and ``tk`` / ``tn`` are multiples of 128 or the whole dimension.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from milnce_tpu.ops import pallas_mode

ROW_TILE = 128                  # rows a visit: the MXU's side; per block of
#                                 a matrix the MXU then works about half the
#                                 time the block takes to arrive from HBM
BLOCK_BYTES = 4 << 20           # of a matrix, a grid step (double-buffered)
_LANES, _SUBLANES = 128, 16
_VMEM_LIMIT = 48 << 20


def _round_up(x: int, to: int) -> int:
    return -(-x // to) * to


def _largest_divisor(x: int, at_most: int) -> int:
    """The largest multiple of 128 that divides ``x`` and is at most
    ``at_most``; ``x`` itself where it fits or has no such divisor."""
    if x <= at_most:
        return x
    for t in range(at_most // _LANES * _LANES, 0, -_LANES):
        if x % t == 0:
            return t
    return x


def tiling(m: int, k: int, n: int, dtype) -> tuple[int, int, int]:
    """(tm, tk, tn) for ``m`` rows against (k, n) matrices of ``dtype``:
    ``ROW_TILE`` rows a visit (fewer where there are fewer rows), and the
    largest block of a matrix inside ``BLOCK_BYTES`` that divides it:
    whole rows of the matrix first (a block of whole rows is one
    contiguous piece of HBM), then as many of them as fit."""
    itemsize = jnp.dtype(dtype).itemsize
    tm = min(ROW_TILE, _round_up(m, _SUBLANES))
    tn = _largest_divisor(n, max(_LANES, BLOCK_BYTES // itemsize // _LANES))
    tk = _largest_divisor(k, max(_LANES, BLOCK_BYTES // itemsize // tn))
    return tm, tk, tn


def tile_visits(group_sizes, m: int, tm: int):
    """The walk over ``m`` rows (a multiple of ``tm``) sorted into groups
    of ``group_sizes`` -> (offsets (G + 1,), group_ids (V,), tile_ids (V,),
    visits ()), all int32, ``V = m / tm + G - 1``: visit ``v < visits``
    multiplies row tile ``tile_ids[v]`` with the matrix of group
    ``group_ids[v]``; the entries from ``visits`` on are never read."""
    groups = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes).astype(jnp.int32)
    starts = ends - group_sizes
    first = starts // tm
    tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    visit_ends = jnp.cumsum(tiles).astype(jnp.int32)
    v = jnp.arange(m // tm + groups - 1, dtype=jnp.int32)
    group_ids = jnp.minimum(
        jnp.sum(visit_ends[None, :] <= v[:, None], axis=1), groups - 1
    ).astype(jnp.int32)
    tile_ids = jnp.clip(first[group_ids] + v
                        - (visit_ends - tiles)[group_ids], 0, m // tm - 1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, group_ids, tile_ids.astype(jnp.int32), visit_ends[-1]


def _kernel(offsets, group_ids, tile_ids, rows, matrix, out, acc, *,
            tm, tk, k, precision):
    visit, k_i, k_tiles = pl.program_id(1), pl.program_id(2), pl.cdiv(k, tk)

    @pl.when(k_i == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    x, w = rows[...], matrix[...]
    if k % tk:
        # the last block along k hangs over the edge: what lies beyond it
        # is not the operands' (and 0 x NaN is NaN), so both sides go to 0
        left = k - k_i * tk
        x = jnp.where(lax.broadcasted_iota(jnp.int32, x.shape, 1) < left,
                      x, jnp.zeros_like(x))
        w = jnp.where(lax.broadcasted_iota(jnp.int32, w.shape, 0) < left,
                      w, jnp.zeros_like(w))
    acc[...] += lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                                precision=precision,
                                preferred_element_type=jnp.float32)

    @pl.when(k_i == k_tiles - 1)
    def _():
        group = group_ids[visit]
        row = (lax.broadcasted_iota(jnp.int32, acc.shape, 0)
               + tile_ids[visit] * tm)
        mine = (row >= offsets[group]) & (row < offsets[group + 1])
        out[...] = jnp.where(mine, acc[...],
                             out[...].astype(jnp.float32)).astype(out.dtype)


def grouped_matmul(rows, stack, group_sizes, *, out_dtype=jnp.float32,
                   tiles: tuple[int, int, int] | None = None):
    """rows (m, k) sorted by group, stack (G, k, n), group_sizes (G,)
    int32 with ``sum <= m`` -> (m, n) ``out_dtype``: each group's rows
    times its matrix, operands as they come (one dtype), sums in float32.
    Rows from ``sum(group_sizes)`` on are unspecified.  ``tiles``: (tm,
    tk, tn), by default ``tiling`` of the shapes."""
    (m, k), (groups, _, n) = rows.shape, stack.shape
    assert stack.shape[1] == k and group_sizes.shape == (groups,)
    assert rows.dtype == stack.dtype, (rows.dtype, stack.dtype)
    return _grouped_matmul(
        rows, stack, group_sizes.astype(jnp.int32),
        out_dtype=jnp.dtype(out_dtype),
        tiles=tuple(tiles or tiling(m, k, n, rows.dtype)),
        interpret=pallas_mode.interpret())


# jitted with everything that shapes the kernel static: the 21 products of
# a tower (7 layers x gate, up, down) are then traced and lowered once a
# signature, not once a call site (a second a rung's program)
@functools.partial(jax.jit, static_argnames=("out_dtype", "tiles",
                                             "interpret"))
def _grouped_matmul(rows, stack, group_sizes, *, out_dtype, tiles, interpret):
    (m, k), n = rows.shape, stack.shape[2]
    tm, tk, tn = tiles
    padded = _round_up(m, tm)
    if padded != m:
        rows = jnp.pad(rows, ((0, padded - m), (0, 0)))
    offsets, group_ids, tile_ids, visits = tile_visits(group_sizes, padded, tm)
    # a product of bfloat16 inputs is exact in the float32 it is summed
    # in: a process-wide "highest" (the tests') has nothing to add, and
    # the TPU's compiler refuses it
    precision = (None if rows.dtype == jnp.float32
                 else lax.Precision.DEFAULT)
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm, tk=tk, k=k, precision=precision),
        out_shape=jax.ShapeDtypeStruct((padded, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pl.cdiv(n, tn), visits, pl.cdiv(k, tk)),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, v, k_i, _o, _g, t:
                             (t[v], k_i)),
                pl.BlockSpec((None, tk, tn), lambda n_i, v, k_i, _o, g, _t:
                             (g[v], k_i, n_i)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda n_i, v, k_i, _o, _g, t:
                                   (t[v], n_i)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="grouped_matmul",
        interpret=interpret,
    )(offsets, group_ids, tile_ids, rows, stack)
    return out[:m] if padded != m else out
