"""Soft-DTW as a Pallas TPU kernel (forward + analytic backward).

TPU-native redesign of the reference's numba-CUDA wavefront kernels
(soft_dtw_cuda.py:34-76 forward, :79-112 backward, :115-175 autograd
wiring):

- CUDA launches one block per pair with one thread per row and a
  ``syncthreads`` barrier per anti-diagonal.  On TPU the whole wavefront
  of one pair lives in VMEM: the kernel runs a ``fori_loop`` over the
  2N-1 anti-diagonals, each step a fully-vectorized VPU op over the
  diagonal (no barriers — the sequential loop IS the dependency chain).
- Tables are kept **diagonal-major (skewed) and diagonal-LEADING**:
  refs have shape (n_diagonals, batch_tile, N+1).  The per-step dynamic
  index (the diagonal counter) lands on the *leading, untiled* dimension
  — a cheap address offset in Mosaic — while the (batch_tile, N+1)
  slices the loop actually computes on are statically-shaped, fully
  tiled (8, 128) vector ops.  Putting the diagonal on a tiled dimension
  instead makes every loop step a read-modify-write of the whole block
  (measured ~300x slower than lax.scan on a v5e before this layout).
- Batch is tiled into the block (``bt`` multiple of 8 on the sublane
  dim): alignment lengths in the MIL-NCE regime are 8-32 frames, and
  SDTW_3 evaluates B^2 pairs — batch fills the VPU the short diagonal
  can't.
- The backward pass implements the Cuturi-Blondel E-matrix recurrence as
  a reverse wavefront over the saved R table, wired in via
  ``jax.custom_vjp`` (mirror of soft_dtw_cuda.py:148-175).
- No 1024-length cap (the CUDA block-size limit that forces the
  reference onto its CPU path, soft_dtw_cuda.py:318-320): when the
  per-pair tables outgrow VMEM the forward streams diagonals from HBM in
  chunks (two carry rows of scratch) and the backward falls back to the
  scan — the ceiling is HBM, not VMEM.
- Borders use the same large-finite sentinel as the scan reference
  (`BIG`), with invalid cells mapped to ``-BIG`` in the backward — the
  finite analog of the reference's ``inf -> -inf`` fixup
  (soft_dtw_cuda.py:101-102).

On the CPU the kernel runs in Pallas interpret mode (ops/pallas_mode.py),
so the same code path is unit-testable there.  All three variants
(in-VMEM, chunked, backward) lower through Mosaic; that they compile for
a v5e at the main path's shapes is pinned by tests/test_tpu_compile.py.

Mosaic lowering rules the layout was bought with (found on a v5e):

- Block shapes must keep the *last two* dims (8, 128)-tileable or equal
  to the array dims; scalar-ish outputs like a (bt, 1) value block are
  unlowerably mis-tiled — read scalars out of the result table on the
  host instead.
- Dynamic indices belong on the *leading, untiled* ref dimension
  (diagonal-leading layout); a dynamic sublane index makes every loop
  step a whole-block read-modify-write.
- Mosaic's vector lowering crashes (``Check failed: limits[i] <=
  dim(i)``) when leading-dim x sublane block area gets large; bisected:
  forward survives ~8192, backward dies above ~5360.  ``_batch_tile``
  caps the product at 5120.
- Scoped VMEM is ~16 MB and OOMs are *compile-time* errors (``Ran out
  of memory in memory space vmem``); the 1.2M-element
  ``_VMEM_TABLE_BUDGET`` keeps the worst block (3 tables,
  double-buffered) near ~11 MB.
- A Mosaic grid executes its blocks SEQUENTIALLY on the core, so a batch
  split into G VMEM-sized tiles runs G*(N+M) wavefront steps end to end,
  where one ``lax.scan`` runs (N+M) wide ones: a multi-block
  sublane-batch grid has G times the scan's sequential depth.  The
  kernel wins where that inversion doesn't happen — one block holding
  the whole batch, or the lanes layout (batch on the 128-wide lane dim).
  The chunked long-sequence kernels advance ONE shared wavefront across
  the grid's chunk axis, so chunking adds HBM streaming, not depth.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from milnce_tpu.ops import pallas_mode
from milnce_tpu.ops.softdtw import BIG, check_bandwidth, skew_cost


# ---------------------------------------------------------------- forward
def _fwd_kernel(d_ref, r_ref, *, n: int, m: int, gamma: float,
                bandwidth: int, bt: int):
    """One batch tile of ``bt`` pairs, whole wavefront in VMEM.

    d_ref: (N+M-1, bt, N) skewed costs.  r_ref: (N+M+1, bt, N+1) skewed
    DP tables (padded coords).  Both diagonal-leading: ``ref[p]`` is the
    (bt, N+1) anti-diagonal p — a static-shaped slice at a dynamic
    leading offset."""
    n1 = n + 1
    i_buf = lax.broadcasted_iota(jnp.int32, (bt, n1), 1)

    # Diagonal 0: R[0,0] = 0, rest BIG.  Diagonal 1: all BIG (borders).
    r_ref[0] = jnp.where(i_buf == 0, 0.0, BIG)
    r_ref[1] = jnp.full((bt, n1), BIG, jnp.float32)

    inv_gamma = 1.0 / gamma

    def body(p, _):
        r_mm = r_ref[p - 2]                         # diag p-2: (bt, N+1)
        r_m = r_ref[p - 1]                          # diag p-1
        cost = d_ref[p - 2]                         # D[i-1, j-1] along diag p
        prev_diag = r_mm[:, :-1]                    # R[i-1, j-1]
        prev_up = r_m[:, :-1]                       # R[i-1, j]
        prev_left = r_m[:, 1:]                      # R[i, j-1]
        n0 = -prev_diag * inv_gamma
        n1_ = -prev_up * inv_gamma
        n2 = -prev_left * inv_gamma
        mx = jnp.maximum(jnp.maximum(n0, n1_), n2)
        softmin = -gamma * (jnp.log(jnp.exp(n0 - mx) + jnp.exp(n1_ - mx)
                                    + jnp.exp(n2 - mx)) + mx)
        interior = cost + softmin                   # i = 1..N
        row = jnp.concatenate(
            [jnp.full((bt, 1), BIG, jnp.float32), interior], axis=1)
        j_buf = p - i_buf
        valid = ((i_buf >= 1) & (j_buf >= 1) & (j_buf <= m))
        if bandwidth > 0:                           # soft_dtw_cuda.py:66
            valid &= jnp.abs(i_buf - j_buf) <= bandwidth
        r_ref[p] = jnp.where(valid, row, BIG)
        return 0

    lax.fori_loop(2, n + m + 1, body, 0)


def _fwd_kernel_chunked(d_ref, r_ref, carry, *, n: int, m: int,
                        gamma: float, bandwidth: int, chunk: int, bt: int):
    """Streaming forward: grid (B/bt, n_chunks), diagonals arrive in
    CHUNK-sized blocks from HBM; only two carry rows live across chunks
    (VMEM scratch).  Removes the all-diagonals-in-VMEM requirement, so the
    sequence-length ceiling is HBM, not VMEM (the reference's ceiling was
    1024 CUDA threads, soft_dtw_cuda.py:318-320).

    Block t of chunk c holds diagonal p = c*chunk + t + 2; r_ref stores
    diagonals >= 2 (diagonals 0/1 are constants, re-attached host-side).
    The chunk index is the fast grid axis, so for each batch tile the
    chunks arrive in order and the carry threads through.
    """
    n1 = n + 1
    c = pl.program_id(1)
    i_buf = lax.broadcasted_iota(jnp.int32, (bt, n1), 1)
    inv_gamma = 1.0 / gamma

    @pl.when(c == 0)
    def _init():
        carry[0] = jnp.where(i_buf == 0, 0.0, BIG)           # diag 0
        carry[1] = jnp.full((bt, n1), BIG, jnp.float32)      # diag 1

    def body(t, _):
        p = c * chunk + t + 2
        r_mm = carry[0]
        r_m = carry[1]
        cost = d_ref[t]                              # (bt, N)
        n0 = -r_mm[:, :-1] * inv_gamma
        n1_ = -r_m[:, :-1] * inv_gamma
        n2 = -r_m[:, 1:] * inv_gamma
        mx = jnp.maximum(jnp.maximum(n0, n1_), n2)
        softmin = -gamma * (jnp.log(jnp.exp(n0 - mx) + jnp.exp(n1_ - mx)
                                    + jnp.exp(n2 - mx)) + mx)
        row = jnp.concatenate(
            [jnp.full((bt, 1), BIG, jnp.float32), cost + softmin], axis=1)
        j_buf = p - i_buf
        valid = ((i_buf >= 1) & (j_buf >= 1) & (j_buf <= m))
        if bandwidth > 0:
            valid &= jnp.abs(i_buf - j_buf) <= bandwidth
        row = jnp.where(valid, row, BIG)
        r_ref[t] = row
        carry[0] = r_m
        carry[1] = row
        return 0

    lax.fori_loop(0, chunk, body, 0)


# Budget (in f32 elements) for the per-block VMEM resident set of the
# single-shot kernels.  The backward holds THREE (N+M+3)x(N+2) tables per
# pair and Pallas double-buffers HBM<->VMEM, so the worst case is
# ~6x table x bt x 4 bytes plus temporaries; 1.2M elements keeps that
# under ~11 MB of the ~16 MB/core (verified against a real v5e scoped-
# vmem OOM at 1.9M-element blocks).
_VMEM_TABLE_BUDGET = 1_200_000

_CHUNK_VMEM_ELEMS = 500_000  # chunked-path block budget (d+r, dbl-buffered)

# Empirical Mosaic vector-lowering cap on (leading-dim x sublane) block
# area, bisected on v5e libtpu 2026-07 (see _batch_tile docstring); both
# kernel layouts must respect it.
_MOSAIC_BLOCK_AREA_CAP = 5120


def _batch_tile(n: int, m: int) -> int:
    """Pairs per block, multiple of 8 (Mosaic sublane tiling), capped at
    128.  0 means even an 8-pair tile busts the VMEM budget — callers
    must take the streaming/scan long-sequence path.

    Extra cap (empirical, v5e libtpu 2026-07): grids whose
    (leading-dim x batch-tile) block area is too large crash Mosaic's
    vector lowering (`Check failed: limits[i] <= dim(i)` in
    vector_extract_strided_slice).  Bisected boundaries: the forward
    survives products up to ~8192 (65x128 dies, 65x120 ok); the backward
    dies earlier (67x88=5896 dies, 67x80=5360 and 131x40=5240 ok).  Cap
    both at 5120 — under every observed-good point with margin — using
    the larger (backward) leading dim N+M+3."""
    table = (n + m + 3) * (n + 2)
    bt = min(_VMEM_TABLE_BUDGET // (3 * table), 128) // 8 * 8
    return min(bt, _MOSAIC_BLOCK_AREA_CAP // (n + m + 3) // 8 * 8)


def _table_fits_vmem(n: int, m: int) -> bool:
    return _batch_tile(n, m) >= 8


def _pad_batch(x: jax.Array, bt: int) -> jax.Array:
    bsz = x.shape[0]
    pad = (-bsz) % bt
    return x if pad == 0 else jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))


def _tile_for_batch(bsz: int, n: int, m: int) -> int:
    """The batch tile the single-shot kernels use for an actual batch:
    VMEM/Mosaic-capped, never padding a tiny batch up to a full tile."""
    bt = _batch_tile(n, m)
    assert bt >= 8, (f"soft-DTW tables for N={n}, M={m} exceed the Pallas "
                     "VMEM budget; use the chunked/scan long-sequence path")
    return min(bt, -(-bsz // 8) * 8)


def fits_one_block(bsz: int, n: int, m: int) -> bool:
    """True when the whole padded batch runs as a SINGLE kernel block —
    the regime where the wavefront kernel beats the scan (~3x, measured
    on a v5e before PR 1, to be measured again by the benchmark).
    Multi-block grids re-run the diagonal loop per
    tile and lose to one scan over the full batch."""
    bt = _batch_tile(n, m)
    return bt >= 8 and -(-bsz // 8) * 8 <= bt


def _run_forward(d_skew: jax.Array, n: int, m: int, gamma: float,
                 bandwidth: int):
    """d_skew: (B, N+M-1, N) -> (value (B,), r_skew (B, N+M+1, N+1))."""
    bsz = d_skew.shape[0]
    bt = _tile_for_batch(bsz, n, m)
    d3 = _pad_batch(d_skew, bt).transpose(1, 0, 2)   # diag-leading
    bp = d3.shape[1]
    kernel = functools.partial(_fwd_kernel, n=n, m=m, gamma=gamma,
                               bandwidth=bandwidth, bt=bt)
    r3 = pl.pallas_call(
        kernel,
        grid=(bp // bt,),
        in_specs=[pl.BlockSpec((n + m - 1, bt, n), lambda b: (0, b, 0))],
        out_specs=pl.BlockSpec((n + m + 1, bt, n + 1), lambda b: (0, b, 0)),
        out_shape=jax.ShapeDtypeStruct((n + m + 1, bp, n + 1), jnp.float32),
        interpret=pallas_mode.interpret(),
    )(d3)
    r_skew = r3.transpose(1, 0, 2)[:bsz]
    return r_skew[:, n + m, n], r_skew


# -------------------------------------------------- batch-on-lanes layout
# Alternative single-shot layout for LARGE batches of SHORT pairs (the
# SDTW_3 B^2 regime): refs are (diagonals, N+1, batch_lanes), i.e. the
# alignment index lives on SUBLANES and batch fills the 128-wide LANE
# dimension.  Per wavefront step this touches ceil((N+1)/8) vector tiles
# instead of ceil(bt/8) — for batch >> N+1 that is up to n1/128 of the
# sublane-batch layout's total tile traffic.  Measured on a v5e before
# PR 1, to be measured again by the benchmark: 25.8x over the scan at
# (128, 17, 15) fwd+bwd and 3.5x at (1024, 32, 32) — regimes where the sublane-batch layout LOSES
# to the scan — so it is the default wherever its shape conditions hold
# (escape hatch: MILNCE_SDTW_LANES=0).


def _lane_tile(bsz: int) -> int:
    """Lanes per block: one full-lane block (<=128, lane dim equal to
    the array's) or 128-lane blocks over a padded batch."""
    return bsz if bsz <= 128 else 128


def _use_lanes(bsz: int, n: int, m: int) -> bool:
    if os.environ.get("MILNCE_SDTW_LANES") == "0":
        return False
    area = (n + m + 3) * (n + 2)
    bl = _lane_tile(bsz)
    return (area <= _MOSAIC_BLOCK_AREA_CAP
            and 3 * area * bl <= _VMEM_TABLE_BUDGET
            and bsz > n + 1)


def prefers_pallas(bsz: int, n: int, m: int) -> bool:
    """Shape-dispatch rule for ``SoftDTW(backend='auto')`` (measured on
    a v5e before PR 1, to be measured again by the benchmark): the
    kernel wins wherever the
    batch-on-lanes layout applies (3.5-26x, any batch size) or the whole
    padded batch runs as a single sublane-batch block (~3x).  Elsewhere —
    multi-block sublane grids re-running the diagonal loop per tile —
    one scan over the full batch wins."""
    return _use_lanes(bsz, n, m) or fits_one_block(bsz, n, m)


def _lanes_pad(x: jax.Array):
    bl = _lane_tile(x.shape[0])
    return _pad_batch(x, bl), bl


def _fwd_kernel_lanes(d_ref, r_ref, *, n: int, m: int, gamma: float,
                      bandwidth: int, bl: int):
    """d_ref: (N+M-1, N, bl); r_ref: (N+M+1, N+1, bl).  Same recurrence
    as _fwd_kernel with i on sublanes and batch on lanes."""
    n1 = n + 1
    i_buf = lax.broadcasted_iota(jnp.int32, (n1, bl), 0)

    r_ref[0] = jnp.where(i_buf == 0, 0.0, BIG)
    r_ref[1] = jnp.full((n1, bl), BIG, jnp.float32)

    inv_gamma = 1.0 / gamma

    def body(p, _):
        r_mm = r_ref[p - 2]                         # (N+1, bl)
        r_m = r_ref[p - 1]
        cost = d_ref[p - 2]                         # (N, bl)
        prev_diag = r_mm[:-1, :]                    # R[i-1, j-1]
        prev_up = r_m[:-1, :]                       # R[i-1, j]
        prev_left = r_m[1:, :]                      # R[i, j-1]
        n0 = -prev_diag * inv_gamma
        n1_ = -prev_up * inv_gamma
        n2 = -prev_left * inv_gamma
        mx = jnp.maximum(jnp.maximum(n0, n1_), n2)
        softmin = -gamma * (jnp.log(jnp.exp(n0 - mx) + jnp.exp(n1_ - mx)
                                    + jnp.exp(n2 - mx)) + mx)
        row = jnp.concatenate(
            [jnp.full((1, bl), BIG, jnp.float32), cost + softmin], axis=0)
        j_buf = p - i_buf
        valid = ((i_buf >= 1) & (j_buf >= 1) & (j_buf <= m))
        if bandwidth > 0:
            valid &= jnp.abs(i_buf - j_buf) <= bandwidth
        r_ref[p] = jnp.where(valid, row, BIG)
        return 0

    lax.fori_loop(2, n + m + 1, body, 0)


def _run_forward_lanes(d_skew: jax.Array, n: int, m: int, gamma: float,
                       bandwidth: int):
    """d_skew: (B, N+M-1, N) -> (value (B,), r_skew (B, N+M+1, N+1))."""
    bsz = d_skew.shape[0]
    d_pad, bl = _lanes_pad(d_skew)
    d3 = d_pad.transpose(1, 2, 0)                    # (S, N, B_pad)
    bp = d3.shape[2]
    kernel = functools.partial(_fwd_kernel_lanes, n=n, m=m, gamma=gamma,
                               bandwidth=bandwidth, bl=bl)
    r3 = pl.pallas_call(
        kernel,
        grid=(bp // bl,),
        in_specs=[pl.BlockSpec((n + m - 1, n, bl), lambda b: (0, 0, b))],
        out_specs=pl.BlockSpec((n + m + 1, n + 1, bl), lambda b: (0, 0, b)),
        out_shape=jax.ShapeDtypeStruct((n + m + 1, n + 1, bp), jnp.float32),
        interpret=pallas_mode.interpret(),
    )(d3)
    r_skew = r3.transpose(2, 0, 1)[:bsz]
    return r_skew[:, n + m, n], r_skew


def _bwd_kernel_lanes(r_ref, d_ref, e_ref, *, n: int, m: int, gamma: float,
                      bandwidth: int, bl: int):
    """Reverse wavefront, lanes layout: refs (N+M+3, N+2, bl)."""
    n2 = n + 2
    i_buf = lax.broadcasted_iota(jnp.int32, (n2, bl), 0)
    inv_gamma = 1.0 / gamma

    e_ref[...] = jnp.zeros((n + m + 3, n2, bl), jnp.float32)
    e_ref[n + m + 2] = (i_buf == n + 1).astype(jnp.float32)

    def shift_up(row):                              # row[i] -> row[i+1]
        return jnp.concatenate(
            [row[1:, :], jnp.zeros((1, bl), row.dtype)], axis=0)

    def body(k, _):
        q = n + m + 2 - k
        r_q = r_ref[q]                              # (N+2, bl)
        r_q1 = r_ref[q + 1]
        r_q2 = r_ref[q + 2]
        d_q1 = d_ref[q + 1]
        d_q2 = d_ref[q + 2]
        e_q1 = e_ref[q + 1]
        e_q2 = e_ref[q + 2]

        a = jnp.exp((shift_up(r_q1) - r_q - shift_up(d_q1)) * inv_gamma)
        b_ = jnp.exp((r_q1 - r_q - d_q1) * inv_gamma)
        c = jnp.exp((shift_up(r_q2) - r_q - shift_up(d_q2)) * inv_gamma)
        e_row = shift_up(e_q1) * a + e_q1 * b_ + shift_up(e_q2) * c

        j_buf = q - i_buf
        valid = ((i_buf >= 1) & (i_buf <= n) & (j_buf >= 1) & (j_buf <= m)
                 & (r_q > -BIG / 2))
        if bandwidth > 0:
            valid &= jnp.abs(i_buf - j_buf) <= bandwidth
        e_ref[q] = jnp.where(valid, e_row, 0.0)
        return 0

    lax.fori_loop(2, n + m + 1, body, 0)


def _run_backward_lanes(r_ext_skew: jax.Array, d_ext_skew: jax.Array,
                        n: int, m: int, gamma: float,
                        bandwidth: int) -> jax.Array:
    bsz = r_ext_skew.shape[0]
    r_pad, bl = _lanes_pad(r_ext_skew)
    d_pad, _ = _lanes_pad(d_ext_skew)
    r3 = r_pad.transpose(1, 2, 0)
    d3 = d_pad.transpose(1, 2, 0)
    bp = r3.shape[2]
    kernel = functools.partial(_bwd_kernel_lanes, n=n, m=m, gamma=gamma,
                               bandwidth=bandwidth, bl=bl)
    spec = pl.BlockSpec((n + m + 3, n + 2, bl), lambda b: (0, 0, b))
    out = pl.pallas_call(
        kernel,
        grid=(bp // bl,),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((n + m + 3, n + 2, bp), jnp.float32),
        interpret=pallas_mode.interpret(),
    )(r3, d3)
    return out.transpose(2, 0, 1)[:bsz]


def _run_forward_chunked(d_skew: jax.Array, n: int, m: int, gamma: float,
                         bandwidth: int, chunk: int | None = None):
    """d_skew: (B, N+M-1, N) -> (value (B,), r_skew (B, N+M+1, N+1))."""
    import math

    bsz = d_skew.shape[0]
    bt = 8
    if chunk is None:
        # chunk is the untiled leading dim, so a floor of 1 is legal; never
        # let the floor push the block past the VMEM budget at huge N
        chunk = max(1, min(512, _CHUNK_VMEM_ELEMS // (bt * (2 * n + 1))))
    n_diag = n + m - 1                    # diagonals 2..n+m
    n_chunks = math.ceil(n_diag / chunk)
    pad_p = n_chunks * chunk - n_diag
    d3 = jnp.pad(_pad_batch(d_skew, bt),
                 ((0, 0), (0, pad_p), (0, 0))).transpose(1, 0, 2)
    bp = d3.shape[1]
    kernel = functools.partial(_fwd_kernel_chunked, n=n, m=m, gamma=gamma,
                               bandwidth=bandwidth, chunk=chunk, bt=bt)
    r3 = pl.pallas_call(
        kernel,
        grid=(bp // bt, n_chunks),
        in_specs=[pl.BlockSpec((chunk, bt, n), lambda b, c: (c, b, 0))],
        out_specs=pl.BlockSpec((chunk, bt, n + 1), lambda b, c: (c, b, 0)),
        out_shape=jax.ShapeDtypeStruct((n_chunks * chunk, bp, n + 1),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((2, bt, n + 1), jnp.float32)],
        interpret=pallas_mode.interpret(),
    )(d3)
    r_body = r3.transpose(1, 0, 2)[:bsz, :n_diag]
    # re-attach the constant diagonals 0 and 1
    diag0 = jnp.where(jnp.arange(n + 1) == 0, 0.0, BIG)
    head = jnp.stack([diag0, jnp.full((n + 1,), BIG)], axis=0)
    head = jnp.broadcast_to(head[None], (bsz, 2, n + 1))
    r_skew = jnp.concatenate([head, r_body], axis=1)
    return r_skew[:, n + m, n], r_skew


def _softdtw_bwd_scan(r_ext: jax.Array, d_ext_skew: jax.Array, n: int,
                      m: int, gamma: float, bandwidth: int) -> jax.Array:
    """Any-length backward: the reverse-wavefront E recurrence as a
    lax.scan over diagonals (rows stream from HBM automatically).  Used
    when the whole table exceeds the Pallas kernel's VMEM budget."""
    bsz = r_ext.shape[0]
    n2 = n + 2
    i_buf = jnp.arange(n2)
    inv_gamma = 1.0 / gamma

    def shift_left(row):
        return jnp.concatenate(
            [row[:, 1:], jnp.zeros((bsz, 1), row.dtype)], axis=1)

    def step(carry, inputs):
        e_q1, e_q2 = carry                     # diagonals q+1, q+2
        r_q, r_q1, r_q2, d_q1, d_q2, q = inputs
        a = jnp.exp((shift_left(r_q1) - r_q - shift_left(d_q1)) * inv_gamma)
        b_ = jnp.exp((r_q1 - r_q - d_q1) * inv_gamma)
        c = jnp.exp((shift_left(r_q2) - r_q - shift_left(d_q2)) * inv_gamma)
        e_row = shift_left(e_q1) * a + e_q1 * b_ + shift_left(e_q2) * c
        j_buf = q - i_buf
        valid = ((i_buf >= 1) & (i_buf <= n) & (j_buf >= 1) & (j_buf <= m))
        valid = valid[None, :] & (r_q > -BIG / 2)
        if bandwidth > 0:
            valid &= (jnp.abs(i_buf - j_buf) <= bandwidth)[None, :]
        e_row = jnp.where(valid, e_row, 0.0)
        return (e_row, e_q1), e_row

    # iterate q = n+m down to 2; inputs pre-gathered per diagonal
    qs = jnp.arange(n + m, 1, -1)
    r_q = r_ext[:, qs, :]
    r_q1 = r_ext[:, qs + 1, :]
    r_q2 = r_ext[:, qs + 2, :]
    d_q1 = d_ext_skew[:, qs + 1, :]
    d_q2 = d_ext_skew[:, qs + 2, :]
    swap = lambda x: x.transpose(1, 0, 2)
    e_init_q2 = jnp.zeros((bsz, n2), jnp.float32).at[:, n + 1].set(1.0)
    e_init_q1 = jnp.zeros((bsz, n2), jnp.float32)
    (_, _), e_rows = lax.scan(
        step, (e_init_q1, e_init_q2),
        (swap(r_q), swap(r_q1), swap(r_q2), swap(d_q1), swap(d_q2), qs))
    # e_rows[k] = diagonal q = n+m-k; build skewed E table rows 0..n+m+2
    e_skew = jnp.zeros((bsz, n + m + 3, n2), jnp.float32)
    e_skew = e_skew.at[:, qs, :].set(swap(e_rows))
    return e_skew


def _bwd_kernel_chunked(r_ref, d_ref, e_ref, carry, *, n: int, m: int,
                        gamma: float, bandwidth: int, chunk: int, bt: int,
                        n_chunks: int):
    """Streaming backward: grid (B/bt, n_chunks), the E-recurrence's
    mirror of ``_fwd_kernel_chunked``.  The chunk axis index_map REVERSES
    block order (the wavefront runs high diagonal -> low), and six carry
    rows — E, R, D at diagonals q+1 and q+2 — thread across chunk
    boundaries in VMEM scratch, so no block ever reads a neighbor
    diagonal from another block.  The sequence-length ceiling is HBM,
    like the forward; the reference's backward simply stops at 1024
    (soft_dtw_cuda.py:79-112, 318-320).

    Diagonal q lives at array row q; rows above n+m+2 are zero padding
    whose E is masked to 0 (their q fails the j<=m validity test) and
    whose r/d values only ever neighbor the overridden/masked top rows.
    The q = n+m+2 corner seed (E=1 at i=N+1, soft_dtw_cuda.py:166-167)
    is applied as a where-override, which keeps the loop body uniform
    across real, seed, and padding rows."""
    n2 = n + 2
    c = pl.program_id(1)
    i_buf = lax.broadcasted_iota(jnp.int32, (bt, n2), 1)
    inv_gamma = 1.0 / gamma

    @pl.when(c == 0)
    def _init():
        carry[...] = jnp.zeros((6, bt, n2), jnp.float32)

    def shift_left(row):                            # row[i] -> row[i+1]
        return jnp.concatenate(
            [row[:, 1:], jnp.zeros((bt, 1), row.dtype)], axis=1)

    def body(s, _):
        t = chunk - 1 - s                           # top row of the block first
        q = (n_chunks - 1 - c) * chunk + t          # diagonal index
        e_q1, e_q2 = carry[0], carry[1]
        r_q1, r_q2 = carry[2], carry[3]
        d_q1, d_q2 = carry[4], carry[5]
        r_q = r_ref[t]
        d_q = d_ref[t]

        a = jnp.exp((shift_left(r_q1) - r_q - shift_left(d_q1)) * inv_gamma)
        b_ = jnp.exp((r_q1 - r_q - d_q1) * inv_gamma)
        c_ = jnp.exp((shift_left(r_q2) - r_q - shift_left(d_q2)) * inv_gamma)
        e_row = shift_left(e_q1) * a + e_q1 * b_ + shift_left(e_q2) * c_

        j_buf = q - i_buf
        valid = ((i_buf >= 1) & (i_buf <= n) & (j_buf >= 1) & (j_buf <= m)
                 & (r_q > -BIG / 2))                # unreached cells -> 0
        if bandwidth > 0:
            valid &= jnp.abs(i_buf - j_buf) <= bandwidth
        e_row = jnp.where(valid, e_row, 0.0)
        e_row = jnp.where(q == n + m + 2,           # corner seed E[N+1,M+1]=1
                          (i_buf == n + 1).astype(jnp.float32), e_row)
        e_ref[t] = e_row
        carry[1] = e_q1                             # next step's q+2
        carry[0] = e_row                            # next step's q+1
        carry[3] = r_q1
        carry[2] = r_q
        carry[5] = d_q1
        carry[4] = d_q
        return 0

    lax.fori_loop(0, chunk, body, 0)


def _run_backward_chunked(r_ext_skew: jax.Array, d_ext_skew: jax.Array,
                          n: int, m: int, gamma: float, bandwidth: int,
                          chunk: int | None = None) -> jax.Array:
    """(B, N+M+3, N+2) extended skewed R and D -> skewed E table, any
    length: diagonals stream from HBM in chunks, highest first."""
    import math

    bsz = r_ext_skew.shape[0]
    bt = 8
    n2 = n + 2
    if chunk is None:
        # three streams (r, d, e) share the block budget; floor 1 is legal
        chunk = max(1, min(512, _CHUNK_VMEM_ELEMS // (bt * 3 * n2)))
    n_rows = n + m + 3
    n_chunks = math.ceil(n_rows / chunk)
    pad_p = n_chunks * chunk - n_rows
    r3 = jnp.pad(_pad_batch(r_ext_skew, bt),
                 ((0, 0), (0, pad_p), (0, 0))).transpose(1, 0, 2)
    d3 = jnp.pad(_pad_batch(d_ext_skew, bt),
                 ((0, 0), (0, pad_p), (0, 0))).transpose(1, 0, 2)
    bp = r3.shape[1]
    kernel = functools.partial(_bwd_kernel_chunked, n=n, m=m, gamma=gamma,
                               bandwidth=bandwidth, chunk=chunk, bt=bt,
                               n_chunks=n_chunks)
    spec = pl.BlockSpec((chunk, bt, n2), lambda b, c: (n_chunks - 1 - c, b, 0))
    out = pl.pallas_call(
        kernel,
        grid=(bp // bt, n_chunks),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((n_chunks * chunk, bp, n2),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((6, bt, n2), jnp.float32)],
        interpret=pallas_mode.interpret(),
    )(r3, d3)
    return out.transpose(1, 0, 2)[:bsz, :n_rows]


# --------------------------------------------------------------- backward
def _bwd_kernel(r_ref, d_ref, e_ref, *, n: int, m: int, gamma: float,
                bandwidth: int, bt: int):
    """Reverse wavefront over padded-extended coords i in [0,N+1],
    j in [0,M+1] (diag q = i+j in [0, N+M+2]), skewed diagonal-leading
    layout, a tile of ``bt`` pairs per block (see _fwd_kernel on why).
    r_ref/d_ref/e_ref: (N+M+3, bt, N+2)."""
    n2 = n + 2
    i_buf = lax.broadcasted_iota(jnp.int32, (bt, n2), 1)
    inv_gamma = 1.0 / gamma

    e_ref[...] = jnp.zeros((n + m + 3, bt, n2), jnp.float32)
    # E[N+1, M+1] = 1 (corner seed, soft_dtw_cuda.py:166-167)
    corner = (i_buf == n + 1).astype(jnp.float32)
    e_ref[n + m + 2] = corner

    def shift_left(row):                            # row[i] -> row[i+1]
        return jnp.concatenate(
            [row[:, 1:], jnp.zeros((bt, 1), row.dtype)], axis=1)

    def body(k, _):
        q = n + m + 2 - k
        r_q = r_ref[q]                              # R[i, q-i]: (bt, N+2)
        r_q1 = r_ref[q + 1]                         # diag q+1
        r_q2 = r_ref[q + 2]                         # diag q+2
        d_q1 = d_ref[q + 1]
        d_q2 = d_ref[q + 2]
        e_q1 = e_ref[q + 1]
        e_q2 = e_ref[q + 2]

        r_up = shift_left(r_q1)                     # R[i+1, j]
        r_left = r_q1                               # R[i, j+1]
        r_diag = shift_left(r_q2)                   # R[i+1, j+1]
        d_up = shift_left(d_q1)                     # D_[i+1, j]
        d_left = d_q1                               # D_[i, j+1]
        d_diag = shift_left(d_q2)                   # D_[i+1, j+1]
        e_up = shift_left(e_q1)
        e_left = e_q1
        e_diag = shift_left(e_q2)

        a = jnp.exp((r_up - r_q - d_up) * inv_gamma)
        b_ = jnp.exp((r_left - r_q - d_left) * inv_gamma)
        c = jnp.exp((r_diag - r_q - d_diag) * inv_gamma)
        e_row = e_up * a + e_left * b_ + e_diag * c

        j_buf = q - i_buf
        valid = ((i_buf >= 1) & (i_buf <= n) & (j_buf >= 1) & (j_buf <= m)
                 & (r_q > -BIG / 2))                # unreached cells -> 0
        if bandwidth > 0:
            valid &= jnp.abs(i_buf - j_buf) <= bandwidth
        e_ref[q] = jnp.where(valid, e_row, 0.0)
        return 0

    # Start at q = n+m (k=2): diagonal n+m+1 holds no valid cell (j would
    # exceed M), and skipping it keeps every q+2 read in bounds.
    lax.fori_loop(2, n + m + 1, body, 0)


def _run_backward(r_ext_skew: jax.Array, d_ext_skew: jax.Array, n: int,
                  m: int, gamma: float, bandwidth: int) -> jax.Array:
    bsz = r_ext_skew.shape[0]
    bt = _tile_for_batch(bsz, n, m)
    r3 = _pad_batch(r_ext_skew, bt).transpose(1, 0, 2)
    d3 = _pad_batch(d_ext_skew, bt).transpose(1, 0, 2)
    bp = r3.shape[1]
    kernel = functools.partial(_bwd_kernel, n=n, m=m, gamma=gamma,
                               bandwidth=bandwidth, bt=bt)
    spec = pl.BlockSpec((n + m + 3, bt, n + 2), lambda b: (0, b, 0))
    out = pl.pallas_call(
        kernel,
        grid=(bp // bt,),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((n + m + 3, bp, n + 2), jnp.float32),
        interpret=pallas_mode.interpret(),
    )(r3, d3)
    return out.transpose(1, 0, 2)[:bsz]


# ----------------------------------------------------------- custom VJP
@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def softdtw_pallas(D: jax.Array, gamma: float = 1.0,
                   bandwidth: int = 0) -> jax.Array:
    """Batched soft-DTW of cost matrices D (B, N, M) -> (B,)."""
    value, _ = _softdtw_pallas_fwd(D, gamma, bandwidth)
    return value


def _softdtw_pallas_fwd(D, gamma, bandwidth):
    bsz, n, m = D.shape
    check_bandwidth(n, m, int(bandwidth))
    d_skew = skew_cost(D.astype(jnp.float32))
    if _use_lanes(bsz, n, m):
        value, r_skew = _run_forward_lanes(d_skew, n, m, float(gamma),
                                           int(bandwidth))
    elif _table_fits_vmem(n, m):
        value, r_skew = _run_forward(d_skew, n, m, float(gamma),
                                     int(bandwidth))
    else:
        # long-sequence path: stream diagonals in chunks
        value, r_skew = _run_forward_chunked(d_skew, n, m, float(gamma),
                                             int(bandwidth))
    return value, (D, r_skew)


def _softdtw_pallas_bwd(gamma, bandwidth, residuals, grad_out):
    D, r_skew = residuals
    bsz, n, m = D.shape
    # Extended R in skewed layout: pad with BIG (-> treated as unreached),
    # then seed the (N+1, M+1) corner with R[N, M] (soft_dtw_cuda.py:162-164).
    r_ext = jnp.pad(r_skew, ((0, 0), (0, 2), (0, 1)), constant_values=BIG)
    r_ext = jnp.where(r_ext >= BIG / 2, -BIG, r_ext)
    r_ext = r_ext.at[:, n + m + 2, n + 1].set(r_skew[:, n + m, n])
    # Padded costs D_[i, j] (zeros border), skewed to match.
    d_ext = jnp.pad(D.astype(jnp.float32), ((0, 0), (1, 1), (1, 1)))
    d_ext_skew = skew_cost(d_ext)                   # (B, N+M+3, N+2)
    if _use_lanes(bsz, n, m):
        e_skew = _run_backward_lanes(r_ext, d_ext_skew, n, m, float(gamma),
                                     int(bandwidth))
    elif _table_fits_vmem(n, m):
        e_skew = _run_backward(r_ext, d_ext_skew, n, m, float(gamma),
                               int(bandwidth))
    elif os.environ.get("MILNCE_SDTW_BWD_SCAN") == "1":
        # debugging escape hatch / cross-implementation golden
        e_skew = _softdtw_bwd_scan(r_ext, d_ext_skew, n, m, float(gamma),
                                   int(bandwidth))
    else:
        # long-sequence path: stream diagonals from HBM, highest first
        e_skew = _run_backward_chunked(r_ext, d_ext_skew, n, m,
                                       float(gamma), int(bandwidth))
    # grad_D[i, j] = g * E[i+1, j+1]  (skewed: diag i+j+2, idx i+1)
    i_idx = jnp.arange(n)[:, None]
    j_idx = jnp.arange(m)[None, :]
    e_full = e_skew[:, i_idx + j_idx + 2, i_idx + 1]
    return (grad_out[:, None, None] * e_full.astype(D.dtype),)


softdtw_pallas.defvjp(_softdtw_pallas_fwd, _softdtw_pallas_bwd)
