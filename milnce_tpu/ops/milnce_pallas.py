"""Chunked MIL-NCE logsumexp as a fused Pallas TPU kernel.

The pure-jax stream (losses/milnce_chunked.py ``_stream_lse_scan``)
already removes the O(B_local * Bg * K) similarity cubes, but each scan
step still round-trips its chunk logits block through XLA-managed HBM
temporaries.  This kernel fuses the whole step — chunk matmul (MXU) +
online max/rescale + accumulate (VPU) — in VMEM:

- grid ``(n_chunks,)``: Pallas streams the ``(chunk, D)`` /
  ``(chunk*K, D)`` negative blocks from HBM (double-buffered by the
  pipeline) while the local ``(B, D)`` / ``(B*K, D)`` blocks and the
  four accumulator blocks stay VMEM-resident across the grid via
  constant-index BlockSpecs (``@pl.when(c == 0)`` initializes them);
- accumulators are ``(rows, 128)`` blocks with all lanes equal — a
  per-row scalar broadcast over the lane dim, so every read/write is a
  full (8, 128)-tileable block (the softdtw_pallas lowering lesson:
  never make Mosaic slice a 1-wide lane);
- the backward is its OWN kernel behind ``jax.custom_vjp``
  (the soft-DTW wiring): it recomputes each chunk's logits, forms the
  softmax weights ``exp(x - lse) * g`` and emits the local grads as
  accumulated blocks plus the gathered-negative grads as per-chunk
  output blocks — nothing O(Bg * K) beyond the embeddings themselves;
- padding rows (batch to sublane multiples, Bg to whole chunks) are
  masked to ``-BIG`` logits / zero weights, the same finite-sentinel
  discipline as ops/softdtw.py.

On the CPU the kernel runs in Pallas interpret mode (ops/pallas_mode.py),
so the same code path is unit-testable there (tests/test_milnce_chunked.py
pins value+grad parity against the scan stream and the dense loss).
``prefers_pallas`` is the ``backend='auto'`` shape-dispatch rule — a
pure function of static shapes, pinned no-recompile by the
``milnce_chunked_dispatch`` trace-invariant entry.  TPU timings:
BENCH_MILNCE_LOSS.md (CPU numbers committed; the chip crossover is
predicted from the VMEM-residency rule, not yet measured — same status
the im2col stem had before its chip session).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from milnce_tpu.ops import pallas_mode
from milnce_tpu.ops.softdtw import BIG

_LANES = 128

# f32 elements the per-step VMEM resident set may use: local blocks +
# double-buffered chunk blocks + logits temporaries + accumulators.
# Same budget scale the soft-DTW kernels verified against a real v5e
# scoped-vmem OOM (ops/softdtw_pallas.py _VMEM_TABLE_BUDGET).
_VMEM_F32_BUDGET = 1_200_000


def _pad8(n: int) -> int:
    return -(-n // 8) * 8


def _pad_rows(x: jax.Array, rows: int) -> jax.Array:
    pad = rows - x.shape[0]
    return x if pad == 0 else jnp.pad(x, ((0, pad), (0, 0)))


def prefers_pallas(b: int, b_global: int, k: int, d: int,
                   chunk: int) -> bool:
    """``backend='auto'`` rule: the fused kernel wherever its blocks are
    lane-aligned (D a multiple of 128 — the MXU contraction dim) and the
    per-step resident set fits the VMEM budget; the scan otherwise.
    Conservative by construction: CPU interpret-mode parity is pinned in
    tests, the TPU win is predicted from VMEM residency (one fused
    pipeline vs per-chunk HBM temporaries) pending a chip session —
    BENCH_MILNCE_LOSS.md records which."""
    if chunk % 8 and chunk != b_global:
        # a sublane-misaligned EXPLICIT chunk (the default rule always
        # aligns) would hand Mosaic (chunk, D) blocks off the (8, 128)
        # tile grid — legal in interpret mode only; route it to the scan
        # (single-chunk streams are exempt: the block equals the array)
        return False
    return (d % _LANES == 0
            and _resident_f32(b, k, d, chunk) <= _VMEM_F32_BUDGET)


def _resident_f32(b: int, k: int, d: int, chunk: int) -> int:
    """f32 elements the BACKWARD kernel keeps in VMEM per grid step —
    the larger of the two resident sets (it holds recomputed logits AND
    weight blocks, the gv/gt grad accumulators, and the per-chunk
    gva/gta output blocks the forward doesn't have); a rule that only
    modeled the forward would compile the forward and VMEM-OOM mid-step
    in the backward on a real chip."""
    bp, bkp = _pad8(b), _pad8(b * k)
    ck = chunk * k
    return (2 * (bp + bkp) * d          # v/t blocks + gv/gt accums
            + 2 * (bp + bkp) * _LANES   # lse + cotangent blocks
            + 4 * (chunk + ck) * d      # chunk in + grad out blocks,
                                        # double-buffered
            + 2 * (bp * ck + bkp * chunk))  # logits + weight temps


def _check_compiled_preconditions(b: int, k: int, d: int, chunk: int,
                                  bg: int) -> None:
    """Compiled-kernel preconditions, checked at trace time so an
    explicit ``loss.milnce_backend=pallas`` outside them fails naming
    the knob and the shape instead of as an opaque Mosaic lowering error
    or the compiler's ``RESOURCE_EXHAUSTED ... vmem`` from deep inside
    the step compile (``backend='auto'`` never selects such a shape).
    Interpret mode has no tile grid and no VMEM and legitimately accepts
    anything — the parity tests' odd shapes stay runnable on the CPU."""
    if pallas_mode.interpret():
        return
    if chunk % 8 and chunk != bg:
        raise ValueError(
            f"loss.milnce_chunk={chunk} is not sublane-aligned for the "
            "compiled Pallas kernel (chunk blocks need 8-row-aligned "
            "sublanes; trailing dims Mosaic pads itself): use a "
            f"multiple of 8, a chunk >= the gathered batch ({bg}), or "
            "backend='scan'")
    resident = _resident_f32(b, k, d, chunk)
    if resident > _VMEM_F32_BUDGET:
        raise ValueError(
            f"loss.milnce_backend=pallas at b_local={b}, b_global={bg}, "
            f"K={k}, D={d}, loss.milnce_chunk={chunk} keeps {resident} "
            f"f32 elements in VMEM per grid step, over the kernel's "
            f"budget of {_VMEM_F32_BUDGET}: lower loss.milnce_chunk, or "
            "use loss.milnce_backend=auto (which takes the scan here)")


def _row_scalar(ref):
    """Per-row scalar out of an all-lanes-equal (rows, 128) accumulator
    block: a full-block read + lane-max (max of equal values), never a
    1-wide lane slice."""
    return jnp.max(ref[...], axis=1, keepdims=True)


def _store_scalar(ref, col, rows):
    ref[...] = jnp.broadcast_to(col, (rows, _LANES))


# ---------------------------------------------------------------- forward
def _fwd_kernel(v_ref, t_ref, va_ref, ta_ref, rm_ref, rs_ref, cm_ref,
                cs_ref, *, bg, k, chunk, bp, bkp):
    """One negative chunk: fused matmul + online max/rescale/accumulate.
    rm/rs (rows) and cm/cs (cols) are the running (max, rescaled-sum)
    logsumexp accumulators, resident across the grid."""
    c = pl.program_id(0)

    @pl.when(c == 0)
    def _init():
        _store_scalar(rm_ref, jnp.full((bp, 1), -BIG, jnp.float32), bp)
        _store_scalar(rs_ref, jnp.zeros((bp, 1), jnp.float32), bp)
        _store_scalar(cm_ref, jnp.full((bkp, 1), -BIG, jnp.float32), bkp)
        _store_scalar(cs_ref, jnp.zeros((bkp, 1), jnp.float32), bkp)

    ck = chunk * k
    # chunk blocks arrive in the INPUT dtype (upcasting the gathered
    # arrays host-side would materialize O(Bg*D) f32 copies) and promote
    # to f32 here, in VMEM, one block at a time
    ta = ta_ref[...].astype(jnp.float32)
    va = va_ref[...].astype(jnp.float32)
    # rows: local videos vs this chunk's candidate texts -> (bp, ck)
    x = lax.dot_general(v_ref[...], ta, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    col = c * ck + lax.broadcasted_iota(jnp.int32, (bp, ck), 1)
    x = jnp.where(col < bg * k, x, -BIG)
    m_old, s_old = _row_scalar(rm_ref), _row_scalar(rs_ref)
    m_new = jnp.maximum(m_old, jnp.max(x, axis=1, keepdims=True))
    s_new = (s_old * jnp.exp(m_old - m_new)
             + jnp.sum(jnp.exp(x - m_new), axis=1, keepdims=True))
    _store_scalar(rm_ref, m_new, bp)
    _store_scalar(rs_ref, s_new, bp)

    # cols: local candidate texts vs this chunk's videos -> (bkp, chunk)
    y = lax.dot_general(t_ref[...], va, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    row = c * chunk + lax.broadcasted_iota(jnp.int32, (bkp, chunk), 1)
    y = jnp.where(row < bg, y, -BIG)
    m_old, s_old = _row_scalar(cm_ref), _row_scalar(cs_ref)
    m_new = jnp.maximum(m_old, jnp.max(y, axis=1, keepdims=True))
    s_new = (s_old * jnp.exp(m_old - m_new)
             + jnp.sum(jnp.exp(y - m_new), axis=1, keepdims=True))
    _store_scalar(cm_ref, m_new, bkp)
    _store_scalar(cs_ref, s_new, bkp)


def _run_forward(v, t, v_all, t_all, chunk, bg, k):
    b, d = v.shape
    bk = t.shape[0]
    bp, bkp = _pad8(b), _pad8(bk)
    _check_compiled_preconditions(b, k, d, chunk, bg)
    nc = -(-bg // chunk)
    f32 = jnp.float32
    vp = _pad_rows(v.astype(f32), bp)
    tp = _pad_rows(t.astype(f32), bkp)
    vap = _pad_rows(v_all, nc * chunk)          # input dtype: the kernel
    tap = _pad_rows(t_all, nc * chunk * k)      # upcasts per block
    kernel = functools.partial(_fwd_kernel, bg=bg, k=k, chunk=chunk,
                               bp=bp, bkp=bkp)
    const = lambda shape: pl.BlockSpec(shape, lambda c: (0, 0))  # noqa: E731
    rm, rs, cm, cs = pl.pallas_call(
        kernel,
        grid=(nc,),
        in_specs=[const((bp, d)), const((bkp, d)),
                  pl.BlockSpec((chunk, d), lambda c: (c, 0)),
                  pl.BlockSpec((chunk * k, d), lambda c: (c, 0))],
        out_specs=[const((bp, _LANES)), const((bp, _LANES)),
                   const((bkp, _LANES)), const((bkp, _LANES))],
        out_shape=[jax.ShapeDtypeStruct((bp, _LANES), f32),
                   jax.ShapeDtypeStruct((bp, _LANES), f32),
                   jax.ShapeDtypeStruct((bkp, _LANES), f32),
                   jax.ShapeDtypeStruct((bkp, _LANES), f32)],
        interpret=pallas_mode.interpret(),
    )(vp, tp, vap, tap)
    row_lse = rm[:b, 0] + jnp.log(rs[:b, 0])
    col_lse = cm[:bk, 0] + jnp.log(cs[:bk, 0])
    return row_lse, col_lse


# --------------------------------------------------------------- backward
def _bwd_kernel(v_ref, t_ref, va_ref, ta_ref, rls_ref, grow_ref, cls_ref,
                gcol_ref, gv_ref, gt_ref, gva_ref, gta_ref, *, bg, k,
                chunk, bp, bkp):
    """Recompute this chunk's logits, weight by exp(x - lse) * g, and
    emit grads: gv/gt accumulate across the grid (constant-index
    blocks), gva/gta are this chunk's output blocks."""
    c = pl.program_id(0)

    @pl.when(c == 0)
    def _init():
        gv_ref[...] = jnp.zeros_like(gv_ref)
        gt_ref[...] = jnp.zeros_like(gt_ref)

    ck = chunk * k
    v, t = v_ref[...], t_ref[...]
    ta = ta_ref[...].astype(jnp.float32)
    va = va_ref[...].astype(jnp.float32)
    x = lax.dot_general(v, ta, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    col = c * ck + lax.broadcasted_iota(jnp.int32, (bp, ck), 1)
    w = (jnp.where(col < bg * k, jnp.exp(x - _row_scalar(rls_ref)), 0.0)
         * _row_scalar(grow_ref))                        # (bp, ck)
    gv_ref[...] += jnp.dot(w, ta, preferred_element_type=jnp.float32)
    gta_ref[...] = lax.dot_general(w, v, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32
                                   ).astype(gta_ref.dtype)

    y = lax.dot_general(t, va, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    row = c * chunk + lax.broadcasted_iota(jnp.int32, (bkp, chunk), 1)
    u = (jnp.where(row < bg, jnp.exp(y - _row_scalar(cls_ref)), 0.0)
         * _row_scalar(gcol_ref))                        # (bkp, chunk)
    gt_ref[...] += jnp.dot(u, va, preferred_element_type=jnp.float32)
    gva_ref[...] = lax.dot_general(u, t, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32
                                   ).astype(gva_ref.dtype)


def _bcast_rows(a, rows):
    """(n,) -> all-lanes-equal (rows, 128) f32 block, zero-padded: the
    pad rows pair a zero lse with a zero cotangent, so their weights are
    exactly 0 (exp(0) * 0) with no overflow risk."""
    col = jnp.zeros((rows,), jnp.float32).at[:a.shape[0]].set(
        a.astype(jnp.float32))
    return jnp.broadcast_to(col[:, None], (rows, _LANES))


def _run_backward(v, t, v_all, t_all, row_lse, col_lse, g_row, g_col,
                  chunk, bg, k):
    b, d = v.shape
    bk = t.shape[0]
    bp, bkp = _pad8(b), _pad8(bk)
    _check_compiled_preconditions(b, k, d, chunk, bg)
    nc = -(-bg // chunk)
    f32 = jnp.float32
    vp = _pad_rows(v.astype(f32), bp)
    tp = _pad_rows(t.astype(f32), bkp)
    vap = _pad_rows(v_all, nc * chunk)          # input dtype: the kernel
    tap = _pad_rows(t_all, nc * chunk * k)      # upcasts per block
    kernel = functools.partial(_bwd_kernel, bg=bg, k=k, chunk=chunk,
                               bp=bp, bkp=bkp)
    const = lambda shape: pl.BlockSpec(shape, lambda c: (0, 0))  # noqa: E731
    g_v, g_t, g_va, g_ta = pl.pallas_call(
        kernel,
        grid=(nc,),
        in_specs=[const((bp, d)), const((bkp, d)),
                  pl.BlockSpec((chunk, d), lambda c: (c, 0)),
                  pl.BlockSpec((chunk * k, d), lambda c: (c, 0)),
                  const((bp, _LANES)), const((bp, _LANES)),
                  const((bkp, _LANES)), const((bkp, _LANES))],
        out_specs=[const((bp, d)), const((bkp, d)),
                   pl.BlockSpec((chunk, d), lambda c: (c, 0)),
                   pl.BlockSpec((chunk * k, d), lambda c: (c, 0))],
        out_shape=[jax.ShapeDtypeStruct((bp, d), f32),
                   jax.ShapeDtypeStruct((bkp, d), f32),
                   jax.ShapeDtypeStruct((nc * chunk, d), v_all.dtype),
                   jax.ShapeDtypeStruct((nc * chunk * k, d), t_all.dtype)],
        interpret=pallas_mode.interpret(),
    )(vp, tp, vap, tap,
      _bcast_rows(row_lse, bp), _bcast_rows(g_row, bp),
      _bcast_rows(col_lse, bkp), _bcast_rows(g_col, bkp))
    return (g_v[:b], g_t[:bk], g_va[:bg], g_ta[:bg * k])


# ----------------------------------------------------------- custom VJP
@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def milnce_stream_pallas(v, t, v_all, t_all, chunk):
    """(row_lse (B,), col_lse_flat (B*K,)) of the MIL-NCE similarity
    cube, fused in VMEM — the kernel twin of
    ``losses.milnce_chunked._stream_lse_scan`` (identical contract)."""
    out, _ = _milnce_stream_fwd(v, t, v_all, t_all, chunk)
    return out


def _milnce_stream_fwd(v, t, v_all, t_all, chunk):
    b = v.shape[0]
    k = t.shape[0] // b
    bg = v_all.shape[0]
    row_lse, col_lse = _run_forward(v, t, v_all, t_all, chunk, bg, k)
    return (row_lse, col_lse), (v, t, v_all, t_all, row_lse, col_lse)


def _milnce_stream_bwd(chunk, res, cots):
    v, t, v_all, t_all, row_lse, col_lse = res
    g_row, g_col = cots
    b = v.shape[0]
    k = t.shape[0] // b
    bg = v_all.shape[0]
    g_v, g_t, g_va, g_ta = _run_backward(v, t, v_all, t_all, row_lse,
                                         col_lse, g_row, g_col, chunk,
                                         bg, k)
    return (g_v.astype(v.dtype), g_t.astype(t.dtype),
            g_va.astype(v_all.dtype), g_ta.astype(t_all.dtype))


milnce_stream_pallas.defvjp(_milnce_stream_fwd, _milnce_stream_bwd)
