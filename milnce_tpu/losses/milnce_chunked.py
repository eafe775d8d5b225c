"""Memory-efficient MIL-NCE: chunked streaming loss (never materialize
the global similarity cube).

``milnce_loss`` (losses/milnce.py) scores each shard's local rows and
columns of the global similarity cube as two dense ``(B_local, Bg, K)``
logits cubes.  At the baseline operating point (Bg=8192, K=5) the cubes
plus their AD-saved twins are the dominant *loss-side* term the PR 8
static planner attributes to the step — and they are pure intermediates:
the loss only ever needs per-row logsumexps of them.

This module computes those logsumexps **without the cubes** — the
memory-efficient-contrastive / FlashAttention move applied to MIL-NCE:

- the gathered negatives ``(Bg, D)`` / ``(Bg*K, D)`` are split into
  chunks of ``chunk`` global samples; a ``lax.scan`` streams the chunks,
  keeping only running ``(B_local,)`` / ``(B_local*K,)`` online-softmax
  accumulators (max + rescaled sum, numerically identical to one global
  logsumexp up to summation order);
- a ``jax.custom_vjp`` recomputes each chunk's logits in the backward
  (softmax weights from the saved row logsumexps), so AD saves only the
  gathered embeddings — which are live anyway — and nothing
  O(B_local * Bg * K);
- semantics are IDENTICAL to ``milnce_loss``: positive-bag logsumexp
  numerator, row+column denominator with double-counted positives, the
  same 2 ``all_gather`` collectives (whose AD transposes stay the same 2
  reduce_scatters), and the same ``psum_local_grad`` reduction.

Backend gate (the soft-DTW playbook, ops/softdtw.py ``SoftDTW``):
``backend='scan'`` is this module's pure-jax stream; ``'pallas'`` is the
fused TPU kernel (ops/milnce_pallas.py — chunk matmul + max/rescale +
accumulate in VMEM, its own custom VJP); ``'auto'`` picks per shape via
``milnce_pallas.prefers_pallas`` (trace-stable: the rule is a pure
function of static shapes, pinned no-recompile by the
``milnce_chunked_dispatch`` trace-invariant entry).  Impl selection
(dense cube vs this stream) rides config: ``loss.milnce_impl``,
``loss.milnce_chunk``, ``loss.milnce_backend`` -> :func:`build_milnce_loss`
-> every train step (plain / guarded / grad-cache / 2-D FSDP).

Measured peaks and chunk-size guidance: PERF.md "Memory-efficient loss",
BENCH_MILNCE_LOSS.md; per-chip pins: the ``milnce_loss_dense`` /
``milnce_loss_chunked`` GL013 memplan entries (analysis/memplan.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from milnce_tpu.losses.milnce import milnce_loss, psum_local_grad
from milnce_tpu.ops.softdtw import BIG

MILNCE_IMPLS = ("dense", "chunked", "auto")
MILNCE_BACKENDS = ("auto", "scan", "pallas")

# impl='auto' switches to the stream once the dense cubes STOP being
# cheap: two (B_local, Bg, K) f32 cubes plus their AD-saved twins beyond
# this budget.  64 MiB keeps dense (fewer matmul passes — the stream's
# backward recompute costs ~2 extra chunk matmuls) for every small-mesh
# run while the Bg=8192 recipe (4 cubes ~ 84 MiB at B_local=128, K=5)
# goes chunked.
DENSE_CUBE_BUDGET_BYTES = 64 * 2 ** 20

# chunk=0 targets this many row-logits elements per streamed block
# (B_local * chunk * K f32 ~ 2 MiB): big enough that the chunk matmul is
# MXU-shaped, small enough that a block is VMEM-resident for the fused
# kernel.
_CHUNK_TARGET_ELEMS = 512 * 1024


def milnce_default_chunk(b_local: int, k: int, b_global: int) -> int:
    """The chunk=0 rule: global samples per streamed block, sublane-
    aligned (multiple of 8) and never larger than the gathered batch."""
    if b_global <= 8:
        return b_global
    c = max(8, min(b_global, _CHUNK_TARGET_ELEMS // max(1, b_local * k)))
    return max(8, c // 8 * 8)


def prefers_chunked(b_local: int, b_global: int, k: int) -> bool:
    """impl='auto' shape rule: stream once the dense cubes + AD twins
    exceed :data:`DENSE_CUBE_BUDGET_BYTES`."""
    return 4 * b_local * b_global * k * 4 > DENSE_CUBE_BUDGET_BYTES


def _axis_prod(axis_name) -> int:
    """Static mesh extent of ``axis_name`` (None = 1, tuple = product) —
    legal inside the shard_map body, where mesh axis sizes are static."""
    if axis_name is None:
        return 1
    names = axis_name if isinstance(axis_name, (tuple, list)) else (axis_name,)
    n = 1
    for name in names:
        n *= int(lax.axis_size(name))
    return n


def _chunked_negatives(v_all: jax.Array, t_all: jax.Array, k: int,
                       chunk: int):
    """The scan stream's chunk layout, shared by forward AND backward
    (one copy — the two passes must agree on it or gradients silently
    skew): zero-pad the gathered negatives up to a whole number of
    chunks (the uneven-last-chunk case) and reshape into per-chunk
    blocks with their start offsets.  Padding columns are masked to
    ``-BIG`` in every logits block, so they contribute exp(-BIG - m) = 0
    to the running sums and exactly 0 to every chunk-recomputed
    gradient.  Stays in the INPUT dtype — upcasting the gathered arrays
    here would materialize O(Bg*D) f32 copies, exactly the class of
    buffer this loss exists to avoid; each block promotes to f32 inside
    its matmul instead."""
    bg, d = v_all.shape
    nc = -(-bg // chunk)
    pad = nc * chunk - bg
    if pad:
        v_all = jnp.pad(v_all, ((0, pad), (0, 0)))
        t_all = jnp.pad(t_all, ((0, pad * k), (0, 0)))
    return (v_all.reshape(nc, chunk, d), t_all.reshape(nc, chunk * k, d),
            jnp.arange(nc, dtype=jnp.int32) * chunk, nc)


# --------------------------------------------------------------- scan path
def _scan_forward(v, t, v_all, t_all, chunk):
    """Streaming forward: (row_lse (B,), col_lse_flat (B*K,)), f32.

    Accumulators are the online-softmax pair (running max m, rescaled sum
    s): one new chunk of logits x updates ``m' = max(m, max x)``,
    ``s' = s * exp(m - m') + sum exp(x - m')`` — associative, so the
    result equals the one-shot logsumexp up to summation order."""
    b, d = v.shape
    bk = t.shape[0]
    k = bk // b
    bg = v_all.shape[0]
    f32 = jnp.float32
    vf, tf = v.astype(f32), t.astype(f32)
    v_ch, t_ch, starts, _nc = _chunked_negatives(v_all, t_all, k, chunk)

    def body(carry, xs):
        rm, rs, cm, cs = carry
        v_c, t_c, start = xs
        # rows: local videos vs this chunk's candidate texts
        x = jnp.matmul(vf, t_c.T.astype(f32))            # (B, chunk*K)
        ok = (start * k + jnp.arange(chunk * k)) < bg * k
        x = jnp.where(ok[None, :], x, -BIG)
        m = jnp.maximum(rm, jnp.max(x, axis=1))
        rs = rs * jnp.exp(rm - m) + jnp.sum(jnp.exp(x - m[:, None]), axis=1)
        rm = m
        # cols: local candidate texts vs this chunk's videos
        y = jnp.matmul(tf, v_c.T.astype(f32))            # (B*K, chunk)
        ok = (start + jnp.arange(chunk)) < bg
        y = jnp.where(ok[None, :], y, -BIG)
        m = jnp.maximum(cm, jnp.max(y, axis=1))
        cs = cs * jnp.exp(cm - m) + jnp.sum(jnp.exp(y - m[:, None]), axis=1)
        cm = m
        return (rm, rs, cm, cs), None

    init = (jnp.full((b,), -jnp.inf, f32), jnp.zeros((b,), dtype=f32),
            jnp.full((bk,), -jnp.inf, f32), jnp.zeros((bk,), dtype=f32))
    (rm, rs, cm, cs), _ = lax.scan(body, init, (v_ch, t_ch, starts))
    return rm + jnp.log(rs), cm + jnp.log(cs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _stream_lse_scan(v, t, v_all, t_all, chunk):
    """(row_lse (B,), col_lse_flat (B*K,)): logsumexp of each local row /
    column of the similarity cube, streamed over negative chunks."""
    out, _ = _stream_lse_scan_fwd(v, t, v_all, t_all, chunk)
    return out


def _stream_lse_scan_fwd(v, t, v_all, t_all, chunk):
    row_lse, col_lse = _scan_forward(v, t, v_all, t_all, chunk)
    # residuals: embeddings (live anyway) + the (B,)/(B*K,) logsumexps —
    # NOTHING sized O(Bg) beyond the inputs themselves
    return (row_lse, col_lse), (v, t, v_all, t_all, row_lse, col_lse)


def _stream_lse_scan_bwd(chunk, res, cots):
    """Recompute each chunk's logits; softmax weights w = exp(x - lse)
    turn the lse cotangents into embedding grads, chunk by chunk."""
    v, t, v_all, t_all, row_lse, col_lse = res
    g_row, g_col = cots
    b, d = v.shape
    bk = t.shape[0]
    k = bk // b
    bg = v_all.shape[0]
    f32 = jnp.float32
    vf, tf = v.astype(f32), t.astype(f32)
    gr = g_row.astype(f32)[:, None]
    gc = g_col.astype(f32)[:, None]
    rls = row_lse[:, None]
    cls = col_lse[:, None]
    v_ch, t_ch, starts, nc = _chunked_negatives(v_all, t_all, k, chunk)

    def body(carry, xs):
        g_v, g_t = carry
        v_c, t_c, start = xs
        t_cf = t_c.astype(f32)
        v_cf = v_c.astype(f32)
        x = jnp.matmul(vf, t_cf.T)                       # (B, chunk*K)
        ok = (start * k + jnp.arange(chunk * k)) < bg * k
        w = jnp.where(ok[None, :], jnp.exp(x - rls), 0.0) * gr
        g_v = g_v + jnp.matmul(w, t_cf)
        g_tc = jnp.matmul(w.T, vf)                       # (chunk*K, D)
        y = jnp.matmul(tf, v_cf.T)                       # (B*K, chunk)
        ok = (start + jnp.arange(chunk)) < bg
        u = jnp.where(ok[None, :], jnp.exp(y - cls), 0.0) * gc
        g_t = g_t + jnp.matmul(u, v_cf)
        g_vc = jnp.matmul(u.T, tf)                       # (chunk, D)
        # per-chunk downcast: the stacked grads land in the input dtype,
        # never as an O(Bg*K*D) f32 twin
        return (g_v, g_t), (g_vc.astype(v_all.dtype),
                            g_tc.astype(t_all.dtype))

    init = (jnp.zeros((b, d), dtype=f32), jnp.zeros((bk, d), dtype=f32))
    (g_v, g_t), (g_va_ch, g_ta_ch) = lax.scan(
        body, init, (v_ch, t_ch, starts))
    g_va = g_va_ch.reshape(nc * chunk, d)[:bg]
    g_ta = g_ta_ch.reshape(nc * chunk * k, d)[:bg * k]
    return (g_v.astype(v.dtype), g_t.astype(t.dtype), g_va, g_ta)


_stream_lse_scan.defvjp(_stream_lse_scan_fwd, _stream_lse_scan_bwd)


# ------------------------------------------------------------- public loss
def milnce_loss_chunked(video_embd: jax.Array, text_embd: jax.Array,
                        axis_name=None, chunk: int = 0,
                        backend: str = "auto") -> jax.Array:
    """MIL-NCE loss, identical semantics to :func:`milnce_loss`, with
    the similarity cube streamed instead of materialized.

    Args:
      video_embd: (B, D) local video embeddings.
      text_embd: (B*K, D) local candidate text embeddings, sample-major.
      axis_name: mesh axis (or axis tuple) to gather negatives over;
        None = single shard.
      chunk: global samples per streamed block (0 = the
        :func:`milnce_default_chunk` rule).  Bg % chunk != 0 is handled
        by a masked pad chunk.
      backend: 'scan' | 'pallas' | 'auto' (shape rule:
        ops/milnce_pallas.prefers_pallas).

    Returns: scalar loss (identical on every shard when distributed).
    """
    b, d = video_embd.shape
    bk = text_embd.shape[0]
    assert bk % b == 0, (video_embd.shape, text_embd.shape)
    k = bk // b
    if backend not in MILNCE_BACKENDS:
        raise ValueError(f"unknown milnce backend {backend!r} (expected "
                         f"one of {', '.join(MILNCE_BACKENDS)})")

    if axis_name is None:
        v_all, t_all = video_embd, text_embd
    else:
        v_all = lax.all_gather(video_embd, axis_name, axis=0, tiled=True)
        t_all = lax.all_gather(text_embd, axis_name, axis=0, tiled=True)
    b_global = v_all.shape[0]

    if chunk <= 0:
        chunk = milnce_default_chunk(b, k, b_global)
    chunk = min(int(chunk), b_global)
    if backend == "auto":
        from milnce_tpu.ops.milnce_pallas import prefers_pallas

        backend = "pallas" if prefers_pallas(b, b_global, k, d,
                                             chunk) else "scan"
    if backend == "pallas":
        from milnce_tpu.ops.milnce_pallas import milnce_stream_pallas

        row_lse, col_flat = milnce_stream_pallas(video_embd, text_embd,
                                                 v_all, t_all, chunk)
    else:
        row_lse, col_flat = _stream_lse_scan(video_embd, text_embd,
                                             v_all, t_all, chunk)

    # positive bag: diag[i, k] = v_i . t_{i,k} — local by construction
    # (the dense path reads the same values out of its rows cube at the
    # shard offset; the all_gather transpose routes that cotangent back
    # to the local shard, so taking it directly is gradient-identical)
    diag = jnp.einsum("bd,bkd->bk", video_embd,
                      text_embd.reshape(b, k, d)).astype(jnp.float32)
    numerator = jax.nn.logsumexp(diag, axis=1)
    # column denominator half: lse over (Bg, K) = lse over K of the
    # per-(i,k) streamed lse
    col_lse = jax.nn.logsumexp(col_flat.reshape(b, k), axis=1)
    denominator = jnp.logaddexp(row_lse, col_lse)
    local_sum = jnp.sum(denominator - numerator)
    if axis_name is not None:
        local_sum = psum_local_grad(local_sum, axis_name)
    return local_sum / b_global


def build_milnce_loss(loss_cfg):
    """LossConfig -> ``fn(video_embd, text_embd, axis_name)``.

    The train-step factories (train/step.py) call this ONCE at build
    time: ``milnce_impl='dense'`` (and loss_cfg=None) keeps the traced
    program byte-identical to the pre-chunked step — its pinned
    collective counts and memory plans never move — while 'chunked' /
    'auto' route through :func:`milnce_loss_chunked`.  Bad knob values
    fail here, at build time, not after a full model trace."""
    impl = getattr(loss_cfg, "milnce_impl", "dense") or "dense"
    chunk = int(getattr(loss_cfg, "milnce_chunk", 0) or 0)
    backend = getattr(loss_cfg, "milnce_backend", "auto") or "auto"
    if impl not in MILNCE_IMPLS:
        raise ValueError(f"unknown loss.milnce_impl {impl!r} (expected "
                         f"one of {', '.join(MILNCE_IMPLS)})")
    if backend not in MILNCE_BACKENDS:
        raise ValueError(f"unknown loss.milnce_backend {backend!r} "
                         f"(expected one of {', '.join(MILNCE_BACKENDS)})")

    def loss_fn(video_embd, text_embd, axis_name: Optional[str] = None):
        use = impl
        if use == "auto":
            b = video_embd.shape[0]
            k = text_embd.shape[0] // b
            use = ("chunked" if prefers_chunked(b, b * _axis_prod(axis_name),
                                                k) else "dense")
        if use == "dense":
            return milnce_loss(video_embd, text_embd, axis_name=axis_name)
        return milnce_loss_chunked(video_embd, text_embd,
                                   axis_name=axis_name, chunk=chunk,
                                   backend=backend)

    return loss_fn
