"""MIL-NCE loss with mesh-wide negatives.

Semantics of the reference (loss.py:6-18 + the AllGather wrapping at
main_distributed.py:234-236, utils.py:8-24), re-designed as a *pure,
mesh-aware function*:

- similarity cube ``x[i, j, k] = v_i . t_{j,k}`` over the GLOBAL batch
  (B video rows, B*K candidate text rows);
- numerator_i   = logsumexp_k x[i, i, k]          (positive candidate bag);
- denominator_i = logsumexp over row i AND column i of the cube (both
  retrieval directions — the reference's ``cat((x, x^T), dim=1)``), which
  counts the positives twice, exactly as the reference does;
- loss = mean_i (denominator_i - numerator_i).

Distributed form: instead of materializing the (Bg, Bg*K) matrix on every
chip after an NCCL all-gather, each shard gathers embeddings over the mesh
axis (one XLA collective over ICI) but scores only its LOCAL rows and
columns — per-chip memory O(B_local * B_global * K) — then psum-reduces.
This is mathematically identical to the reference's replicated loss.

Memory bound at the baseline scale (Bg=8192, K=5, 64 chips -> B_local=128):
two (B_local, Bg, K) f32 cubes = 2 x 128*8192*5*4 B ~ 42 MB per chip
(the replicated reference form would need ~1.3 GB per GPU for x plus its
transpose concat, loss.py:16).  The denominator combines two separate
logsumexp reductions with logaddexp, so no (B, 2*Bg*K) concat is ever
materialized; tests/test_milnce.py pins the compiled per-chip temp size
at Bg=8192.

The cubes are NOT free, though — an earlier revision of this docstring
called the gather+local-score form "already HBM-trivial", which the
PR 8 static planner disproved once AD residuals are counted: reverse
mode saves both cubes (and their softmax intermediates) for the
backward, so the loss side really holds ~4 cubes plus the lse-transpose
scatter.  Measured by the GL013 memplan pins (analysis/memplan.py, the
``milnce_loss_dense`` / ``milnce_loss_chunked`` entries at B_local=64,
Bg=512, K=5, D=16): this dense form peaks at 2,863,940 B/chip with the
(B_local, Bg*K) cube ops as the named top contributors, vs 703,276
B/chip for the chunked stream — O(B_local * Bg * K) vs
O(B_local * chunk), a gap that grows linearly in Bg/chunk.  At the
Bg=8192 what-if (``mem_plan --what-if --batch 8192 --mesh data=64``),
the loss side (gathered-text transpose + cube matmul) becomes the
step's top per-chip contributor as soon as the video/text towers stop
dominating (grad-accum recipe, low-res curriculum stages, larger K) —
dense 1.046 GiB/chip vs chunked 0.791 GiB/chip at the 8f@64 K=32 point
(BENCH_MILNCE_LOSS.md has the full table).

When the cubes matter, use ``losses/milnce_chunked.py``
(``loss.milnce_impl = chunked | auto``): identical semantics and
collective structure, with the cube streamed through running
logsumexp accumulators and recomputed chunk-by-chunk in the backward
(scan form, plus a fused Pallas kernel in ops/milnce_pallas.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def psum_local_grad(x: jax.Array, axis_name) -> jax.Array:
    """``lax.psum`` whose reverse-mode gradient is the LOCAL term.

    The loss is differentiated INSIDE the ``shard_map`` body and the
    train step then psums the parameter grads, so the reduction's
    cotangent must pass through unscaled.  A plain ``lax.psum`` does
    that only when the body is traced with ``check_vma=True``; under
    ``check_vma=False`` (what both train steps pass) it transposes to
    another psum and every gradient comes out axis-size times too
    large.  The explicit VJP is the same under both settings
    (tests/test_milnce.py pins each on the 8-device mesh)."""
    return lax.psum(x, axis_name)


def _psum_local_grad_fwd(x, axis_name):
    return lax.psum(x, axis_name), None


def _psum_local_grad_bwd(axis_name, _, g):
    # under check_vma=True the cotangent of the replicated sum is typed
    # unvarying; the local term it feeds is varying over the axis
    names = axis_name if isinstance(axis_name, (tuple, list)) else (axis_name,)
    missing = tuple(n for n in names if n not in jax.typeof(g).vma)
    return (lax.pcast(g, missing, to="varying") if missing else g,)


psum_local_grad.defvjp(_psum_local_grad_fwd, _psum_local_grad_bwd)


def milnce_loss(video_embd: jax.Array, text_embd: jax.Array,
                axis_name: Optional[str] = None) -> jax.Array:
    """MIL-NCE loss.

    Args:
      video_embd: (B, D) local video embeddings.
      text_embd: (B*K, D) local candidate text embeddings, sample-major
        (sample 0's K candidates first, like the flattened (B, K, W) batch).
      axis_name: mesh axis to gather negatives over; None = single shard.

    Returns: scalar loss (identical on every shard when distributed).
    """
    b = video_embd.shape[0]
    assert text_embd.shape[0] % b == 0, (video_embd.shape, text_embd.shape)

    if axis_name is None:
        v_all, t_all = video_embd, text_embd
        offset = 0
        b_global = b
    else:
        v_all = lax.all_gather(video_embd, axis_name, axis=0, tiled=True)
        t_all = lax.all_gather(text_embd, axis_name, axis=0, tiled=True)
        offset = lax.axis_index(axis_name) * b
        b_global = v_all.shape[0]

    # Local rows of the cube: (B, Bg, K)
    rows = jnp.matmul(video_embd, t_all.T).reshape(b, b_global, -1)
    # Local columns of the cube: cols[j, i, k] = x[j, offset+i, k] -> (Bg, B, K)
    cols = jnp.matmul(v_all, text_embd.T).reshape(b_global, b, -1)

    diag = rows[jnp.arange(b), offset + jnp.arange(b), :]          # (B, K)
    numerator = jax.nn.logsumexp(diag, axis=1)
    # lse over row i AND column i of the cube.  Two separate reductions
    # combined with logaddexp == lse of the concatenation (the reference's
    # ``cat((x, x^T), dim=1)``), without materializing a (B, 2*Bg*K) copy —
    # peak per-chip logits memory stays at the two (B_local, Bg, K) cubes.
    denominator = jnp.logaddexp(
        jax.nn.logsumexp(rows.reshape(b, -1), axis=1),
        jax.nn.logsumexp(jnp.swapaxes(cols, 0, 1).reshape(b, -1), axis=1))

    local_sum = jnp.sum(denominator - numerator)
    if axis_name is not None:
        # Value: the mesh-global sum.  Gradient: the LOCAL term only,
        # which agrees with the unsharded reference once the train step
        # psums the param grads
        # (tests/test_milnce.py::test_sharded_gradients_match_unsharded).
        local_sum = psum_local_grad(local_sum, axis_name)
    return local_sum / b_global
