"""The plain reference of a text query: the sentence tower of
``s3dg_milnce`` and a brute-force scan, float32 at ``highest`` precision,
block by block over the corpus so that it fits beside nothing.  It takes
the benchmark's weights and the benchmark's corpus, both made again from
the seed; nothing the program made.

``precision='float8'`` is the control: the tower's and the scan's matmul
inputs rounded to ``float8_e4m3fn``, one step below the bfloat16 the
configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.reference.s3dg_milnce import _round, text_embedding


@functools.partial(jax.jit, static_argnames=("k", "precision"))
def _scan_block(q, block, served_local, *, k, precision):
    """q (S, D) float32, block (R, D) -> the block's best k (scores,
    local rows) and the scores of ``served_local`` (S, k) rows (local
    numbers; out of range = not in this block)."""
    scores = jnp.matmul(_round(q, precision),
                        _round(block.astype(jnp.float32), precision).T,
                        precision=lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)
    top_s, top_i = lax.top_k(scores, k)
    rows = block.shape[0]
    inside = (served_local >= 0) & (served_local < rows)
    at = jnp.take_along_axis(scores, jnp.clip(served_local, 0, rows - 1),
                             axis=1)
    return top_s, top_i, jnp.where(inside, at, -jnp.inf)


def query_embeddings(w: dict, token_rows, precision: str = "float32"):
    with jax.default_matmul_precision("highest"):
        return text_embedding(w, jnp.asarray(token_rows, jnp.int32),
                              precision=precision)


def scan(q, blocks, served_idx, k: int, precision: str = "float32") -> dict:
    """``blocks``: iterable of (first row, block array on the device).
    -> {"top_scores" (S, k), "top_idx" (S, k), "at_served" (S, k)}: the
    scan's own best k, best first, and its score of every served row."""
    served_idx = np.asarray(served_idx, np.int64)
    s = served_idx.shape[0]
    top_s = np.full((s, 0), -np.inf, np.float32)
    top_i = np.zeros((s, 0), np.int64)
    at = np.full(served_idx.shape, -np.inf, np.float32)
    for first, block in blocks:
        local = jnp.asarray(served_idx - first, jnp.int32)
        with jax.default_matmul_precision("highest"):
            bs, bi, bat = jax.device_get(_scan_block(
                q, block, local, k=k, precision=precision))
        at = np.maximum(at, bat)
        top_s = np.concatenate([top_s, bs], axis=1)
        top_i = np.concatenate([top_i, bi.astype(np.int64) + first], axis=1)
        order = np.argsort(-top_s, axis=1, kind="stable")[:, :k]
        top_s = np.take_along_axis(top_s, order, axis=1)
        top_i = np.take_along_axis(top_i, order, axis=1)
    return {"top_scores": top_s, "top_idx": top_i, "at_served": at}
