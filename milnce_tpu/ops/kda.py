"""Kimi Delta Attention's rule (the gated delta rule with a decay per
channel), computed in chunks.

Per row and head, with ``q_t``, ``k_t`` in R^K (unit length), ``v_t`` in
R^V, a decay ``alpha_t = exp(g_t)`` in (0, 1]^K, ``beta_t`` in (0, 2) and a
state ``S`` in R^(K x V), ``S_0 = 0``:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = scale S_t^T q_t

The chunked form (Kimi Linear, arXiv:2510.26692; DeltaNet's WY / UT
transform with the decay folded in) does a chunk of ``C`` positions as
matrix products and carries only the state.  With ``G_i = sum_{t<=i} g_t``
inside the chunk (float32 logs, never rising) and ``S`` the state entering
it:

    A[i, j] = beta_i sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])     j < i
    P[i, j] = sum_c q_i[c] k_j[c] exp(G_i[c] - G_j[c])            j <= i
    (I + A) [W | U~] = diag(beta) [K o exp(G) | V]
    U = U~ - W S
    O = scale ((Q o exp(G)) S + P U)
    S' = exp(G_C) o S + (K o exp(G_C - G))^T U

A decay per channel makes ``A`` and ``P`` sums over ``c`` of a C x C x K
product, and ``exp(G_i) exp(-G_j)`` overflows as soon as a chunk's decay
passes e^-88.  So the chunk is cut into sub-blocks of ``SUB`` positions
(secondary chunking): for ``i`` in sub-block ``b`` and ``j`` in an earlier
one, ``exp(G_i - G_j) = exp(G_i - R_b) exp(R_b - G_j)`` with ``R_b`` the
log-decay just before ``b`` starts, both factors at most 1, and the sum is
one product; inside a sub-block the factor is taken pair by pair
(``sub x sub x K`` a sub-block, never ``C x C x K``).  No exponent this
module takes is positive: a decay of e^-1000 inside a chunk gives zeros,
not inf or NaN.

Decays, the state, the triangular solve and every product here are float32
(``Precision.HIGHEST``); q, k, v may come in bfloat16.  The chunks run in a
``lax.scan``, so a chunk's temporaries are all that is held at once.  A
sequence that is no multiple of the chunk is padded inside with ``g = 0``,
``beta = 0`` and zero vectors: the state passes through them unchanged.
Right padding of a row needs no mask: no real position reads a later one.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

SCOPE = "text_hybrid/kda_scan"
SUB = 16                # positions a sub-block (the secondary chunking)
HIGHEST = lax.Precision.HIGHEST


def kda_chunked(q, k, v, g, beta, *, chunk: int, scale: float):
    """q, k (R, S, H, K) unit rows; v (R, S, H, V); g (R, S, H, K)
    float32, at most 0 (the log of the decay); beta (R, S, H) -> o (R, S,
    H, V) float32.  ``chunk`` a multiple of :data:`SUB` (or below it)."""
    with jax.named_scope(SCOPE):
        return _kda(q, k, v, g, beta, chunk, scale, min(SUB, chunk))


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _pairs(G, kc, qc, chunk: int, sub: int):
    """-> (A without beta, P) (R, H, C, C) of one chunk: ``A`` strictly
    lower, ``P`` lower with its diagonal (module docstring)."""
    at = jnp.arange(sub)
    incl = at[:, None] >= at[None, :]
    strict = (at[:, None] > at[None, :]).astype(jnp.float32)
    a_rows, p_rows = [], []
    for first in range(0, chunk, sub):
        last = first + sub
        gb, kb, qb = G[..., first:last, :], kc[..., first:last, :], \
            qc[..., first:last, :]
        # inside the sub-block, pair by pair: exp(G_i - G_j), j <= i
        e = jnp.exp(jnp.where(incl[:, :, None],
                              gb[..., :, None, :] - gb[..., None, :, :],
                              -jnp.inf))
        a_in = jnp.sum(kb[..., :, None, :] * kb[..., None, :, :] * e,
                       axis=-1) * strict
        p_in = jnp.sum(qb[..., :, None, :] * kb[..., None, :, :] * e,
                       axis=-1)
        a_parts, p_parts = [a_in], [p_in]
        if first:
            # the earlier sub-blocks, through the log-decay just before
            # this one: both factors at most 1
            ref = G[..., first - 1:first, :]
            left = jnp.exp(gb - ref)
            right = kc[..., :first, :] * jnp.exp(ref - G[..., :first, :])
            a_parts.insert(0, _mm("...ik,...jk->...ij", kb * left, right))
            p_parts.insert(0, _mm("...ik,...jk->...ij", qb * left, right))
        if last < chunk:
            zeros = jnp.zeros(a_in.shape[:-1] + (chunk - last,),
                              jnp.float32)
            a_parts.append(zeros)
            p_parts.append(zeros)
        a_rows.append(jnp.concatenate(a_parts, axis=-1))
        p_rows.append(jnp.concatenate(p_parts, axis=-1))
    return jnp.concatenate(a_rows, axis=-2), jnp.concatenate(p_rows, axis=-2)


def _kda(q, k, v, g, beta, chunk: int, scale: float, sub: int):
    rows, s, heads, dk = q.shape
    dv = v.shape[-1]
    if chunk % sub:
        raise ValueError(f"kda_chunked: chunk {chunk} is no multiple of "
                         f"the sub-block {sub}")
    pad = -s % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta))
    n = (s + pad) // chunk

    def chunks(t):
        """(R, S, H, ...) -> (N, R, H, C, ...)."""
        t = t.reshape((rows, n, chunk) + t.shape[2:])
        return jnp.moveaxis(t, (1, 3), (0, 2))

    def step(state, xs):
        qc, kc, vc, gc, bc = (t.astype(jnp.float32) for t in xs)
        G = jnp.cumsum(gc, axis=-2)                         # (R, H, C, K)
        a, p = _pairs(G, kc, qc, chunk, sub)
        a = a * bc[..., :, None]
        decayed = jnp.exp(G)
        rhs = bc[..., None] * jnp.concatenate([kc * decayed, vc], axis=-1)
        solved = lax.linalg.triangular_solve(
            a, rhs, left_side=True, lower=True, unit_diagonal=True)
        w, u = solved[..., :dk], solved[..., dk:]
        u = u - _mm("...ck,...kv->...cv", w, state)
        out = scale * (_mm("...ck,...kv->...cv", qc * decayed, state)
                       + _mm("...ij,...jv->...iv", p, u))
        whole = G[..., -1:, :]                              # (R, H, 1, K)
        state = (jnp.exp(whole)[..., 0, :, None] * state
                 + _mm("...ck,...cv->...kv", kc * jnp.exp(whole - G), u))
        return state, out

    zero = jnp.zeros((rows, heads, dk, dv), jnp.float32)
    _, out = lax.scan(step, zero, tuple(chunks(t) for t in (q, k, v, g,
                                                             beta)))
    out = jnp.moveaxis(out, (0, 2), (1, 3)).reshape(rows, s + pad, heads, dv)
    return out[:, :s]
