"""Kimi Delta Attention's rule (the gated delta rule with a decay per
channel), computed in chunks by one Pallas kernel.

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
one product; inside a sub-block the factor is taken pair by pair, one
diagonal ``j = i - d`` at a time (``C x K`` a diagonal, never ``C x C x
K``).  No exponent this module takes is positive: a decay of e^-1000 inside
a chunk gives zeros, not inf or NaN.

The kernel: the grid walks (row, block of heads, chunk), the chunks of a
(row, head) in order, and the K x V state stays in VMEM scratch from the
first chunk to the last; a grid step reads its chunk's q, k, v, g and beta
tiles and writes its output tile once.  ``G``, ``A``, ``P``, the decays and
the solve live in VMEM.  The unit-lower system is solved through the
inverse of ``I + A``, built by doubling: the inverse ``T`` of the diagonal
blocks of size ``s`` gives that of size ``2s`` as ``T - T A_s T``, with
``A_s`` the entries of ``A`` that join the two halves of a ``2s`` block
(the block form of forward substitution; no power series).

Decays, the state, the solve and every product here are float32
(``Precision.HIGHEST``); q, k, v may come in bfloat16.  A sequence that is
no multiple of the chunk is padded with ``g = 0``, ``beta = 0`` and zero
vectors: the state passes through them unchanged.  Right padding of a row
needs no mask: no real position reads a later one.  Forward only: nothing
takes a gradient through it, and asking for one raises.  On the CPU the
kernel runs in Pallas interpret mode (ops/pallas_mode.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from milnce_tpu.ops import pallas_mode

SCOPE = "text_hybrid/kda_scan"
SUB = 16                # positions a sub-block (the secondary chunking)
HIGHEST = lax.Precision.HIGHEST
HEADS_A_STEP = 4        # heads a grid step, where they divide the heads


def kda_chunked(q, k, v, g, beta, *, chunk: int, scale: float):
    """q, k (R, S, H, K) unit rows; v (R, S, H, V); g (R, S, H, K)
    float32, at most 0 (the log of the decay); beta (R, S, H) -> o (R, S,
    H, V) float32.  ``chunk`` a multiple of :data:`SUB` (or below it)."""
    if chunk % min(SUB, chunk):
        raise ValueError(f"kda_chunked: chunk {chunk} is no multiple of "
                         f"the sub-block {SUB}")
    s = q.shape[1]
    pad = -s % chunk
    with jax.named_scope(SCOPE):
        if pad:
            q, k, v, g, beta = (
                jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                for t in (q, k, v, g, beta))
        return _rule(q, k, v, g, beta, chunk, float(scale))[:, :s]


@functools.partial(jax.custom_jvp, nondiff_argnums=(5, 6))
def _rule(q, k, v, g, beta, chunk, scale):
    """The kernel over sequences padded to a multiple of ``chunk``."""
    rows, s, heads, dk = q.shape
    dv = v.shape[-1]
    sub = min(SUB, chunk)
    n = s // chunk
    step = max(d for d in range(1, HEADS_A_STEP + 1) if heads % d == 0)

    def flat(t):
        """(R, S, H, W) -> (R, S, H W): a head's tile is a block of W
        lanes."""
        return t.reshape(rows, s, heads * t.shape[-1])

    def tile(width):
        return pl.BlockSpec((1, chunk, step * width),
                            lambda r, h, c: (r, c, h))

    out = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, sub=sub, scale=scale,
                          step=step, dk=dk, dv=dv),
        out_shape=jax.ShapeDtypeStruct((rows, s, heads * dv),
                                       jnp.float32),
        grid=(rows, heads // step, n),
        in_specs=[tile(dk), tile(dk), tile(dv), tile(dk),
                  pl.BlockSpec((1, chunk, heads), lambda r, h, c: (r, c, 0))],
        out_specs=tile(dv),
        scratch_shapes=[pltpu.VMEM((step, dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=rows * heads * n * _chunk_flops(chunk, dk, dv, sub),
            transcendentals=rows * heads * n * chunk * dk
            * (sub + 2 * (chunk // sub) + 1),
            bytes_accessed=sum(t.size * t.dtype.itemsize
                               for t in (q, k, v, g, beta))
            + rows * s * heads * dv * 4),
        name="kda_chunked",
        interpret=pallas_mode.interpret(),
    )(flat(q), flat(k), flat(v), flat(g.astype(jnp.float32)),
      beta.astype(jnp.float32))
    return out.reshape(rows, s, heads, dv)


@_rule.defjvp
def _forward_only(chunk, scale, primals, tangents):
    raise NotImplementedError(
        "kda_chunked is forward only: no gradient is defined through the "
        "delta rule's kernel")


def _chunk_flops(c: int, dk: int, dv: int, sub: int) -> int:
    """The kernel's products over one chunk of one head, 2 FLOPs a
    multiply-add: the sub-blocks' cross terms of A and P, the inverse by
    doubling, [W | U~], the two products with the state, P U and the new
    state."""
    doublings = max(c - 1, 0).bit_length() - 1
    return 2 * ((c // sub - 1) * 2 * c * c * dk + doublings * 2 * c ** 3
                + c * c * (dk + dv) + 2 * c * dk * dv + c * c * dv
                + c * dk * dv)


def _mm(a, b, contract=((1,), (0,))):
    return lax.dot_general(a, b, (contract, ((), ())), precision=HIGHEST,
                           preferred_element_type=jnp.float32)


def _kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, state, *, chunk, sub,
            scale, step, dk, dv):
    """One chunk of ``step`` heads of one row; ``state`` holds each head's
    S^T (V x K) from the chunk before."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros(state.shape, jnp.float32)

    row = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    pos = lax.broadcasted_iota(jnp.int32, (chunk, dk), 0)
    # the sub-block of a position and its place in it, without a division
    block = sum((pos >= first).astype(jnp.int32)
                for first in range(sub, chunk, sub))
    at = pos - sub * block
    lane = lax.broadcasted_iota(jnp.int32, b_ref.shape[1:], 1)
    head0 = pl.program_id(1) * step
    for j in range(step):
        q = q_ref[0, :, j * dk:(j + 1) * dk].astype(jnp.float32)
        k = k_ref[0, :, j * dk:(j + 1) * dk].astype(jnp.float32)
        v = v_ref[0, :, j * dv:(j + 1) * dv].astype(jnp.float32)
        beta = jnp.sum(jnp.where(lane == head0 + j, b_ref[0], 0.0), axis=1,
                       keepdims=True)                            # (C, 1)
        G = _cumsum(g_ref[0, :, j * dk:(j + 1) * dk], pos, chunk)  # (C, K)
        a, p = _pairs(G, k, q, row, col, block, at, chunk, sub)
        t = _unit_lower_inverse(a * beta, row, col, chunk)
        decayed = jnp.exp(G)
        wu = _mm(t, beta * jnp.concatenate([k * decayed, v], axis=1))
        w, u = wu[:, :dk], wu[:, dk:]
        s_t = state[j]                                           # (V, K)
        by_state = _mm(jnp.concatenate([w, q * decayed], axis=0), s_t,
                       ((1,), (1,)))                             # (2C, V)
        u = u - by_state[:chunk]
        o = by_state[chunk:] + _mm(p, u)
        o_ref[0, :, j * dv:(j + 1) * dv] = scale * o
        whole = G[chunk - 1:chunk, :]                            # (1, K)
        state[j] = (s_t * jnp.exp(whole)
                    + _mm(u, k * jnp.exp(whole - G), ((0,), (0,))))


def _cumsum(g, pos, chunk: int):
    """Inclusive sum over the chunk's positions (rows), by doubling."""
    d = 1
    while d < chunk:
        g = g + jnp.where(pos >= d, pltpu.roll(g, d, 0), 0.0)
        d *= 2
    return g


def _pairs(G, k, q, row, col, block, at, chunk: int, sub: int):
    """-> (A without beta, P) (C, C) of one chunk: ``A`` strictly lower,
    ``P`` lower with its diagonal (module docstring)."""
    a = jnp.zeros((chunk, chunk), jnp.float32)
    p = jnp.zeros((chunk, chunk), jnp.float32)
    # inside a sub-block, pair by pair: the diagonal j = i - d at once,
    # exp(G_i - G_j) of the pairs that share a sub-block
    for d in range(sub):
        kd = k if d == 0 else pltpu.roll(k, d, 0)
        gd = G if d == 0 else pltpu.roll(G, d, 0)
        e = kd * jnp.exp(jnp.where(at >= d, G - gd, -jnp.inf))
        on = col == row - d
        p = jnp.where(on, jnp.sum(q * e, axis=1, keepdims=True), p)
        if d:
            a = jnp.where(on, jnp.sum(k * e, axis=1, keepdims=True), a)
    # the earlier sub-blocks, through the log-decay just before this one:
    # both factors at most 1
    for b in range(1, chunk // sub):
        ref = G[b * sub - 1:b * sub, :]
        left = jnp.exp(jnp.where(block == b, G - ref, -jnp.inf))
        right = k * jnp.exp(jnp.where(block < b, ref - G, -jnp.inf))
        both = _mm(jnp.concatenate([k * left, q * left], axis=0), right,
                   ((1,), (1,)))
        a = a + both[:chunk]
        p = p + both[chunk:]
    return a, p


def _unit_lower_inverse(a, row, col, chunk: int):
    """(I + a)^-1 for ``a`` strictly lower (C, C), by doubling the size of
    the diagonal blocks inverted: blocks of ``s`` (aligned, the last one
    cut) to blocks of ``2s`` by ``T - T a_s T``, ``a_s`` the entries of
    ``a`` joining the two halves of a block of ``2s``."""
    apart = row ^ col            # a pair shares an aligned block of n iff < n
    t = jnp.where(row == col, 1.0, jnp.where(apart < 2, -a, 0.0))
    s = 2
    while s < chunk:
        joins = jnp.where((apart >= s) & (apart < 2 * s), a, 0.0)
        t = t - _mm(t, _mm(joins, t))
        s *= 2
    return t
