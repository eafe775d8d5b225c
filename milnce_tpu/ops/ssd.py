"""Mamba-2's state-space scan (SSD), computed in chunks.

Per row and head, with ``x_t`` in R^P, ``B_t``, ``C_t`` in R^N (shared by
all heads: one group), a state ``S`` in R^(P x N), ``S_0 = 0``:

    a_t = exp(dt_t A)          S_t = a_t S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D x_t

The recurrence is sequential in ``t``; the chunked form (Dao & Gu 2024,
"Transformers are SSMs", section 6) does the work of a chunk of ``chunk``
positions as matrix products and carries only the state from chunk to
chunk:

- inside a chunk ``y = (L o (C B^T)) (dt x)`` with ``L_ts = prod_{s<r<=t}
  a_r`` for ``s <= t`` and 0 above the diagonal (the decay-masked
  quadratic form; one ``chunk x chunk`` product a head);
- each chunk's own contribution to the state at its end, ``sum_s
  (prod_{s<r<=last} a_r) dt_s x_s B_s^T``;
- the short recurrence over chunks: the state ENTERING chunk ``k`` is the
  state entering ``k - 1`` decayed over that chunk plus its contribution;
- the entering state's part of the output, ``(prod_{r<=t} a_r) S_in C_t``.

``dt``, the decays and the carried state are float32; the products run in
``x``'s type (bfloat16 when served so) and are summed in float32.  A
sequence that is no multiple of the chunk is padded inside with ``dt = 0``
positions (a decay of 1 and no input: the state passes through them).

A right-padded row needs no mask: no real position reads a later one.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

SCOPE = "text_hybrid/ssd"


def ssd_scan(x, dt, a, b, c, d, *, chunk: int):
    """x (R, S, H, P); dt (R, S, H) float32, positive (after its
    softplus); a (H,) float32, negative; b, c (R, S, N); d (H,) -> y (R, S,
    H, P) in ``x``'s type."""
    with jax.named_scope(SCOPE):
        return _ssd_scan(x, dt, a, b, c, d, chunk)


def _ssd_scan(x, dt, a, b, c, d, chunk: int):
    rows, s, heads, p = x.shape
    dtype = x.dtype
    pad = -s % chunk
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    k = (s + pad) // chunk
    xc = x.reshape(rows, k, chunk, heads, p)
    bc = b.reshape(rows, k, chunk, -1).astype(dtype)
    cc = c.reshape(rows, k, chunk, -1).astype(dtype)
    dtc = dt.astype(jnp.float32).reshape(rows, k, chunk, heads)
    # log-decays, heads ahead of the positions: (R, K, H, chunk); cum_t is
    # the log of prod_{r<=t} a_r within the chunk
    cum = jnp.cumsum((dtc * a.astype(jnp.float32)).transpose(0, 1, 3, 2),
                     axis=-1)
    xdt = (xc.astype(jnp.float32) * dtc[..., None]).astype(dtype)

    # inside a chunk
    at = jnp.arange(chunk)
    lower = at[:, None] >= at[None, :]
    decay = jnp.exp(jnp.where(lower, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                    # (R, K, H, t, s)
    cb = jnp.einsum("rktn,rksn->rkts", cc, bc,
                    preferred_element_type=jnp.float32)
    y = jnp.einsum("rkhts,rkshp->rkthp",
                   (cb[:, :, None] * decay).astype(dtype), xdt,
                   preferred_element_type=jnp.float32)

    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(cum[..., -1:] - cum)                   # (R, K, H, s)
    own = jnp.einsum(
        "rkshp,rksn->rkhpn",
        (xdt.astype(jnp.float32)
         * to_end.transpose(0, 1, 3, 2)[..., None]).astype(dtype), bc,
        preferred_element_type=jnp.float32)

    # the recurrence over chunks: the state entering each
    def carry(state, chunk_k):
        own_k, decay_k = chunk_k
        return decay_k[..., None, None] * state + own_k, state

    across = jnp.exp(cum[..., -1])                          # (R, K, H)
    _, entering = lax.scan(
        carry, jnp.zeros((rows, heads, p, own.shape[-1]), jnp.float32),
        (own.transpose(1, 0, 2, 3, 4), across.transpose(1, 0, 2)))
    entering = entering.transpose(1, 0, 2, 3, 4)            # (R, K, H, P, N)

    # the entering state's part of the output
    y = y + (jnp.einsum("rktn,rkhpn->rkthp", cc, entering.astype(dtype),
                        preferred_element_type=jnp.float32)
             * jnp.exp(cum).transpose(0, 1, 3, 2)[..., None])
    y = y + xc.astype(jnp.float32) * d.astype(jnp.float32)[:, None]
    return y.reshape(rows, s + pad, heads, p)[:, :s].astype(dtype)
