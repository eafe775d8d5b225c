"""``ops/kda.py`` (Kimi Delta Attention's gated delta rule in chunks, a decay
per channel, one Pallas kernel, interpreted here) against the recurrence it
stands for, written out position by position: chunks of 4, 8 and 64 over
sequences that are no multiple of them; decays strong enough that a chunk's
log-decay passes -60 (and -1000) with beta near 2; rows that never read each
other; bfloat16 inputs; the served widths (heads of 128, chunks of 64, the
state carried over two and three chunks); no gradient."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from milnce_tpu.ops.kda import kda_chunked

ROWS, HEADS, K, V = 2, 3, 16, 8
SCALE = K ** -0.5


def recurrence(q, k, v, g, beta, scale=SCALE):
    """S_t = (I - b_t k_t k_t^T) Diag(exp g_t) S_{t-1} + b_t k_t v_t^T;
    o_t = scale S_t^T q_t."""
    def step(state, at_t):
        q_t, k_t, v_t, g_t, b_t = at_t
        state = jnp.exp(g_t)[..., None] * state
        state = state + b_t[..., None, None] * k_t[..., None] * (
            v_t - jnp.einsum("rhk,rhkv->rhv", k_t, state))[..., None, :]
        return state, scale * jnp.einsum("rhkv,rhk->rhv", state, q_t)

    zero = jnp.zeros(q.shape[:1] + q.shape[2:] + v.shape[-1:], jnp.float32)
    _, o = jax.lax.scan(step, zero, tuple(jnp.moveaxis(t, 1, 0)
                                          for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def inputs(seq, seed=0, rows=ROWS, decay=0.1, beta_low=0.0, heads=HEADS,
           dk=K, dv=V):
    """Unit q and k; log-decays -decay x (1/2 + Exp(1)) a channel; beta in
    [2 beta_low, 2)."""
    rng = np.random.default_rng(seed)

    def unit(*shape):
        x = rng.standard_normal(shape)
        return jnp.asarray(x / np.linalg.norm(x, axis=-1, keepdims=True),
                           jnp.float32)

    g = -decay * (0.5 + rng.exponential(size=(rows, seq, heads, dk)))
    beta = 2.0 * rng.uniform(beta_low, 1.0, (rows, seq, heads))
    return (unit(rows, seq, heads, dk), unit(rows, seq, heads, dk),
            jnp.asarray(rng.standard_normal((rows, seq, heads, dv)),
                        jnp.float32),
            jnp.asarray(g, jnp.float32), jnp.asarray(beta, jnp.float32))


@pytest.mark.parametrize("seq", [5, 19, 70], ids=["below_a_chunk",
                                                  "no_multiple",
                                                  "over_a_long_chunk"])
@pytest.mark.parametrize("chunk", [4, 8, 64])
def test_the_chunked_rule_is_the_recurrence(seq, chunk):
    args = inputs(seq, seed=seq)
    got = kda_chunked(*args, chunk=chunk, scale=SCALE)
    np.testing.assert_allclose(np.asarray(got), np.asarray(recurrence(*args)),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("decay,below", [(1.0, -60.0), (16.0, -1000.0)],
                         ids=["chunk_below_e-60", "chunk_below_e-1000"])
def test_strong_decays_and_beta_near_two_stay_finite_and_exact(decay, below):
    """A chunk of 64 whose log-decay reaches -64 (-1000) in every channel,
    beta in [1.9, 2): every factor the sub-blocks take is at most 1, so
    nothing overflows; the answer is still the recurrence's."""
    args = inputs(70, seed=11, decay=decay, beta_low=0.95)
    g = np.asarray(args[3])
    assert g[:, :64].sum(axis=1).max() < below
    got = np.asarray(kda_chunked(*args, chunk=64, scale=SCALE))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, np.asarray(recurrence(*args)),
                               rtol=2e-5, atol=2e-5)


def test_a_row_never_reads_another_and_no_position_a_later_one():
    q, k, v, g, beta = inputs(37, seed=7)
    whole = np.asarray(kda_chunked(q, k, v, g, beta, chunk=8, scale=SCALE))
    alone = np.asarray(kda_chunked(q[1:], k[1:], v[1:], g[1:], beta[1:],
                                   chunk=8, scale=SCALE))
    np.testing.assert_allclose(whole[1:], alone, rtol=1e-6, atol=1e-6)
    # what follows position 20 (right padding, or anything) changes
    # nothing up to it
    cut = np.asarray(kda_chunked(q, k, v.at[:, 21:].set(9.0),
                                 g.at[:, 21:].set(0.0), beta, chunk=8,
                                 scale=SCALE))
    np.testing.assert_allclose(cut[:, :21], whole[:, :21], rtol=1e-6,
                               atol=1e-6)


def test_bfloat16_inputs_give_float32_close_to_the_recurrence():
    q, k, v, g, beta = inputs(19, seed=3)
    want = np.asarray(recurrence(q, k, v, g, beta))
    got = kda_chunked(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                      v.astype(jnp.bfloat16), g, beta, chunk=8, scale=SCALE)
    assert got.dtype == jnp.float32
    assert np.abs(np.asarray(got) - want).max() < 0.03 * np.abs(want).max()


@pytest.mark.parametrize("seq", [128, 150], ids=["two_chunks",
                                               "three_chunks_ragged"])
def test_the_served_widths_carry_the_state_across_chunks(seq):
    """Heads of 128 (K = V), chunks of 64, two heads: the state the kernel
    keeps in its scratch from one chunk to the next is the recurrence's."""
    args = inputs(seq, seed=seq + 1, heads=2, dk=128, dv=128)
    scale = 128 ** -0.5
    got = kda_chunked(*args, chunk=64, scale=scale)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(recurrence(*args, scale=scale)),
                               rtol=2e-5, atol=2e-5)


def test_bfloat16_inputs_at_the_served_widths_give_float32():
    q, k, v, g, beta = inputs(70, seed=5, heads=2, dk=128, dv=128)
    got = kda_chunked(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                      v.astype(jnp.bfloat16), g, beta, chunk=64,
                      scale=128 ** -0.5)
    assert got.dtype == jnp.float32 and got.shape == v.shape


def test_no_gradient_is_taken_through_the_rule():
    q, k, v, g, beta = inputs(8, seed=2)
    with pytest.raises(NotImplementedError, match="forward only"):
        jax.grad(lambda v: kda_chunked(q, k, v, g, beta, chunk=8,
                                       scale=SCALE).sum())(v)
