"""``ops/ssd.py`` (Mamba-2's scan in chunks) against the recurrence it
stands for, written out position by position: sequences below, at and
above a chunk and no multiple of it; the same output whatever the chunk;
and rows that never read each other."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from milnce_tpu.ops.ssd import ssd_scan

ROWS, HEADS, P, N = 3, 4, 8, 16


def recurrence(x, dt, a, b, c, d):
    """S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + d x_t."""
    def step(state, at_t):
        x_t, dt_t, b_t, c_t = at_t
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :])
        return state, jnp.einsum("rhpn,rn->rhp", state, c_t) + d[:, None] * x_t

    zero = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:], jnp.float32)
    _, y = jax.lax.scan(step, zero, tuple(jnp.moveaxis(t, 1, 0)
                                          for t in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)


def inputs(seq, seed=0, rows=ROWS):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)  # noqa: E731
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.3),
                                        (rows, seq, HEADS))), jnp.float32)
    a = -jnp.asarray(rng.uniform(1.0, 16.0, (HEADS,)), jnp.float32)
    return (f(rows, seq, HEADS, P), dt, a, f(rows, seq, N), f(rows, seq, N),
            f(HEADS))


@pytest.mark.parametrize("seq", [5, 8, 19, 32],
                         ids=["below_a_chunk", "one_chunk", "no_multiple",
                              "four_chunks"])
@pytest.mark.parametrize("chunk", [4, 8, "whole"])
def test_the_chunked_scan_is_the_recurrence(seq, chunk):
    args = inputs(seq, seed=seq)
    got = ssd_scan(*args, chunk=seq if chunk == "whole" else chunk)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(recurrence(*args)),
                               rtol=1e-5, atol=1e-5)


def test_a_row_never_reads_another_and_no_position_a_later_one():
    x, dt, a, b, c, d = inputs(19, seed=7)
    whole = np.asarray(ssd_scan(x, dt, a, b, c, d, chunk=8))
    alone = np.asarray(ssd_scan(x[1:2], dt[1:2], a, b[1:2], c[1:2], d,
                                chunk=8))
    np.testing.assert_allclose(whole[1:2], alone, rtol=1e-6, atol=1e-6)
    # what follows position 10 changes nothing up to it
    x2 = x.at[:, 11:].set(9.0)
    cut = np.asarray(ssd_scan(x2, dt, a, b, c, d, chunk=8))
    np.testing.assert_allclose(cut[:, :11], whole[:, :11],
                               rtol=1e-6, atol=1e-6)


def test_bfloat16_inputs_keep_their_type_and_the_state_float32():
    x, dt, a, b, c, d = inputs(19, seed=3)
    want = np.asarray(recurrence(x, dt, a, b, c, d))
    got = ssd_scan(x.astype(jnp.bfloat16), dt, a, b.astype(jnp.bfloat16),
                   c.astype(jnp.bfloat16), d, chunk=8)
    assert got.dtype == jnp.bfloat16
    err = np.abs(np.asarray(got, np.float32) - want).max()
    assert err < 0.05 * np.abs(want).max()
