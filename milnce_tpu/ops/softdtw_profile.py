"""Soft-DTW timing + correctness harness.

TPU-native port of the reference's only self-verification tool
(`/root/reference/soft_dtw_cuda.py:389-463` — ``timed_run``/``profile``):
times forward+backward of the Pallas kernel against the ``lax.scan``
golden implementation and asserts they agree, across shape sweeps.

Run standalone on any backend (Pallas runs compiled on TPU, interpret
elsewhere):

    python -m milnce_tpu.ops.softdtw_profile            # default sweep
    python -m milnce_tpu.ops.softdtw_profile 32 256 256 512

Unlike the reference, the profile is also exercised in the test suite
(tests/test_softdtw_pallas.py) — the reference had no tests at all
(SURVEY.md §4).
"""

from __future__ import annotations

import json
import sys

import jax
import jax.numpy as jnp
import numpy as np


def timed_run(fn, D, n_iters: int = 256):
    """Mirror of soft_dtw_cuda.py:389-413: one verification pass with
    gradients + a timed fwd / fwd+bwd measurement.  Returns
    (fwd_s, bwd_s, value, grad).

    Timing protocol: ``milnce_tpu.utils.timing.chained_seconds`` (chained
    scan with a CSE-defeating carry perturbation, differenced between two
    chain lengths, host-materialized — timed one dispatch at a time, a
    kernel this small reads as the host's dispatch latency)."""
    from milnce_tpu.utils.timing import chained_seconds

    value_and_grad = jax.jit(jax.value_and_grad(lambda d: jnp.sum(fn(d))))

    # verification pass (also compiles the single-shot forms)
    value, grad = value_and_grad(D)
    jax.block_until_ready((value, grad))

    t_fwd = chained_seconds(lambda d: jnp.sum(fn(d)), D, n_iters)
    # grad() re-runs the forward, so each iteration is one fwd+bwd pass
    t_bwd = chained_seconds(lambda d: jnp.sum(jax.grad(
        lambda x: jnp.sum(fn(x)))(d)), D, n_iters)

    return t_fwd, t_bwd, np.asarray(value), np.asarray(grad)


def profile(batch_size: int, seq_len_a: int, seq_len_b: int, dims: int,
            gamma: float = 1.0, n_iters: int = 256, tol: float = 1e-3):
    """Cross-check scan vs Pallas fwd+bwd and report timings
    (soft_dtw_cuda.py:416-452).  Returns the result record."""
    from milnce_tpu.ops.softdtw import softdtw_scan
    from milnce_tpu.ops.softdtw_pallas import softdtw_pallas

    rng = np.random.RandomState(0)
    x = rng.randn(batch_size, seq_len_a, dims).astype(np.float32)
    y = rng.randn(batch_size, seq_len_b, dims).astype(np.float32)
    # Mean (not summed) squared-euclidean cost keeps the harness focused
    # on the DP kernel itself at a realistic O(1) cost scale (training
    # costs are cosine/dot on normalized embeddings).  Unnormalized d=512
    # costs push R to ~1e5+, where f32 rounding of R enters the
    # E-recurrence's exp((r1 - r - d)/gamma) as multiplicative weight
    # error and the hand-rolled backward (the reference's own algorithm,
    # soft_dtw_cuda.py:106-109) visibly drifts from autodiff — a drift the
    # reference harness can't see because it compares the E-recurrence
    # against itself (soft_dtw_cuda.py:439-440).
    D = jnp.asarray(((x[:, :, None, :] - y[:, None, :, :]) ** 2).mean(-1))

    t_fwd_s, t_bwd_s, v_s, g_s = timed_run(
        lambda d: softdtw_scan(d, gamma), D, n_iters)
    t_fwd_p, t_bwd_p, v_p, g_p = timed_run(
        lambda d: softdtw_pallas(d, gamma), D, n_iters)

    # the allclose half of the reference harness (soft_dtw_cuda.py:439-440)
    assert np.allclose(v_s, v_p, atol=tol, rtol=tol), (
        f"forward mismatch: max|dv|={np.abs(v_s - v_p).max()}")
    assert np.allclose(g_s, g_p, atol=tol, rtol=tol), (
        f"backward mismatch: max|dg|={np.abs(g_s - g_p).max()}")

    backend = jax.default_backend()
    rec = {
        "backend": backend,
        "pallas_compiled": backend == "tpu",
        "shape": [batch_size, seq_len_a, seq_len_b, dims],
        "scan_fwd_ms": round(t_fwd_s * 1e3, 3),
        "scan_fwd_bwd_ms": round(t_bwd_s * 1e3, 3),
        "pallas_fwd_ms": round(t_fwd_p * 1e3, 3),
        "pallas_fwd_bwd_ms": round(t_bwd_p * 1e3, 3),
        "speedup_fwd": round(t_fwd_s / t_fwd_p, 2) if t_fwd_p else None,
        "speedup_fwd_bwd": round(t_bwd_s / t_bwd_p, 2) if t_bwd_p else None,
        "allclose": True,
    }
    print(json.dumps(rec))
    return rec


if __name__ == "__main__":
    if len(sys.argv) == 5:
        shapes = [tuple(int(a) for a in sys.argv[1:])]
    else:
        # reference presets (soft_dtw_cuda.py:460-463) + the MIL-NCE
        # training regime (SDTW_3 scores B^2 short pairs, loss.py:103-106)
        shapes = [(128, 17, 15, 2), (512, 64, 64, 2), (32, 256, 256, 512),
                  (1024, 32, 32, 64)]
    for shape in shapes:
        profile(*shape)
