"""Pallas soft-DTW kernel vs the lax.scan golden: forward values and
custom-VJP gradients (the hermetic port of the reference's CPU<->GPU
cross-check, soft_dtw_cuda.py:439-440).  Runs in interpret mode on CPU,
compiled on TPU — same code path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from milnce_tpu.ops.softdtw import SoftDTW, softdtw_scan
from milnce_tpu.ops.softdtw_pallas import softdtw_pallas


@pytest.mark.parametrize("n,m", [
    (4, 4),
    pytest.param(7, 5, marks=pytest.mark.slow),
    pytest.param(3, 9, marks=pytest.mark.slow),
    (16, 16),
])
def test_forward_matches_scan(n, m):
    rng = np.random.RandomState(0)
    D = jnp.asarray(rng.rand(3, n, m).astype(np.float32))
    expected = np.asarray(softdtw_scan(D, 0.5))
    got = np.asarray(softdtw_pallas(D, 0.5))
    np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("gamma", [1.0, 0.1])
def test_gradient_matches_scan_autodiff(gamma):
    rng = np.random.RandomState(1)
    D = jnp.asarray(rng.rand(2, 6, 5).astype(np.float32))
    expected = jax.grad(lambda d: softdtw_scan(d, gamma).sum())(D)
    got = jax.grad(lambda d: softdtw_pallas(d, gamma).sum())(D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-3, atol=1e-5)


def test_bandwidth_matches_scan():
    rng = np.random.RandomState(2)
    D = jnp.asarray(rng.rand(2, 8, 8).astype(np.float32))
    expected = np.asarray(softdtw_scan(D, 0.5, bandwidth=2))
    got = np.asarray(softdtw_pallas(D, 0.5, 2))
    np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_gradient_with_upstream_cotangent():
    rng = np.random.RandomState(3)
    D = jnp.asarray(rng.rand(3, 5, 5).astype(np.float32))
    w = jnp.asarray([0.5, -1.0, 2.0])
    expected = jax.grad(lambda d: (w * softdtw_scan(d, 0.7)).sum())(D)
    got = jax.grad(lambda d: (w * softdtw_pallas(d, 0.7)).sum())(D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-3, atol=1e-5)


def test_softdtw_module_pallas_backend():
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(2, 6, 8).astype(np.float32))
    y = jnp.asarray(rng.randn(2, 5, 8).astype(np.float32))
    ref = SoftDTW(gamma=0.1, dist_func="cosine", backend="scan")(x, y)
    got = SoftDTW(gamma=0.1, dist_func="cosine", backend="pallas")(x, y)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-4)


def test_rectangular_extreme():
    rng = np.random.RandomState(5)
    D = jnp.asarray(rng.rand(1, 2, 12).astype(np.float32))
    np.testing.assert_allclose(np.asarray(softdtw_pallas(D, 1.0)),
                               np.asarray(softdtw_scan(D, 1.0)), rtol=1e-5)


@pytest.mark.slow
def test_batch_tiling_pads_and_slices():
    """Batches above the 128-element tile cap split into multiple padded
    blocks (fwd AND bwd); values/grads must match the scan exactly."""
    rng = np.random.RandomState(6)
    D = jnp.asarray(rng.rand(130, 4, 4).astype(np.float32))
    np.testing.assert_allclose(np.asarray(softdtw_pallas(D, 0.5)),
                               np.asarray(softdtw_scan(D, 0.5)),
                               rtol=1e-5, atol=1e-5)
    got = jax.grad(lambda d: softdtw_pallas(d, 0.5).sum())(D)
    want = jax.grad(lambda d: softdtw_scan(d, 0.5).sum())(D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-5)


@pytest.mark.slow
def test_profile_harness_smoke():
    """The timing+allclose harness (the reference's only self-check,
    soft_dtw_cuda.py:389-463) runs end-to-end and reports agreement."""
    from milnce_tpu.ops.softdtw_profile import profile

    rec = profile(4, 5, 6, 3, n_iters=4)
    assert rec["allclose"] is True
    assert rec["shape"] == [4, 5, 6, 3]
    assert rec["scan_fwd_ms"] >= 0.0 and rec["pallas_fwd_ms"] >= 0.0


@pytest.mark.slow
def test_mil_regime_batch_squared_pairs():
    """The SDTW_3 training regime: B^2 short pairs (32x32 alignment, the
    shape that crashed Mosaic's vector lowering before the batch-tile
    cap; see _batch_tile)."""
    rng = np.random.RandomState(7)
    D = jnp.asarray(rng.rand(64, 32, 32).astype(np.float32))
    np.testing.assert_allclose(np.asarray(softdtw_pallas(D, 1.0)),
                               np.asarray(softdtw_scan(D, 1.0)),
                               rtol=1e-4, atol=1e-4)
    got = jax.grad(lambda d: softdtw_pallas(d, 1.0).sum())(D)
    want = jax.grad(lambda d: softdtw_scan(d, 1.0).sum())(D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-4)


@pytest.mark.slow
def test_lanes_layout_matches_scan(monkeypatch):
    """Large-batch short-pair shapes route through the batch-on-lanes
    kernels by default (3.5-26x measured on a v5e before PR 1, to be
    measured again by the benchmark);
    values and grads must match the scan (multi-block at B=300,
    rectangular, and the 32x32 MIL shape)."""
    monkeypatch.delenv("MILNCE_SDTW_LANES", raising=False)
    from milnce_tpu.ops import softdtw_pallas as sp

    rng = np.random.RandomState(13)
    for (b, n, m) in [(64, 32, 32), (300, 10, 8), (40, 16, 24)]:
        assert sp._use_lanes(b, n, m)
        D = jnp.asarray(rng.rand(b, n, m).astype(np.float32))
        np.testing.assert_allclose(np.asarray(softdtw_pallas(D, 0.7)),
                                   np.asarray(softdtw_scan(D, 0.7)),
                                   rtol=1e-4, atol=1e-4)
        got = jax.grad(lambda d: softdtw_pallas(d, 0.7).sum())(D)
        want = jax.grad(lambda d: softdtw_scan(d, 0.7).sum())(D)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-3, atol=1e-3)
    # small batches stay on the sublane-batch layout
    assert not sp._use_lanes(4, 10, 8)
    # MILNCE_SDTW_LANES=0 is the escape hatch back to sublane-batch
    monkeypatch.setenv("MILNCE_SDTW_LANES", "0")
    assert not sp._use_lanes(64, 32, 32)
