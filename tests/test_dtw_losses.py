"""DTW loss family: shape-generic behavior (spec: reference loss.py:20-134,
with the hardcoded shapes removed per SURVEY.md §1)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax import shard_map
from milnce_tpu.losses.dtw_losses import (cdtw_loss, sdtw_3_loss,
                                          sdtw_cidm_loss, sdtw_negative_loss)


def _seqs(b=4, n=6, m=5, d=8, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(b, n, d).astype(np.float32)),
            jnp.asarray(rng.randn(b, m, d).astype(np.float32)))


def test_cdtw_scalar_and_finite():
    v, t = _seqs()
    out = cdtw_loss(v, t, index=2, gamma=0.1)
    assert out.shape == (1,)
    assert np.isfinite(float(out[0]))


def test_cdtw_anchor_matters():
    v, t = _seqs(seed=1)
    l0 = float(cdtw_loss(v, t, index=0, gamma=0.1)[0])
    l1 = float(cdtw_loss(v, t, index=1, gamma=0.1)[0])
    assert l0 != l1


def test_sdtw_cidm_runs_any_batch_size():
    for b in (2, 5):
        v, t = _seqs(b=b, seed=b)
        start = jnp.asarray(np.arange(b, dtype=np.float32) * 7.0)
        out = sdtw_cidm_loss(v, t, start)
        assert np.isfinite(float(out))


def test_sdtw_negative_any_batch_size():
    """The reference hardcodes B=160, n=8 (loss.py:81-88); ours must not."""
    for b, n in [(3, 4), (5, 2)]:
        v, t = _seqs(b=b, n=n, m=n, seed=b)
        out = sdtw_negative_loss(v, t, gamma=0.1)
        assert np.isfinite(float(out))


def test_sdtw_negative_matches_numpy_formula():
    """Negative term: block-diagonal (own-clip) entries zeroed — exp(0)=1
    still contributes, exactly like the reference mask (loss.py:83-88)."""
    from milnce_tpu.ops.softdtw import SoftDTW

    b, n, d = 3, 4, 8
    rng = np.random.RandomState(7)
    v = rng.randn(b, n, d).astype(np.float32)
    t = rng.randn(b, n, d).astype(np.float32)
    pairwise = v.reshape(-1, d) @ t.reshape(-1, d).T
    for i in range(b):
        pairwise[i * n:(i + 1) * n, i * n:(i + 1) * n] = 0.0
    negative = np.exp(pairwise).sum(1).reshape(b, n).sum(1)
    sdtw = SoftDTW(gamma=0.1, dist_func="cosine")
    pos = np.asarray(sdtw(jnp.asarray(v), jnp.asarray(t)))
    expected = float(np.mean(pos + negative / (b - 1)))
    got = float(sdtw_negative_loss(jnp.asarray(v), jnp.asarray(t), gamma=0.1))
    np.testing.assert_allclose(got, expected, rtol=1e-4)


def test_sdtw3_pair_chunk_parity():
    """ISSUE 12 satellite: ``pair_chunk`` streams each NCE term's
    negative logsumexp over anchor-row chunks (jax.checkpoint'd scan —
    O(B * pair_chunk) pair batches instead of the B^2 broadcast) and
    must match the dense all-pairs form to float tolerance, values AND
    gradients, including the uneven B % pair_chunk != 0 tail."""
    v, t = _seqs(b=5, n=4, m=4, d=8, seed=21)
    dense = sdtw_3_loss(v, t, gamma=0.1)
    for chunk in (2, 3, 5):                     # uneven (5 % 2, 5 % 3) + whole
        chunked = sdtw_3_loss(v, t, gamma=0.1, pair_chunk=chunk)
        for a, b in zip(dense, chunked):
            np.testing.assert_allclose(float(b), float(a), rtol=1e-4,
                                       atol=1e-5)
    g_dense = jax.grad(lambda a: sum(sdtw_3_loss(a, t, gamma=0.1)))(v)
    g_chunk = jax.grad(
        lambda a: sum(sdtw_3_loss(a, t, gamma=0.1, pair_chunk=2)))(v)
    np.testing.assert_allclose(np.asarray(g_chunk), np.asarray(g_dense),
                               atol=1e-5)
    # pair_chunk=0 (and >= B) keeps the dense program — the pinned
    # train_step_sdtw3 trace never moves by default
    full = sdtw_3_loss(v, t, gamma=0.1, pair_chunk=0)
    for a, b in zip(dense, full):
        assert float(a) == float(b)


def test_sequence_loss_threads_pair_chunk(monkeypatch):
    """loss.sdtw_pair_chunk must reach sdtw_3_loss through the
    train-step dispatcher (a config-only dead knob would leave the
    streamed form unreachable in production).  A capturing fake stands
    in for the DP — the dispatcher imports it at call time, so the
    monkeypatch intercepts the real forwarding path at trace cost only
    (the streamed values themselves are pinned by the parity test
    above)."""
    import jax as _jax
    from jax.sharding import Mesh, PartitionSpec as P

    import milnce_tpu.losses.dtw_losses as dtw_mod
    from milnce_tpu.config import LossConfig
    from milnce_tpu.train.step import _sequence_loss

    seen = {}

    def fake_sdtw_3(v_all, t_all, pair_chunk=0, **kw):
        seen["pair_chunk"] = pair_chunk
        zero = jnp.float32(0)
        return (zero, zero, zero)

    monkeypatch.setattr(dtw_mod, "sdtw_3_loss", fake_sdtw_3)
    v, t = _seqs(b=8, n=3, m=3, d=4, seed=17)
    start = jnp.zeros((8,))
    mesh = Mesh(np.asarray(_jax.devices()), ("data",))
    cfg = LossConfig(name="sdtw_3", sdtw_gamma=0.1, sdtw_pair_chunk=3)
    fn = shard_map(
        lambda a, b_, s: _sequence_loss(cfg, a, b_, s, "data"),
        mesh=mesh, in_specs=(P("data"), P("data"), P("data")),
        out_specs=P(), check_vma=False)
    _jax.make_jaxpr(fn)(v, t, start)     # trace is enough to dispatch
    assert seen["pair_chunk"] == 3, "sdtw_pair_chunk never reached the dp"


@pytest.mark.slow
def test_sdtw3_three_terms_and_gradients():
    v, t = _seqs(b=3, n=4, m=4, seed=9)
    l1, l2, l3 = sdtw_3_loss(v, t, gamma=0.1)
    for l in (l1, l2, l3):
        assert np.isfinite(float(l))
    g = jax.grad(lambda a: sum(sdtw_3_loss(a, t, gamma=0.1)))(v)
    assert np.isfinite(np.asarray(g)).all()


def test_dist_and_bandwidth_knobs_reach_the_dp():
    """--loss.sdtw_dist / --loss.sdtw_bandwidth must actually change the
    computation (they were once config-only dead knobs); '' keeps each
    loss's reference default distance."""
    from milnce_tpu.losses.dtw_losses import cdtw_batch_loss

    v, t = _seqs(b=3, n=4, m=4, seed=11)
    base = float(cdtw_batch_loss(v, t, gamma=0.1))
    assert base == float(cdtw_batch_loss(v, t, gamma=0.1, dist="cosine"))
    assert base != float(cdtw_batch_loss(v, t, gamma=0.1, dist="negative_dot"))
    assert base != float(cdtw_batch_loss(v, t, gamma=0.1, bandwidth=1))
    l3 = sdtw_3_loss(v, t, gamma=0.1)                     # negative_dot default
    l3_override = sdtw_3_loss(v, t, gamma=0.1, dist="cosine")
    assert float(l3[1]) != float(l3_override[1])


@pytest.mark.slow
def test_sequence_loss_threads_config_knobs():
    """The train-step dispatcher forwards dist/bandwidth from LossConfig."""
    from jax.sharding import Mesh
    from milnce_tpu.config import LossConfig
    from milnce_tpu.train.step import _sequence_loss
    import jax as _jax
    from jax.sharding import PartitionSpec as P

    v, t = _seqs(b=8, n=4, m=4, seed=12)
    start = jnp.zeros((8,))
    mesh = Mesh(np.asarray(_jax.devices()), ("data",))

    def run(cfg):
        fn = shard_map(
            lambda a, b_, s: _sequence_loss(cfg, a, b_, s, "data"),
            mesh=mesh, in_specs=(P("data"), P("data"), P("data")),
            out_specs=P(), check_vma=False)
        return float(fn(v, t, start))

    base = run(LossConfig(name="cdtw", sdtw_gamma=0.1))
    banded = run(LossConfig(name="cdtw", sdtw_gamma=0.1, sdtw_bandwidth=1))
    distd = run(LossConfig(name="cdtw", sdtw_gamma=0.1,
                           sdtw_dist="negative_dot"))
    assert base != banded and base != distd


@pytest.mark.slow
def test_sequence_loss_per_loss_gamma_defaults():
    """sdtw_gamma=None resolves to each loss's reference default: 1e-5
    for cdtw (loss.py:26), 0.1 for the sdtw_* family (loss.py:38,74,97);
    an explicit value overrides."""
    from jax.sharding import Mesh, PartitionSpec as P
    import jax as _jax
    from milnce_tpu.config import LossConfig
    from milnce_tpu.train.step import _sequence_loss

    v, t = _seqs(b=8, n=4, m=4, seed=13)
    start = jnp.zeros((8,))
    mesh = Mesh(np.asarray(_jax.devices()), ("data",))

    def run(cfg):
        fn = shard_map(
            lambda a, b_, s: _sequence_loss(cfg, a, b_, s, "data"),
            mesh=mesh, in_specs=(P("data"), P("data"), P("data")),
            out_specs=P(), check_vma=False)
        return float(fn(v, t, start))

    assert run(LossConfig(name="cdtw")) == run(
        LossConfig(name="cdtw", sdtw_gamma=1e-5))
    assert run(LossConfig(name="cdtw")) != run(
        LossConfig(name="cdtw", sdtw_gamma=0.1))
    assert run(LossConfig(name="sdtw_3")) == run(
        LossConfig(name="sdtw_3", sdtw_gamma=0.1))
