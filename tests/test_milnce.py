"""MIL-NCE loss: golden-value tests vs an independent numpy transcription of
the reference math (loss.py:10-18), plus sharded == unsharded on a virtual
8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import set_mesh, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from milnce_tpu.losses.milnce import milnce_loss


def numpy_milnce(v, t):
    """Reference formula, straight from the math in loss.py:10-18."""
    b = v.shape[0]
    x = (v @ t.T).reshape(b, b, -1)                  # (B, B, K)
    nominator = x[np.arange(b), np.arange(b), :]     # (B, K)
    num = _logsumexp(nominator, axis=1)
    both = np.concatenate([x, x.transpose(1, 0, 2)], axis=1).reshape(b, -1)
    denom = _logsumexp(both, axis=1)
    return float(np.mean(denom - num))


def _logsumexp(a, axis):
    m = a.max(axis=axis, keepdims=True)
    return (m + np.log(np.exp(a - m).sum(axis=axis, keepdims=True))).squeeze(axis)


@pytest.mark.parametrize("b,k,d", [(4, 1, 8), (4, 3, 8), (6, 5, 16)])
def test_matches_reference_formula(b, k, d):
    rng = np.random.RandomState(0)
    v = rng.randn(b, d).astype(np.float32)
    t = rng.randn(b * k, d).astype(np.float32)
    ours = float(milnce_loss(jnp.asarray(v), jnp.asarray(t)))
    np.testing.assert_allclose(ours, numpy_milnce(v, t), rtol=1e-5)


def test_hand_computed_tiny_case():
    # B=2, K=1, D=1: x = [[1, 2], [2, 4]] (v=[1,2], t=[1,2] columns)
    v = jnp.array([[1.0], [2.0]])
    t = jnp.array([[1.0], [2.0]])
    x = np.array([[1.0, 2.0], [2.0, 4.0]])
    num = np.array([1.0, 4.0])
    denom = np.array([_logsumexp(np.array([1, 2, 1, 2.0]), 0),
                      _logsumexp(np.array([2, 4, 2, 4.0]), 0)])
    expected = float(np.mean(denom - num))
    np.testing.assert_allclose(float(milnce_loss(v, t)), expected, rtol=1e-6)


def test_sharded_equals_unsharded():
    devices = jax.devices()
    assert len(devices) == 8, "conftest must provide 8 virtual devices"
    mesh = Mesh(np.array(devices), ("data",))
    b, k, d = 16, 3, 32
    rng = np.random.RandomState(1)
    v = rng.randn(b, d).astype(np.float32)
    t = rng.randn(b * k, d).astype(np.float32)

    @jax.jit
    def sharded(v, t):
        return shard_map(
            lambda vv, tt: milnce_loss(vv, tt, axis_name="data"),
            mesh=mesh, in_specs=(P("data"), P("data")), out_specs=P())(v, t)

    with set_mesh(mesh):
        out = sharded(jax.device_put(v, NamedSharding(mesh, P("data"))),
                      jax.device_put(t, NamedSharding(mesh, P("data"))))
    np.testing.assert_allclose(float(out), numpy_milnce(v, t), rtol=1e-5)


@pytest.mark.parametrize("check_vma", [True, False],
                         ids=["check_vma", "no_check_vma"])
def test_sharded_gradients_match_unsharded(check_vma):
    """Gradient taken INSIDE the shard_map body, under each setting of
    ``check_vma`` the repo passes (the train steps pass False): a plain
    ``lax.psum`` in the loss transposes to another psum under False and
    every element comes out 8.0x the unsharded one — the reduction's
    explicit VJP (losses/milnce.py psum_local_grad) must not."""
    devices = jax.devices()
    assert len(devices) == 8, "conftest must provide 8 virtual devices"
    mesh = Mesh(np.array(devices), ("data",))
    b, k, d = 8, 2, 16
    rng = np.random.RandomState(2)
    v = rng.randn(b, d).astype(np.float32)
    t = rng.randn(b * k, d).astype(np.float32)

    ref_grad_v, ref_grad_t = jax.grad(
        lambda vv, tt: milnce_loss(vv, tt), argnums=(0, 1))(
            jnp.asarray(v), jnp.asarray(t))

    @jax.jit
    def sharded_grads(v, t):
        def local(vv, tt):
            gv, gt = jax.grad(
                lambda a, b_: milnce_loss(a, b_, axis_name="data"),
                argnums=(0, 1))(vv, tt)
            return gv, gt
        return shard_map(local, mesh=mesh,
                         in_specs=(P("data"), P("data")),
                         out_specs=(P("data"), P("data")),
                         check_vma=check_vma)(v, t)

    with set_mesh(mesh):
        gv, gt = sharded_grads(jax.device_put(v, NamedSharding(mesh, P("data"))),
                               jax.device_put(t, NamedSharding(mesh, P("data"))))
    np.testing.assert_allclose(np.asarray(gv), np.asarray(ref_grad_v),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(gt), np.asarray(ref_grad_t),
                               atol=1e-6)


def test_per_chip_memory_at_baseline_scale():
    """Compile-only memory proof at the baseline's global batch
    (SURVEY §7 hard part 4 / VERDICT r1 next #10): at Bg=8192, K=5 on an
    8-device mesh, the compiled per-chip temp footprint stays at the two
    local logits cubes O(B_local*Bg*K) — NOT the replicated O(Bg^2*K)
    cube (which alone would be 8192*8192*5*4 B = 1.3 TB)."""
    devices = jax.devices()
    mesh = Mesh(np.array(devices), ("data",))
    bg, k, d = 8192, 5, 32
    b_local = bg // len(devices)

    @jax.jit
    def sharded(v, t):
        return shard_map(
            lambda vv, tt: milnce_loss(vv, tt, axis_name="data"),
            mesh=mesh, in_specs=(P("data"), P("data")), out_specs=P())(v, t)

    v = jax.ShapeDtypeStruct((bg, d), jnp.float32,
                             sharding=NamedSharding(mesh, P("data")))
    t = jax.ShapeDtypeStruct((bg * k, d), jnp.float32,
                             sharding=NamedSharding(mesh, P("data")))
    with set_mesh(mesh):
        stats = sharded.lower(v, t).compile().memory_analysis()
    cube = b_local * bg * k * 4                      # one (B_local, Bg, K) f32
    # temp budget: rows + cols cubes + reduction scratch; flag anything
    # beyond 4 cubes (the old concat form needed ~6, replicated needs ~800)
    assert stats.temp_size_in_bytes <= 4 * cube, (
        f"per-chip temps {stats.temp_size_in_bytes/1e6:.0f} MB exceed "
        f"4 cubes ({4*cube/1e6:.0f} MB) — logits memory no longer "
        f"O(B_local*Bg*K)")


def test_scale_invariance_of_batch_position():
    """Permuting batch order permutes nothing about the mean loss."""
    rng = np.random.RandomState(3)
    b, k, d = 6, 2, 8
    v = rng.randn(b, d).astype(np.float32)
    t = rng.randn(b * k, d).astype(np.float32)
    perm = rng.permutation(b)
    t_resh = t.reshape(b, k, d)[perm].reshape(b * k, d)
    l1 = float(milnce_loss(jnp.asarray(v), jnp.asarray(t)))
    l2 = float(milnce_loss(jnp.asarray(v[perm]), jnp.asarray(t_resh)))
    np.testing.assert_allclose(l1, l2, rtol=1e-5)
