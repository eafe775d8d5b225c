"""Chunked streaming MIL-NCE (ISSUE 12): value + gradient parity against
the dense cube loss, across both streaming backends (scan, and the
Pallas kernel in interpret mode on CPU), K in {1, 5}, uneven last chunks
(Bg % chunk != 0), and the single-shard / 8-way 1-D / 4x2 2-D mesh
layouts — plus the train-step-level pin: dense and chunked steps train
identically through 2 full optimizer steps, params leaf-for-leaf
(the test_train_2d layout-parity harness, re-aimed at the loss impl).

Pinned tier-1 (never @slow) by tests/test_suite_hygiene.py: these are
the regression fence for the memory-efficient loss path.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import set_mesh, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from milnce_tpu.config import LossConfig, OptimConfig, ParallelConfig
from milnce_tpu.losses.milnce import milnce_loss
from milnce_tpu.losses.milnce_chunked import (build_milnce_loss,
                                              milnce_default_chunk,
                                              milnce_loss_chunked,
                                              prefers_chunked)
from milnce_tpu.models import S3D
from milnce_tpu.parallel.mesh import build_mesh, replicate_to_mesh
from milnce_tpu.parallel.sharding_map import (place_tree, sharded_count,
                                              state_partition_specs)
from milnce_tpu.train.schedule import build_schedule
from milnce_tpu.train.state import build_optimizer, create_train_state
from milnce_tpu.train.step import make_grad_cache_step, make_train_step


def _embeddings(b, k, d, seed=0):
    rng = np.random.RandomState(seed)
    return (rng.randn(b, d).astype(np.float32),
            rng.randn(b * k, d).astype(np.float32))


def _dense_value_and_grads(v, t):
    return jax.value_and_grad(lambda a, b_: milnce_loss(a, b_),
                              argnums=(0, 1))(jnp.asarray(v),
                                              jnp.asarray(t))


# --------------------------------------------------------------------------
# single-shard parity: both backends, K in {1, 5}, uneven chunks
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["scan", "pallas"])
@pytest.mark.parametrize("b,k,d,chunk", [
    (8, 1, 16, 4),          # K=1
    (8, 5, 16, 4),          # K=5, even chunks
    (8, 5, 16, 5),          # uneven last chunk (8 % 5 != 0)
    (6, 5, 16, 4),          # uneven + batch off the sublane grid
], ids=["k1", "k5", "uneven", "uneven-b6"])
def test_single_shard_value_and_grad_parity(backend, b, k, d, chunk):
    v, t = _embeddings(b, k, d, seed=b * 10 + k)
    dense_val, dense_grads = _dense_value_and_grads(v, t)
    val, grads = jax.value_and_grad(
        lambda a, b_: milnce_loss_chunked(a, b_, chunk=chunk,
                                          backend=backend),
        argnums=(0, 1))(jnp.asarray(v), jnp.asarray(t))
    np.testing.assert_allclose(float(val), float(dense_val), rtol=2e-6)
    for g, gd in zip(grads, dense_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(gd),
                                   atol=2e-6)


def test_default_chunk_rule_and_auto_impl_rule():
    # the chunk=0 rule: sublane-aligned, bounded by Bg, ~2 MiB of row
    # logits at the baseline point
    assert milnce_default_chunk(4, 1, 4) == 4          # tiny Bg passthrough
    c = milnce_default_chunk(128, 5, 8192)
    assert c % 8 == 0 and 8 <= c <= 8192
    assert 1_000_000 <= 128 * c * 5 * 4 <= 4_000_000   # ~2 MiB target
    # impl='auto': dense at test scale, chunked at the 8192 recipe
    assert not prefers_chunked(16, 16, 5)
    assert prefers_chunked(128, 8192, 5)


def test_build_milnce_loss_rejects_bad_knobs():
    with pytest.raises(ValueError, match="milnce_impl"):
        build_milnce_loss(LossConfig(milnce_impl="streamed"))
    with pytest.raises(ValueError, match="milnce_backend"):
        build_milnce_loss(LossConfig(milnce_impl="chunked",
                                     milnce_backend="cuda"))
    # loss_cfg=None keeps the dense path (the pinned default)
    v, t = _embeddings(4, 2, 8)
    fn = build_milnce_loss(None)
    np.testing.assert_allclose(
        float(fn(jnp.asarray(v), jnp.asarray(t), None)),
        float(milnce_loss(jnp.asarray(v), jnp.asarray(t))), rtol=1e-6)


# --------------------------------------------------------------------------
# sharded parity: 8-way 1-D and 4x2 2-D meshes
# --------------------------------------------------------------------------

def _sharded_loss_and_grads(mesh, axes, v, t, chunk, backend):
    spec = P(axes)

    @jax.jit
    def run(v, t):
        def local(vv, tt):
            def loss_of(a, b_):
                return milnce_loss_chunked(a, b_, axis_name=axes,
                                           chunk=chunk, backend=backend)
            val, grads = jax.value_and_grad(loss_of, argnums=(0, 1))(vv, tt)
            return val, grads

        return shard_map(local, mesh=mesh, in_specs=(spec, spec),
                         out_specs=(P(), (spec, spec)),
                         check_vma=False)(v, t)

    sh = NamedSharding(mesh, spec)
    with set_mesh(mesh):
        return run(jax.device_put(v, sh), jax.device_put(t, sh))


@pytest.mark.parametrize("layout,backend", [
    ("1d", "scan"), ("2d", "pallas"),
], ids=["1d-scan", "2d-pallas"])
def test_sharded_parity_matches_unsharded_dense(layout, backend):
    """8-way data mesh and the 4x2 (data, model) grid: the chunked loss
    + grads over mesh-wide negatives equal the unsharded dense loss —
    the same transitivity pin the dense loss carries in test_milnce.py,
    now across the chunk scan AND the gather/psum structure.  Two
    layout/backend pairs cover both axes of the matrix (the full
    backend cross-product is pinned single-shard above; compiling all
    four sharded grad programs again would only re-pay the 870 s tier-1
    budget for combinations the single-shard matrix already proves)."""
    devices = jax.devices()
    assert len(devices) == 8, "conftest must provide 8 virtual devices"
    if layout == "1d":
        mesh = Mesh(np.array(devices), ("data",))
        axes = "data"
    else:
        mesh = Mesh(np.array(devices).reshape(4, 2), ("data", "model"))
        axes = ("data", "model")
    b, k, d, chunk = 16, 3, 32, 5                     # uneven: 16 % 5 != 0
    v, t = _embeddings(b, k, d, seed=7)
    dense_val, dense_grads = _dense_value_and_grads(v, t)
    val, grads = _sharded_loss_and_grads(mesh, axes, v, t, chunk, backend)
    np.testing.assert_allclose(float(val), float(dense_val), rtol=1e-5)
    for g, gd in zip(grads, dense_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(gd),
                                   atol=1e-6)


# --------------------------------------------------------------------------
# train-step parity: 2 full optimizer steps, params leaf-for-leaf
# --------------------------------------------------------------------------

_B, _FRAMES, _SIZE, _WORDS, _VOCAB = 16, 4, 32, 5, 32
_MIN_SIZE = 256


def _batch(seed=0):
    rng = np.random.default_rng(seed)
    video = rng.integers(0, 255, (_B, _FRAMES, _SIZE, _SIZE, 3),
                         dtype=np.uint8)
    text = rng.integers(0, _VOCAB, (_B, _WORDS)).astype(np.int32)
    start = np.zeros((_B,), np.float32)
    return video, text, start


def _train(loss_cfg, two_d=False, grad_accum=1, n_steps=2,
           optim_cfg=OptimConfig(warmup_steps=2), n_devices=0):
    """Fresh init -> n_steps of the real step program; returns per-step
    losses and the final state (mirror of test_train_2d._train, with the
    loss impl as the axis under test).  ``n_devices`` builds the data
    mesh over the first N devices (0 = all 8)."""
    if two_d:
        mesh = build_mesh(ParallelConfig(model_axis="model",
                                         model_parallel_size=2))
        bn_axes = ("data", "model")
    else:
        mesh = build_mesh(ParallelConfig(),
                          devices=jax.devices()[:n_devices or None])
        bn_axes = "data"
    model = S3D(num_classes=16, vocab_size=_VOCAB, word_embedding_dim=8,
                text_hidden_dim=16, inception_blocks=1,
                bn_axis_name=bn_axes)
    variables = model.init(
        jax.random.PRNGKey(0),
        jnp.zeros((2, _FRAMES, _SIZE, _SIZE, 3), jnp.float32),
        jnp.zeros((2, _WORDS), jnp.int32))
    opt = build_optimizer(optim_cfg, build_schedule(optim_cfg, 10))
    state = create_train_state(variables, opt)
    if two_d:
        specs = state_partition_specs(state, mesh, "model",
                                      min_size=_MIN_SIZE)
        assert sharded_count(specs.params, "model") > 0
        state = place_tree(state, specs, mesh)
    else:
        specs = None
        state = replicate_to_mesh(state, mesh)
    kw = dict(donate=False, loss_cfg=loss_cfg, state_specs=specs,
              model_axis="model" if two_d else None)
    if grad_accum > 1:
        step = make_grad_cache_step(model, opt, mesh, grad_accum, **kw)
    else:
        step = make_train_step(model, opt, mesh, **kw)
    losses = []
    for i in range(n_steps):
        state, loss = step(state, *_batch(i))
        losses.append(float(loss))
    return losses, state


_CHUNKED = LossConfig(name="milnce", milnce_impl="chunked", milnce_chunk=6,
                      milnce_backend="scan")


def _assert_states_match(st1, st2):
    for a, b in zip(jax.tree_util.tree_leaves(st1.params),
                    jax.tree_util.tree_leaves(st2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_train_step_parity_dense_vs_chunked_1d():
    """2 full optimizer steps on the 8-way mesh: step-2 loss is a
    function of step-1's update, so agreement transitively pins the
    streamed loss's gradients THROUGH the optimizer — and final params
    agree leaf-for-leaf."""
    dense, st_d = _train(None)
    chunked, st_c = _train(_CHUNKED)
    np.testing.assert_allclose(chunked, dense, rtol=2e-4, atol=2e-5)
    _assert_states_match(st_d, st_c)


@pytest.mark.parametrize("loss_name", ["milnce", "sdtw_3"])
def test_train_step_parity_sgd_8way_vs_one_device(loss_name):
    """Two SGD steps on the 8-way mesh equal two on a one-device mesh
    (same global batch, sync BN so both see the same statistics), params
    leaf-for-leaf.  SGD on purpose: Adam is invariant to the gradient's
    scale, so the Adam parity cases above cannot see a loss-reduction
    transpose that multiplies every gradient by the mesh size (the psum
    fault ``psum_local_grad`` repairs); SGD moves the params by exactly
    that factor.  The DTW case pins the pmean-reduced family the same
    way (sdtw_3: its gamma=0.1 soft-min is smooth, where cdtw's 1e-5 is
    a hard min whose alignment path flips on f32 reduction-order noise).
    Tolerance: lr 0.05 scales f32 reduction-order noise to ~4e-5; an
    8x gradient would move the params by ~1e-2."""
    sgd = OptimConfig(name="sgd", lr=0.05, warmup_steps=1)
    loss_cfg = LossConfig(name=loss_name)
    l8, st8 = _train(loss_cfg, optim_cfg=sgd)
    l1, st1 = _train(loss_cfg, optim_cfg=sgd, n_devices=1)
    np.testing.assert_allclose(l8, l1, rtol=2e-4, atol=2e-5)
    for a, b in zip(jax.tree_util.tree_leaves(st8.params),
                    jax.tree_util.tree_leaves(st1.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)
    moved = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(st1.params),
        jax.tree_util.tree_leaves(_train(loss_cfg, optim_cfg=sgd,
                                         n_devices=1, n_steps=0)[1].params)))
    assert moved > 1e-3, f"SGD barely moved the params ({moved}): no teeth"


def test_train_step_parity_dense_vs_chunked_2d():
    """The 4x2 FSDP twin: the chunked loss under the 2-D step (negatives
    gathered over BOTH axes, grads through the per-leaf
    psum_scatter+psum reduction) trains identically to the dense 2-D
    step.  (The grad-cache composition — the chunk scan inside the
    loss-of-cached-embeddings stage — is pinned structurally by the
    scan-reduction-free check on the traced grad-cache program and by
    grad-cache's own dense parity in test_train.py; re-compiling two
    more full step programs here bought nothing those pins don't.)"""
    dense, st_d = _train(None, two_d=True)
    chunked, st_c = _train(_CHUNKED, two_d=True)
    np.testing.assert_allclose(chunked, dense, rtol=2e-4, atol=2e-5)
    _assert_states_match(st_d, st_c)
