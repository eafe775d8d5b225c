"""S3D-G model shape/behavior tests (hermetic, CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from milnce_tpu.models import S3D
from jax import set_mesh, shard_map
from milnce_tpu.models.s3dg import space_to_depth, _tf_same_max_pool


def tiny_model(**kw):
    defaults = dict(num_classes=32, vocab_size=64, word_embedding_dim=8,
                    text_hidden_dim=16)
    defaults.update(kw)
    return S3D(**defaults)


@pytest.fixture(scope="module")
def model_and_vars():
    model = tiny_model()
    video = jnp.zeros((2, 4, 32, 32, 3), jnp.float32)
    text = jnp.zeros((2, 6), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), video, text)
    return model, variables


def test_forward_all_shapes(model_and_vars):
    model, variables = model_and_vars
    video = jnp.ones((2, 4, 32, 32, 3), jnp.float32) * 0.5
    text = jnp.ones((4, 6), jnp.int32)  # B*K flattened rows, K=2
    v, t = model.apply(variables, video, text)
    assert v.shape == (2, 32)
    assert t.shape == (4, 32)


def test_mixed5c_features_are_1024d(model_and_vars):
    model, variables = model_and_vars
    video = jnp.ones((1, 4, 32, 32, 3), jnp.float32)
    feats = model.apply(variables, video, None, mode="video", mixed5c=True)
    assert feats.shape == (1, 1024)  # mixed_5c output dim (s3dg.py:233)


def test_text_only_mode(model_and_vars):
    model, variables = model_and_vars
    out = model.apply(variables, None, jnp.zeros((3, 6), jnp.int32), mode="text")
    assert out.shape == (3, 32)


@pytest.mark.slow
def test_train_mode_updates_batch_stats(model_and_vars):
    model, variables = model_and_vars
    video = jnp.ones((2, 4, 32, 32, 3), jnp.float32)
    text = jnp.zeros((2, 6), jnp.int32)
    _, mutated = model.apply(variables, video, text, train=True,
                             mutable=["batch_stats"])
    old = variables["batch_stats"]["conv1"]["bn"]["mean"]
    new = mutated["batch_stats"]["conv1"]["bn"]["mean"]
    assert not np.allclose(np.asarray(old), np.asarray(new))


def test_gating_flag_actually_disables_gating():
    """The reference cannot disable gating (s3dg.py:212/220 overwrite bug,
    SURVEY.md §2.4); ours must."""
    m = tiny_model(gating=False)
    v = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 32, 32, 3)),
               jnp.zeros((1, 6), jnp.int32))
    flat = jax.tree_util.tree_leaves_with_path(v["params"])
    names = ["/".join(str(k.key) for k in path) for path, _ in flat]
    assert not any("gating" in n for n in names)


@pytest.mark.slow
def test_text_embedding_gradient_is_zero(model_and_vars):
    """word2vec table is frozen via stop_gradient (s3dg.py:199-200)."""
    model, variables = model_and_vars

    def loss_fn(params):
        out = model.apply({**variables, "params": params},
                          None, jnp.ones((2, 6), jnp.int32), mode="text")
        return jnp.sum(out ** 2)

    grads = jax.grad(loss_fn)(variables["params"])
    emb_grad = grads["text_module"]["word_embd"]["embedding"]
    assert np.allclose(np.asarray(emb_grad), 0.0)
    fc1_grad = grads["text_module"]["fc1"]["kernel"]
    assert not np.allclose(np.asarray(fc1_grad), 0.0)


def test_space_to_depth_layout():
    x = jnp.arange(2 * 4 * 4 * 4 * 3, dtype=jnp.float32).reshape(2, 4, 4, 4, 3)
    y = space_to_depth(x)
    assert y.shape == (2, 2, 2, 2, 24)
    # channel order is (t2, h2, w2, c): channel 0 at output (t,h,w) must be
    # input (2t, 2h, 2w, 0)
    np.testing.assert_allclose(y[0, 1, 1, 1, 0], x[0, 2, 2, 2, 0])
    # last channel = (t2=1, h2=1, w2=1, c=2) -> input (2t+1, 2h+1, 2w+1, 2)
    np.testing.assert_allclose(y[0, 0, 0, 0, 23], x[0, 1, 1, 1, 2])


@pytest.mark.slow
def test_space_to_depth_model_shapes():
    m = tiny_model(use_space_to_depth=True)
    video = jnp.zeros((1, 8, 64, 64, 3), jnp.float32)
    text = jnp.zeros((1, 6), jnp.int32)
    variables = m.init(jax.random.PRNGKey(0), video, text)
    v, t = m.apply(variables, video, text)
    assert v.shape == (1, 32)


def _naive_ref_maxpool_1d(row, k, s):
    """Reference MaxPool3dTFPadding semantics (s3dg.py:114-146): pad
    max(k-s,0) low-first, then ceil-mode pooling (zero pad; inputs >=0)."""
    pad_along = max(k - s, 0)
    lo, hi = pad_along // 2, pad_along - pad_along // 2
    padded = np.concatenate([np.zeros(lo), row, np.zeros(hi)])
    out_len = -(-(len(padded) - k) // s) + 1
    return np.array([padded[i * s: i * s + k].max() for i in range(out_len)])


@pytest.mark.parametrize("length", [5, 6, 7, 8])
def test_tf_same_maxpool_matches_reference_semantics(length):
    rng = np.random.RandomState(0)
    # odd lengths are where XLA 'SAME' and the reference's padding differ
    x = rng.rand(1, 1, 1, length, 1).astype(np.float32)
    out = _tf_same_max_pool(jnp.asarray(x), (1, 1, 3), (1, 1, 2))
    expected = _naive_ref_maxpool_1d(x[0, 0, 0, :, 0], 3, 2)
    np.testing.assert_allclose(np.asarray(out)[0, 0, 0, :, 0], expected)


def test_sync_batchnorm_merges_stats_across_shards():
    """bn_axis_name='data' (model.sync_batchnorm — the original TPU run's
    cross-replica BN, README.md:13 flips the trade-off on TPU): batch
    stats computed under shard_map over sharded data must equal the
    stats of the FULL batch, unlike local BN which sees only its shard."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from milnce_tpu.models.s3dg import STConv3D

    devices = jax.devices()
    mesh = Mesh(np.array(devices), ("data",))
    b, t, hw, cin = 16, 2, 4, 3
    rng = np.random.RandomState(0)
    # per-shard means differ: scale each sample by its index
    x = (rng.rand(b, t, hw, hw, cin) * np.arange(1, b + 1)[:, None, None,
                                                          None, None]
         ).astype(np.float32)

    sync = STConv3D(features=4, kernel_size=(1, 1, 1), bn_axis_name="data")
    variables = sync.init(jax.random.PRNGKey(0), jnp.zeros((2, t, hw, hw, cin)))

    @jax.jit
    def sharded_stats(x):
        def local(xs):
            _, mut = sync.apply(variables, xs, train=True,
                                mutable=["batch_stats"])
            return mut["batch_stats"]

        return shard_map(local, mesh=mesh, in_specs=P("data"),
                             out_specs=P(), check_vma=False)(x)

    with set_mesh(mesh):
        stats_sharded = sharded_stats(
            jax.device_put(x, NamedSharding(mesh, P("data"))))

    # reference: local BN over the WHOLE batch in one program
    local_mod = STConv3D(features=4, kernel_size=(1, 1, 1))
    _, mut_full = local_mod.apply(variables, jnp.asarray(x), train=True,
                                  mutable=["batch_stats"])
    np.testing.assert_allclose(
        np.asarray(stats_sharded["bn"]["mean"]),
        np.asarray(mut_full["batch_stats"]["bn"]["mean"]), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(stats_sharded["bn"]["var"]),
        np.asarray(mut_full["batch_stats"]["bn"]["var"]), rtol=1e-4)


class TestConv3DFold2D:
    """fold2d lowers every trunk conv shape as 2D convolutions with an
    IDENTICAL parameter layout (models/conv3d.py) — outputs must match
    the native 3D lowering to numerical noise."""

    # (kernel, strides, padding) — every distinct conv shape in the trunk
    SHAPES = [
        ((1, 1, 1), (1, 1, 1), (0, 0, 0)),       # pointwise branches
        ((1, 3, 3), (1, 1, 1), (0, 1, 1)),       # separable spatial
        ((3, 1, 1), (1, 1, 1), (1, 0, 0)),       # separable temporal
        ((1, 7, 7), (1, 2, 2), (0, 3, 3)),       # strided spatial
        ((3, 7, 7), (2, 2, 2), (1, 3, 3)),       # conv1 stem (full 3D)
        ((2, 4, 4), (1, 1, 1), (1, 2, 2)),       # s2d stem (even kernel)
    ]

    @pytest.mark.parametrize("kernel,strides,padding", SHAPES)
    def test_matches_native(self, kernel, strides, padding):
        from milnce_tpu.models.conv3d import Conv3D

        x = jnp.asarray(np.random.RandomState(0)
                        .randn(2, 5, 12, 12, 6).astype(np.float32))
        kw = dict(features=8, kernel_size=kernel, strides=strides,
                  padding=padding)
        native = Conv3D(impl="native", **kw)
        params = native.init(jax.random.PRNGKey(1), x)
        ref = native.apply(params, x)
        out = Conv3D(impl="fold2d", **kw).apply(params, x)
        assert out.shape == ref.shape
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.slow
    def test_full_model_parity(self):
        """Whole S3D-G forward agrees across conv impls on the same
        variables (the param trees are layout-identical by design)."""
        video = jnp.asarray(np.random.RandomState(0)
                            .rand(2, 4, 32, 32, 3).astype(np.float32))
        text = jnp.zeros((2, 6), jnp.int32)
        native = tiny_model()
        variables = native.init(jax.random.PRNGKey(0), video, text)
        v_ref, t_ref = native.apply(variables, video, text)
        v_out, t_out = tiny_model(conv_impl="fold2d").apply(
            variables, video, text)
        np.testing.assert_allclose(np.asarray(v_out), np.asarray(v_ref),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(t_out), np.asarray(t_ref),
                                   rtol=1e-4, atol=1e-4)


class TestConv3DIm2col:
    """im2col lowers every trunk conv shape as patch extraction + one
    dot_general with an IDENTICAL parameter layout (models/conv3d.py);
    its custom VJP keeps dW and dX in matmul form — so BOTH the forward
    and the gradients must match the native 3D lowering."""

    # the two stem shapes the impl was built for, plus every other
    # distinct trunk conv shape
    STEM_SHAPES = [
        ((3, 7, 7), (2, 2, 2), (1, 3, 3)),       # conv1 stem (full 3D)
        ((2, 4, 4), (1, 1, 1), (1, 2, 2)),       # s2d stem (even kernel)
    ]
    SHAPES = STEM_SHAPES + [
        ((1, 1, 1), (1, 1, 1), (0, 0, 0)),       # pointwise branches
        ((1, 3, 3), (1, 1, 1), (0, 1, 1)),       # separable spatial
        ((3, 1, 1), (1, 1, 1), (1, 0, 0)),       # separable temporal
        ((1, 7, 7), (1, 2, 2), (0, 3, 3)),       # strided spatial
    ]

    @pytest.mark.parametrize("kernel,strides,padding", SHAPES)
    def test_forward_matches_native(self, kernel, strides, padding):
        from milnce_tpu.models.conv3d import Conv3D

        x = jnp.asarray(np.random.RandomState(0)
                        .randn(2, 5, 12, 12, 6).astype(np.float32))
        kw = dict(features=8, kernel_size=kernel, strides=strides,
                  padding=padding)
        native = Conv3D(impl="native", **kw)
        params = native.init(jax.random.PRNGKey(1), x)
        ref = native.apply(params, x)
        out = Conv3D(impl="im2col", **kw).apply(params, x)
        assert out.shape == ref.shape
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("kernel,strides,padding", SHAPES)
    def test_gradients_match_native(self, kernel, strides, padding):
        """Parameter AND input gradients of the custom VJP vs native
        autodiff at EVERY trunk conv shape — the backward is where the
        measured MFU sink lives (PERF.md), and the autotuner may pick
        im2col for any stage, so no shape's VJP goes unguarded."""
        from milnce_tpu.models.conv3d import Conv3D

        rng = np.random.RandomState(2)
        x = jnp.asarray(rng.randn(2, 5, 12, 12, 6).astype(np.float32))
        kw = dict(features=8, kernel_size=kernel, strides=strides,
                  padding=padding)
        params = Conv3D(impl="native", **kw).init(jax.random.PRNGKey(1), x)
        cot = jnp.asarray(rng.randn(
            *Conv3D(impl="native", **kw).apply(params, x).shape)
            .astype(np.float32))

        def loss(p, xx, impl):
            # a random cotangent (via the elementwise product) exercises
            # every output position's contribution to both grads
            return jnp.sum(Conv3D(impl=impl, **kw).apply(p, xx) * cot)

        gp_ref, gx_ref = jax.grad(loss, argnums=(0, 1))(params, x, "native")
        gp, gx = jax.grad(loss, argnums=(0, 1))(params, x, "im2col")
        np.testing.assert_allclose(
            np.asarray(gp["params"]["kernel"]),
            np.asarray(gp_ref["params"]["kernel"]), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_ref),
                                   rtol=1e-4, atol=1e-4)

    def test_unknown_impl_raises(self):
        from milnce_tpu.models.conv3d import Conv3D

        x = jnp.zeros((1, 3, 8, 8, 2), jnp.float32)
        conv = Conv3D(features=4, kernel_size=(1, 1, 1), impl="wat")
        with pytest.raises(ValueError, match="unknown conv impl"):
            conv.init(jax.random.PRNGKey(0), x)


class TestConvImplMap:
    """Per-stage impl map threading: S3D resolves (stage, impl) pairs at
    probe granularity, param trees stay identical, unnamed stages fall
    back to the uniform conv_impl."""

    def test_map_overrides_resolve_per_stage(self):
        m = tiny_model(conv_impl="fold2d",
                       conv_impl_map=(("conv1", "im2col"),
                                      ("mixed_4d", "native")))
        video = jnp.zeros((1, 4, 32, 32, 3), jnp.float32)
        text = jnp.zeros((1, 6), jnp.int32)
        variables = m.init(jax.random.PRNGKey(0), video, text)
        bound = m.bind(variables)
        assert bound.conv1.conv_impl == "im2col"
        assert bound.mixed_4d.conv_impl == "native"
        # unnamed stages keep the uniform default
        assert bound.conv_2c.conv_impl == "fold2d"
        assert bound.mixed_3b.conv_impl == "fold2d"

    def test_mapped_model_matches_native_forward(self):
        video = jnp.asarray(np.random.RandomState(0)
                            .rand(1, 4, 32, 32, 3).astype(np.float32))
        text = jnp.zeros((1, 6), jnp.int32)
        native = tiny_model()
        variables = native.init(jax.random.PRNGKey(0), video, text)
        v_ref, _ = native.apply(variables, video, text)
        mapped = tiny_model(conv_impl_map=(("conv1", "im2col"),
                                           ("mixed_3b", "fold2d")))
        v_out, _ = mapped.apply(variables, video, text)
        np.testing.assert_allclose(np.asarray(v_out), np.asarray(v_ref),
                                   rtol=1e-4, atol=1e-4)
