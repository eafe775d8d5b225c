"""S3D-G (Gated Separable-3D Inception) video tower, TPU-native.

A ground-up Flax re-design of the capability of the reference model
(/root/reference/s3dg.py:11-328).  Differences from the reference are
deliberate TPU-first choices, not omissions:

- **Channels-last** ``(B, T, H, W, C)`` layout: XLA:TPU tiles NDHWC convs
  straight onto the MXU (the reference is NCDHW for cuDNN).
- 3D convolutions via ``flax.linen.Conv`` -> ``lax.conv_general_dilated``
  (MXU); no cuDNN benchmark flags needed — XLA autotunes.
- TF-SAME max-pooling via ``nn.max_pool(..., padding='SAME')``; the
  reference emulates TF-SAME by hand with ConstantPad3d(0)+ceil_mode
  (s3dg.py:114-146).  Padding with ``-inf`` (ours) equals padding with 0
  (theirs) because every pooled tensor here is post-ReLU/post-sigmoid-gate,
  hence non-negative.
- BatchNorm is either local (parity with the GPU reference, README.md:13)
  or cross-replica over a mesh axis (``axis_name='data'``) as in the
  original DeepMind TPU run — a flag, not a fork.
- The reference cannot actually disable gating (`self.gating` is
  overwritten with a module at s3dg.py:220, making the flag always truthy
  — SURVEY.md §2.4); here ``gating=False`` genuinely disables it.

Parameter-shape map to the reference (for checkpoint conversion):
torch ``Conv3d.weight (O, I, t, h, w)`` <-> flax ``kernel (t, h, w, I, O)``.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax

from milnce_tpu.models.conv3d import Conv3D
from milnce_tpu.models.initializers import (kernel_init_for,
                                            torch_bias,
                                            torch_default_kernel)
from milnce_tpu.models.text import SentenceEmbedding

Array = jax.Array


def _triple(v) -> Tuple[int, int, int]:
    if isinstance(v, (tuple, list)):
        assert len(v) == 3
        return tuple(int(x) for x in v)
    return (int(v),) * 3


class SelfGating(nn.Module):
    """Feature gating, the "G" in S3D-G (reference s3dg.py:47-59):
    squeeze over (T,H,W) -> dense -> sigmoid -> channel rescale.

    Dense layers keep the torch-default kernel/bias init in both init
    modes — the reference's kaiming_normal branch re-inits only Conv3d
    and BatchNorm (s3dg.py:240-246), leaving Linears at torch defaults.
    """

    kernel_init: Callable = torch_default_kernel()
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: Array) -> Array:
        squeezed = jnp.mean(x, axis=(1, 2, 3))
        weights = nn.Dense(x.shape[-1], kernel_init=torch_default_kernel(),
                           bias_init=torch_bias(x.shape[-1]),
                           dtype=self.dtype, name="fc")(squeezed)
        weights = jax.nn.sigmoid(weights)
        return weights[:, None, None, None, :] * x


class STConv3D(nn.Module):
    """(Optionally separable) spatio-temporal conv + BN + ReLU
    (reference s3dg.py:61-111).

    ``separable=True`` factorizes a (t,k,k) kernel into a spatial (1,k,k)
    conv followed by a temporal (t,1,1) conv, each with its own BN+ReLU.
    Padding is torch-style symmetric (explicit per-dim), matching the
    reference's nn.Conv3d semantics exactly.
    """

    features: int
    kernel_size: Sequence[int]
    strides: Sequence[int] | int = 1
    padding: Sequence[int] | int = 0
    separable: bool = False
    bn_axis_name: Optional[str] = None
    kernel_init: Callable = nn.initializers.lecun_normal()
    conv_impl: str = "native"
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: Array, train: bool = False) -> Array:
        k = _triple(self.kernel_size)
        s = _triple(self.strides)
        p = _triple(self.padding)

        def conv(y, feat, kern, stride, pad, name):
            return Conv3D(
                feat, kernel_size=kern, strides=stride, padding=pad,
                impl=self.conv_impl, kernel_init=self.kernel_init,
                dtype=self.dtype, name=name,
            )(y)

        def bn(y, name):
            return nn.BatchNorm(
                use_running_average=not train, momentum=0.9, epsilon=1e-5,
                axis_name=self.bn_axis_name if train else None,
                dtype=self.dtype, name=name,
            )(y)

        if self.separable and k[0] != 1:
            x = conv(x, self.features, (1, k[1], k[2]), (1, s[1], s[2]),
                     (0, p[1], p[2]), "conv_spatial")
            x = nn.relu(bn(x, "bn_spatial"))
            x = conv(x, self.features, (k[0], 1, 1), (s[0], 1, 1),
                     (p[0], 0, 0), "conv_temporal")
            x = nn.relu(bn(x, "bn_temporal"))
        else:
            x = conv(x, self.features, k, s, p, "conv")
            x = nn.relu(bn(x, "bn"))
        return x


class InceptionBlock(nn.Module):
    """Four-branch 3D Inception block with per-branch self-gating
    (reference s3dg.py:11-45).

    Branches: (0) 1x1x1; (1) 1x1x1 -> separable 3x3x3; (2) same as (1);
    (3) 3x3x3 maxpool stride 1 -> 1x1x1.  Channel-concat at the end.
    """

    num_outputs_0_0a: int
    num_outputs_1_0a: int
    num_outputs_1_0b: int
    num_outputs_2_0a: int
    num_outputs_2_0b: int
    num_outputs_3_0b: int
    gating: bool = True
    bn_axis_name: Optional[str] = None
    kernel_init: Callable = nn.initializers.lecun_normal()
    conv_impl: str = "native"
    dtype: Any = jnp.float32

    @property
    def output_dim(self) -> int:
        return (self.num_outputs_0_0a + self.num_outputs_1_0b
                + self.num_outputs_2_0b + self.num_outputs_3_0b)

    @nn.compact
    def __call__(self, x: Array, train: bool = False) -> Array:
        common = dict(bn_axis_name=self.bn_axis_name,
                      kernel_init=self.kernel_init,
                      conv_impl=self.conv_impl, dtype=self.dtype)
        b0 = STConv3D(self.num_outputs_0_0a, (1, 1, 1), name="conv_b0",
                      **common)(x, train)
        b1 = STConv3D(self.num_outputs_1_0a, (1, 1, 1), name="conv_b1_a",
                      **common)(x, train)
        b1 = STConv3D(self.num_outputs_1_0b, (3, 3, 3), padding=1,
                      separable=True, name="conv_b1_b", **common)(b1, train)
        b2 = STConv3D(self.num_outputs_2_0a, (1, 1, 1), name="conv_b2_a",
                      **common)(x, train)
        b2 = STConv3D(self.num_outputs_2_0b, (3, 3, 3), padding=1,
                      separable=True, name="conv_b2_b", **common)(b2, train)
        # stride-1 3x3x3 maxpool w/ symmetric pad 1 == SAME padding.
        b3 = nn.max_pool(x, (3, 3, 3), strides=(1, 1, 1), padding="SAME")
        b3 = STConv3D(self.num_outputs_3_0b, (1, 1, 1), name="conv_b3_b",
                      **common)(b3, train)
        if self.gating:
            b0 = SelfGating(self.kernel_init, self.dtype, name="gating_b0")(b0)
            b1 = SelfGating(self.kernel_init, self.dtype, name="gating_b1")(b1)
            b2 = SelfGating(self.kernel_init, self.dtype, name="gating_b2")(b2)
            b3 = SelfGating(self.kernel_init, self.dtype, name="gating_b3")(b3)
        return jnp.concatenate([b0, b1, b2, b3], axis=-1)


def _tf_same_max_pool(x: Array, window: Tuple[int, int, int],
                      strides: Tuple[int, int, int]) -> Array:
    """Reference-exact "TF-SAME" 3D max-pool over (T,H,W) of NDHWC.

    The reference's MaxPool3dTFPadding (s3dg.py:114-146) pads each dim by
    ``max(k - s, 0)`` split low-first, then pools with ceil_mode.  For
    stride-divisible sizes that coincides with XLA 'SAME'; for odd sizes
    it does NOT (XLA SAME centers differently), so we reproduce the
    reference padding explicitly plus the ceil-mode tail.  Padding with
    ``-inf`` (window init value) equals the reference's zero-pad because
    every pooled tensor here is post-ReLU/gate, hence non-negative.
    """
    dims = (1,) + tuple(window) + (1,)
    strd = (1,) + tuple(strides) + (1,)
    padding = [(0, 0)]
    for size, k, s in zip(x.shape[1:4], window, strides):
        pad_along = max(k - s, 0)
        lo = pad_along // 2
        hi = pad_along - lo
        ceil_extra = (-(size + lo + hi - k)) % s      # ceil_mode tail
        padding.append((lo, hi + ceil_extra))
    padding.append((0, 0))
    return lax.reduce_window(x, -jnp.inf, lax.max, dims, strd, padding)


def space_to_depth(video: Array) -> Array:
    """2x2x2 space-to-depth stem rearrangement (reference s3dg.py:248-253),
    channels-last: (B,T,H,W,C) -> (B,T/2,H/2,W/2,8C) with channel order
    (t2,h2,w2,C) — matches the torch permute for checkpoint parity."""
    b, t, h, w, c = video.shape
    video = video.reshape(b, t // 2, 2, h // 2, 2, w // 2, 2, c)
    video = video.transpose(0, 1, 3, 5, 2, 4, 6, 7)
    return video.reshape(b, t // 2, h // 2, w // 2, 8 * c)


class S3D(nn.Module):
    """S3D-G two-tower model: video CNN + word2vec sentence tower
    (reference s3dg.py:207-328).

    ``__call__(video, text, mode, mixed5c, train)``:

    - video: (B, T, H, W, 3) float in [0, 1] (normalize on device).
    - text:  (B', max_words) int token ids.
    - mode 'all' -> (video_embd (B, D), text_embd (B', D));
      'video' -> video embedding (or 1024-d pooled mixed_5c features when
      ``mixed5c=True``, used by the linear probe — s3dg.py:323-325);
      'text' -> text embedding.
    """

    num_classes: int = 512
    gating: bool = True
    use_space_to_depth: bool = False
    inception_blocks: int = 9           # trunk depth: first N of the 9
                                        # Inception blocks (9 = reference
                                        # s3dg.py:223-233; smaller values
                                        # give cheap variants for dryruns)
    vocab_size: int = 66250
    word_embedding_dim: int = 300
    text_hidden_dim: int = 2048
    weight_init: str = "uniform"
    bn_axis_name: Optional[str] = None
    conv_impl: str = "native"           # 'native' 3D convs | 'fold2d' |
                                        # 'im2col' (see models/conv3d.py)
    conv_impl_map: Optional[Tuple[Tuple[str, str], ...]] = None
                                        # per-stage (stage, impl) overrides at
                                        # probe granularity (conv1, conv_2b,
                                        # conv_2c, mixed_*) — tuple of pairs,
                                        # not a dict, so the module stays
                                        # hashable; unnamed stages use
                                        # conv_impl.  build_model constructs
                                        # it from ModelConfig.conv_impl_map.
    embedding_init: Optional[Callable] = None
    remat: bool = False                 # rematerialize Inception blocks to
                                        # trade FLOPs for HBM at big batches
    text_lm: Optional[Any] = None       # models/text_lm.py LMDims: the
                                        # sentence tower is that language
                                        # model (None: the bag-of-words one)
    text_hybrid: Optional[Any] = None   # models/text_hybrid.py HybridDims:
                                        # the sentence tower is that one
    text_dlm: Optional[Any] = None      # models/text_dlm.py DLMDims: the
                                        # same
    dtype: Any = jnp.float32

    def setup(self):
        assert 1 <= self.inception_blocks <= 9, (
            f"inception_blocks must be in [1, 9], got {self.inception_blocks}")
        ki = kernel_init_for(self.weight_init)
        # per-stage impl resolution: the map (autotune output) wins over
        # the uniform conv_impl for the stages it names
        impl_map = dict(self.conv_impl_map or ())

        def impl(stage: str) -> str:
            return impl_map.get(stage, self.conv_impl)

        common = dict(bn_axis_name=self.bn_axis_name, kernel_init=ki,
                      dtype=self.dtype)
        block_cls = (nn.remat(InceptionBlock, static_argnums=(2,))
                     if self.remat else InceptionBlock)
        if self.use_space_to_depth:
            # reference s3dg.py:215 (+ the post-conv crop in forward_video)
            self.conv1 = STConv3D(64, (2, 4, 4), strides=1, padding=(1, 2, 2),
                                  conv_impl=impl("conv1"), name="conv1",
                                  **common)
        else:
            # reference s3dg.py:217
            self.conv1 = STConv3D(64, (3, 7, 7), strides=2, padding=(1, 3, 3),
                                  conv_impl=impl("conv1"), name="conv1",
                                  **common)
        self.conv_2b = STConv3D(64, (1, 1, 1), conv_impl=impl("conv_2b"),
                                name="conv_2b", **common)
        self.conv_2c = STConv3D(192, (3, 3, 3), padding=1, separable=True,
                                conv_impl=impl("conv_2c"), name="conv_2c",
                                **common)
        self.stem_gating = SelfGating(ki, self.dtype, name="gating")
        blocks = dict(gating=self.gating, **common)
        self.mixed_3b = block_cls(64, 96, 128, 16, 32, 32,
                                  conv_impl=impl("mixed_3b"),
                                  name="mixed_3b", **blocks)
        self.mixed_3c = block_cls(128, 128, 192, 32, 96, 64,
                                  conv_impl=impl("mixed_3c"),
                                  name="mixed_3c", **blocks)
        self.mixed_4b = block_cls(192, 96, 208, 16, 48, 64,
                                  conv_impl=impl("mixed_4b"),
                                  name="mixed_4b", **blocks)
        self.mixed_4c = block_cls(160, 112, 224, 24, 64, 64,
                                  conv_impl=impl("mixed_4c"),
                                  name="mixed_4c", **blocks)
        self.mixed_4d = block_cls(128, 128, 256, 24, 64, 64,
                                  conv_impl=impl("mixed_4d"),
                                  name="mixed_4d", **blocks)
        self.mixed_4e = block_cls(112, 144, 288, 32, 64, 64,
                                  conv_impl=impl("mixed_4e"),
                                  name="mixed_4e", **blocks)
        self.mixed_4f = block_cls(256, 160, 320, 32, 128, 128,
                                  conv_impl=impl("mixed_4f"),
                                  name="mixed_4f", **blocks)
        self.mixed_5b = block_cls(256, 160, 320, 32, 128, 128,
                                  conv_impl=impl("mixed_5b"),
                                  name="mixed_5b", **blocks)
        self.mixed_5c = block_cls(384, 192, 384, 48, 128, 128,
                                  conv_impl=impl("mixed_5c"),
                                  name="mixed_5c", **blocks)
        # Linear layers stay at torch defaults in both init modes
        # (s3dg.py:240-246 re-inits only convs/BN); fan-in = output dim of
        # the last active block (1024 for the full mixed_5c trunk).
        all_blocks = (self.mixed_3b, self.mixed_3c, self.mixed_4b,
                      self.mixed_4c, self.mixed_4d, self.mixed_4e,
                      self.mixed_4f, self.mixed_5b, self.mixed_5c)
        trunk_dim = all_blocks[self.inception_blocks - 1].output_dim
        self.fc = nn.Dense(self.num_classes, kernel_init=torch_default_kernel(),
                           bias_init=torch_bias(trunk_dim),
                           dtype=self.dtype, name="fc")
        if self.text_lm is not None:
            from milnce_tpu.models.text_lm import TextLM

            self.text_module = TextLM(self.text_lm,
                                      embd_dim=self.num_classes,
                                      dtype=self.dtype, name="text_module")
            return
        if self.text_hybrid is not None:
            from milnce_tpu.models.text_hybrid import TextHybrid

            self.text_module = TextHybrid(self.text_hybrid,
                                          embd_dim=self.num_classes,
                                          dtype=self.dtype,
                                          name="text_module")
            return
        if self.text_dlm is not None:
            from milnce_tpu.models.text_dlm import TextDLM

            self.text_module = TextDLM(self.text_dlm,
                                       embd_dim=self.num_classes,
                                       dtype=self.dtype, name="text_module")
            return
        self.text_module = SentenceEmbedding(
            embd_dim=self.num_classes,
            vocab_size=self.vocab_size,
            word_embedding_dim=self.word_embedding_dim,
            hidden_dim=self.text_hidden_dim,
            embedding_init=self.embedding_init,
            kernel_init=ki,
            dtype=self.dtype,
            name="text_module",
        )

    def _trunk(self, video: Array, train: bool) -> Array:
        """Conv trunk up to mixed_5c (B, T', H', W', 1024), mirrors
        reference s3dg.py:265-321."""
        net = video
        if self.use_space_to_depth:
            net = space_to_depth(net)
        net = self.conv1(net, train)
        if self.use_space_to_depth:
            net = net[:, 1:, 1:, 1:, :]  # s3dg.py:271-272
        net = _tf_same_max_pool(net, (1, 3, 3), (1, 2, 2))   # maxpool_2a
        net = self.conv_2b(net, train)
        net = self.conv_2c(net, train)
        if self.gating:
            net = self.stem_gating(net)
        net = _tf_same_max_pool(net, (1, 3, 3), (1, 2, 2))   # maxpool_3a
        blocks = (self.mixed_3b, self.mixed_3c, self.mixed_4b, self.mixed_4c,
                  self.mixed_4d, self.mixed_4e, self.mixed_4f, self.mixed_5b,
                  self.mixed_5c)
        # maxpool_4a before block idx 2, maxpool_5a before idx 7
        # (reference s3dg.py:223-233 ordering)
        pools_before = {2: ((3, 3, 3), (2, 2, 2)), 7: ((2, 2, 2), (2, 2, 2))}
        for idx, block in enumerate(blocks[:self.inception_blocks]):
            if idx in pools_before:
                win, strd = pools_before[idx]
                net = _tf_same_max_pool(net, win, strd)
            net = block(net, train)
        return net

    def forward_video(self, video: Array, mixed5c: bool = False,
                      train: bool = False) -> Array:
        """Pooled video embedding (reference s3dg.py:323-328)."""
        net = jnp.mean(self._trunk(video, train), axis=(1, 2, 3))
        if mixed5c:
            return net                                       # (B, 1024)
        return self.fc(net)                                  # (B, num_classes)

    def forward_video_sequence(self, video: Array,
                               train: bool = False) -> Array:
        """Temporal sequence of frame-group embeddings: pool mixed_5c over
        space only -> (B, T', num_classes).

        This is the sequence view the fork's (soft-)DTW losses align
        (loss.py:20-134 operate on (B, n, d) sequences); the reference
        never committed the model change that produces them — we make it a
        first-class mode.
        """
        net = jnp.mean(self._trunk(video, train), axis=(2, 3))
        return self.fc(net)

    def forward_text(self, tokens: Array) -> Array:
        return self.text_module(tokens)

    def __call__(self, video: Optional[Array], text: Optional[Array],
                 mode: str = "all", mixed5c: bool = False,
                 train: bool = False):
        if mode == "all":
            return self.forward_video(video, train=train), self.forward_text(text)
        if mode == "video":
            return self.forward_video(video, mixed5c=mixed5c, train=train)
        if mode == "text":
            return self.forward_text(text)
        if mode == "sequence":
            # (video seq (B, T', D), per-candidate text (B', D))
            return (self.forward_video_sequence(video, train=train),
                    self.forward_text(text))
        raise NotImplementedError(mode)
