"""Weights from the seed: the benchmark's own, handed to the program as
its input (a checkpoint to resume from, an export to serve) and to the
plain reference alike.  Nothing here comes from ``milnce_tpu``: shapes
follow from the configuration's sizes and the S3D-G channel plan.

One jitted call makes every leaf on the device, in float32 (the type the
parameters are kept in; the activations' type is the model's business).
"""

from __future__ import annotations

import zlib

import numpy as np

from benchmarks.reference.s3dg_milnce import INCEPTION_PLAN


def weight_shapes(model: dict) -> dict:
    """name -> shape for every parameter of the configuration ``model``
    (the ``model`` group of a file under ``benchmarks/configs``)."""
    shapes = {}

    def conv(prefix, conv_name, bn_name, kernel, cin, cout):
        shapes[f"{prefix}/{conv_name}/kernel"] = tuple(kernel) + (cin, cout)
        shapes[f"{prefix}/{bn_name}/scale"] = (cout,)
        shapes[f"{prefix}/{bn_name}/bias"] = (cout,)

    def st_conv(prefix, kernel, cin, cout, separable=False):
        if separable:
            conv(prefix, "conv_spatial", "bn_spatial",
                 (1, kernel[1], kernel[2]), cin, cout)
            conv(prefix, "conv_temporal", "bn_temporal",
                 (kernel[0], 1, 1), cout, cout)
        else:
            conv(prefix, "conv", "bn", kernel, cin, cout)

    def dense(prefix, cin, cout):
        shapes[f"{prefix}/kernel"] = (cin, cout)
        shapes[f"{prefix}/bias"] = (cout,)

    st_conv("conv1", (3, 7, 7), 3, 64)
    st_conv("conv_2b", (1, 1, 1), 64, 64)
    st_conv("conv_2c", (3, 3, 3), 64, 192, separable=True)
    dense("gating/fc", 192, 192)
    cin = 192
    for name, (c0, c1a, c1b, c2a, c2b, c3b) in \
            INCEPTION_PLAN[:model["inception_blocks"]]:
        st_conv(f"{name}/conv_b0", (1, 1, 1), cin, c0)
        st_conv(f"{name}/conv_b1_a", (1, 1, 1), cin, c1a)
        st_conv(f"{name}/conv_b1_b", (3, 3, 3), c1a, c1b, separable=True)
        st_conv(f"{name}/conv_b2_a", (1, 1, 1), cin, c2a)
        st_conv(f"{name}/conv_b2_b", (3, 3, 3), c2a, c2b, separable=True)
        st_conv(f"{name}/conv_b3_b", (1, 1, 1), cin, c3b)
        for i, c in enumerate((c0, c1b, c2b, c3b)):
            dense(f"{name}/gating_b{i}/fc", c, c)
        cin = c0 + c1b + c2b + c3b
    dense("fc", cin, model["embedding_dim"])
    shapes["text_module/word_embd/embedding"] = (
        model["vocab_size"], model["word_embedding_dim"])
    dense("text_module/fc1", model["word_embedding_dim"],
          model["text_hidden_dim"])
    dense("text_module/fc2", model["text_hidden_dim"],
          model["embedding_dim"])
    return shapes


def _leaf(key, name, shape):
    import jax
    import jax.numpy as jnp

    normal = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("/embedding"):
        return normal                           # word2vec-like, unit scale
    if name.endswith("/kernel"):
        fan_in = int(np.prod(shape[:-1]))
        return normal * (1.0 / fan_in) ** 0.5
    if name.endswith("/scale"):
        return 1.0 + 0.1 * normal
    return 0.05 * normal                        # biases


def make_weights(seed: int, shapes: dict) -> dict:
    """Every leaf in one jitted program, keyed by the seed and the
    leaf's name (a leaf's values do not depend on which others exist)."""
    import jax

    names = sorted(shapes)

    def build(key):
        return {n: _leaf(jax.random.fold_in(key, zlib.crc32(n.encode())
                                            & 0x7FFFFFFF), n, shapes[n])
                for n in names}

    return jax.jit(build)(jax.random.PRNGKey(seed % (2 ** 31 - 1)))


def nest(flat: dict) -> dict:
    """``a/b/c`` keys -> nested dicts (the program's parameter tree)."""
    out: dict = {}
    for name, value in flat.items():
        node = out
        parts = name.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = value
    return out


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        name = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flatten(v, name))
        else:
            out[name] = v
    return out


def batch_stats_for(flat: dict) -> dict:
    """Fresh running statistics (mean 0, variance 1) for every batch
    norm among ``flat``'s names, as a flat dict."""
    import jax.numpy as jnp

    out = {}
    for name, value in flat.items():
        if name.endswith("/scale"):
            base = name[:-len("/scale")]
            out[f"{base}/mean"] = jnp.zeros(value.shape, jnp.float32)
            out[f"{base}/var"] = jnp.ones(value.shape, jnp.float32)
    return out
