"""The plain reference: S3D-G + the sentence tower + MIL-NCE + Adam.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
written from the published description (Xie et al. 2018, "Rethinking
Spatiotemporal Feature Learning", S3D-G; Miech et al. 2020, "End-to-End
Learning of Visual Representations from Uncurated Instructional Videos",
MIL-NCE) and the reference implementation's channel plan.  It imports
nothing of ``milnce_tpu`` and takes nothing the program made: weights are
the benchmark's (``benchmarks/weights.py``), keyed by the flat names
below, and inputs are uint8 clips and int32 token ids.

Weight names (``/``-joined): ``conv1/conv/kernel`` (t,h,w,in,out),
``conv1/bn/{scale,bias}``, ``conv_2b/...``, ``conv_2c/{conv_spatial,
bn_spatial,conv_temporal,bn_temporal}/...``, ``gating/fc/{kernel,bias}``,
``mixed_XX/{conv_b0,conv_b1_a,conv_b1_b,conv_b2_a,conv_b2_b,conv_b3_b}``,
``mixed_XX/gating_b{0..3}/fc``, ``fc``, ``text_module/{word_embd/
embedding,fc1,fc2}``.

``precision`` selects what the matmuls and convolutions see:

- ``float32``: inputs as they are, ``Precision.HIGHEST`` — the reference;
- ``bfloat16``: conv/dense inputs rounded to bfloat16, float32
  accumulation — what the configurations state the program does;
- ``float8``: the same inputs rounded to ``float8_e4m3fn`` first — the
  control, one precision step below what the configurations state;
- ``bfloat16_stored``: as ``bfloat16``, and every layer's output and the
  loss's own arithmetic kept in bfloat16 too — what a model built with
  bfloat16 activations does.  Never a verdict: the look that says how
  much of the program's distance from the reference is its stated
  precision (PERF.md).

Departures from the published description, each on purpose: batch-norm
statistics are per *group* of rows (``bn_groups``: one group per chip,
the program's local batch norm); the word table is frozen (no gradient),
as in the MIL-NCE release.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

# (b0, b1a, b1b, b2a, b2b, b3b) output channels of the nine Inception
# blocks (S3D-G, Table 1 of the paper's supplement / the release)
INCEPTION_PLAN = (
    ("mixed_3b", (64, 96, 128, 16, 32, 32)),
    ("mixed_3c", (128, 128, 192, 32, 96, 64)),
    ("mixed_4b", (192, 96, 208, 16, 48, 64)),
    ("mixed_4c", (160, 112, 224, 24, 64, 64)),
    ("mixed_4d", (128, 128, 256, 24, 64, 64)),
    ("mixed_4e", (112, 144, 288, 32, 64, 64)),
    ("mixed_4f", (256, 160, 320, 32, 128, 128)),
    ("mixed_5b", (256, 160, 320, 32, 128, 128)),
    ("mixed_5c", (384, 192, 384, 48, 128, 128)),
)
BN_EPS = 1e-5
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
FROZEN = ("text_module/word_embd/embedding",)


def _round(x, precision):
    """``x`` with its values rounded to ``precision`` (the dtype stays
    float32, so the products of rounded values are exact and the
    accumulation float32).  Straight through in the backward: a
    cotangent is not rounded, or float8's narrow range would flush the
    gradients to nought and the control would give no number."""
    if precision == "float32":
        return x
    if precision in ("bfloat16", "bfloat16_stored"):
        low = x.astype(jnp.bfloat16)
    elif precision == "float8":
        low = x.astype(jnp.float8_e4m3fn)
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return x + lax.stop_gradient(low.astype(jnp.float32) - x)


def _stored(y, precision):
    """A layer's output as it is kept: rounded to bfloat16 and back
    where the activations are stored so."""
    if precision == "bfloat16_stored":
        return y.astype(jnp.bfloat16).astype(jnp.float32)
    return y


def _conv(x, kernel, strides, pads, precision):
    out = lax.conv_general_dilated(
        _round(x, precision), _round(kernel, precision), strides,
        [(p, p) for p in pads],
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC"),
        precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    return _stored(out, precision)


def _dense(x, w, prefix, precision):
    y = jnp.matmul(_round(x, precision), _round(w[f"{prefix}/kernel"],
                                                precision),
                   precision=lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)
    return _stored(y + w[f"{prefix}/bias"], precision)


def _batch_norm(x, scale, bias, groups):
    """Training-mode batch norm over (rows of a group, T, H, W)."""
    b = x.shape[0]
    xg = x.reshape((groups, b // groups) + x.shape[1:])
    mean = jnp.mean(xg, axis=(1, 2, 3, 4), keepdims=True)
    var = jnp.mean(jnp.square(xg), axis=(1, 2, 3, 4), keepdims=True) \
        - jnp.square(mean)
    y = (xg - mean) * lax.rsqrt(var + BN_EPS)
    return y.reshape(x.shape) * scale + bias


def _unit(x, w, prefix, conv, bn, strides, pads, groups, precision):
    y = _conv(x, w[f"{prefix}/{conv}/kernel"], strides, pads, precision)
    y = _batch_norm(y, w[f"{prefix}/{bn}/scale"], w[f"{prefix}/{bn}/bias"],
                    groups)
    return jax.nn.relu(_stored(y, precision))


def _st_conv(x, w, prefix, kernel, stride, pad, separable, groups,
             precision):
    """conv + BN + ReLU; separable = spatial (1,k,k) then temporal
    (t,1,1), each with its own BN + ReLU."""
    if separable:
        x = _unit(x, w, prefix, "conv_spatial", "bn_spatial",
                  (1, stride[1], stride[2]), (0, pad[1], pad[2]), groups,
                  precision)
        return _unit(x, w, prefix, "conv_temporal", "bn_temporal",
                     (stride[0], 1, 1), (pad[0], 0, 0), groups, precision)
    return _unit(x, w, prefix, "conv", "bn", stride, pad, groups, precision)


def _gate(x, w, prefix, precision):
    g = jax.nn.sigmoid(_dense(jnp.mean(x, axis=(1, 2, 3)), w,
                              f"{prefix}/fc", precision))
    return _stored(x * g[:, None, None, None, :], precision)


def _max_pool_tf_same(x, window, strides):
    """TF-'SAME' max pooling as the release emulates it: pad each dim by
    max(k - s, 0), low half first, and keep the ceil-mode tail."""
    padding = [(0, 0)]
    for size, k, s in zip(x.shape[1:4], window, strides):
        along = max(k - s, 0)
        lo = along // 2
        hi = along - lo
        padding.append((lo, hi + (-(size + along - k)) % s))
    padding.append((0, 0))
    return lax.reduce_window(x, -jnp.inf, lax.max, (1,) + window + (1,),
                             (1,) + strides + (1,), padding)


def _inception(x, w, name, groups, precision):
    one, three = (1, 1, 1), (3, 3, 3)
    zero, pad1 = (0, 0, 0), (1, 1, 1)
    args = (groups, precision)
    b0 = _st_conv(x, w, f"{name}/conv_b0", one, one, zero, False, *args)
    b1 = _st_conv(x, w, f"{name}/conv_b1_a", one, one, zero, False, *args)
    b1 = _st_conv(b1, w, f"{name}/conv_b1_b", three, one, pad1, True, *args)
    b2 = _st_conv(x, w, f"{name}/conv_b2_a", one, one, zero, False, *args)
    b2 = _st_conv(b2, w, f"{name}/conv_b2_b", three, one, pad1, True, *args)
    b3 = _max_pool_tf_same(x, three, one)
    b3 = _st_conv(b3, w, f"{name}/conv_b3_b", one, one, zero, False, *args)
    outs = [_gate(b, w, f"{name}/gating_b{i}", precision)
            for i, b in enumerate((b0, b1, b2, b3))]
    return jnp.concatenate(outs, axis=-1)


def _stages(bn_groups, precision, blocks):
    """The video tower as a list of (weight prefixes, x, w -> x): one
    conv + BN + ReLU unit of the stem or one Inception block each, with
    the max-pool that follows folded in, so that what is kept between
    stages for the backward is the pooled tensor and not its input."""
    args = (bn_groups, precision)

    def conv1(x, w):
        x = x.astype(jnp.float32) / 255.0
        x = _st_conv(x, w, "conv1", (3, 7, 7), (2, 2, 2), (1, 3, 3), False,
                     *args)
        return _max_pool_tf_same(x, (1, 3, 3), (1, 2, 2))

    def conv_2b(x, w):
        return _st_conv(x, w, "conv_2b", (1, 1, 1), (1, 1, 1), (0, 0, 0),
                        False, *args)

    def conv_2c_spatial(x, w):
        return _unit(x, w, "conv_2c", "conv_spatial", "bn_spatial",
                     (1, 1, 1), (0, 1, 1), *args)

    def conv_2c_temporal(x, w):
        x = _unit(x, w, "conv_2c", "conv_temporal", "bn_temporal",
                  (1, 1, 1), (1, 0, 0), *args)
        x = _gate(x, w, "gating", precision)
        return _max_pool_tf_same(x, (1, 3, 3), (1, 2, 2))

    stages = [(("conv1",), conv1), (("conv_2b",), conv_2b),
              (("conv_2c",), conv_2c_spatial),
              (("conv_2c", "gating"), conv_2c_temporal)]
    # maxpool_4a follows mixed_3c, maxpool_5a follows mixed_4f
    pools_after = {1: ((3, 3, 3), (2, 2, 2)), 6: ((2, 2, 2), (2, 2, 2))}
    for idx, (name, _) in enumerate(INCEPTION_PLAN[:blocks]):
        pool = pools_after.get(idx) if idx + 1 < blocks else None

        def block(x, w, name=name, pool=pool):
            x = _inception(x, w, name, *args)
            return x if pool is None else _max_pool_tf_same(x, *pool)

        stages.append(((name,), block))
    return stages


def _pick(w, prefixes):
    return {k: v for k, v in w.items()
            if any(k.startswith(p + "/") for p in prefixes)}


def video_embedding(w, video_u8, *, bn_groups=1, precision="float32",
                    blocks=9, remat=True):
    """(B, T, H, W, 3) uint8 -> (B, D), training-mode batch norm.  Each
    stage is rematerialised in the backward so that float32 fits."""
    x = video_u8
    for prefixes, stage in _stages(bn_groups, precision, blocks):
        fn = jax.checkpoint(stage) if remat else stage
        x = fn(x, _pick(w, prefixes))
    return _dense(jnp.mean(x, axis=(1, 2, 3)), w, "fc", precision)


def text_embedding(w, token_ids, *, precision="float32"):
    """(N, W) int32 -> (N, D): frozen word table -> dense -> ReLU -> max
    over the words (pad id 0 takes part, as in the release) -> dense."""
    table = lax.stop_gradient(w["text_module/word_embd/embedding"])
    x = jnp.take(table, token_ids, axis=0)
    x = jax.nn.relu(_dense(x, w, "text_module/fc1", precision))
    return _dense(jnp.max(x, axis=1), w, "text_module/fc2", precision)


def milnce_loss(v, t, *, precision="float32"):
    """MIL-NCE over the whole batch: v (B, D), t (B*K, D) sample-major.
    loss = mean_i [ lse(row i and column i of the cube) - lse_k x[i,i,k] ]
    (the positives are in both the row and the column, as released)."""
    b = v.shape[0]
    x = jnp.matmul(_round(v, precision), _round(t, precision).T,
                   precision=lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32).reshape(b, b, -1)
    if precision == "bfloat16_stored":      # the loss's own arithmetic
        x = x.astype(jnp.bfloat16)
    diag = x[jnp.arange(b), jnp.arange(b), :]
    numerator = jax.nn.logsumexp(diag, axis=1)
    rows = x.reshape(b, -1)
    cols = jnp.swapaxes(x, 0, 1).reshape(b, -1)
    denominator = jax.nn.logsumexp(jnp.concatenate([rows, cols], axis=1),
                                   axis=1)
    return jnp.mean(denominator - numerator).astype(jnp.float32)


def loss_fn(w, video_u8, token_ids, *, bn_groups=1, precision="float32",
            blocks=9, remat=True):
    v = video_embedding(w, video_u8, bn_groups=bn_groups,
                        precision=precision, blocks=blocks, remat=remat)
    t = text_embedding(w, token_ids, precision=precision)
    return milnce_loss(v, t, precision=precision)


def lr_at(step, base_lr, warmup, total, cycles=0.5):
    """Linear warm-up then cosine, a function of the update's index
    (0-based: the first update uses lr_at(0) = 0 when warmup > 0)."""
    step = jnp.asarray(step, jnp.float32)
    progress = (step - warmup) / jnp.maximum(1.0, total - warmup)
    cosine = jnp.maximum(
        0.0, 0.5 * (1.0 + jnp.cos(jnp.pi * cycles * 2.0 * progress)))
    return base_lr * jnp.where(step < warmup,
                               step / jnp.maximum(1.0, warmup), cosine)


def adam_update(w, grads, mu, nu, count, lr):
    """One Adam update (Kingma & Ba, bias-corrected, eps outside the
    root) of every leaf but the frozen ones.  ``count`` is 0-based."""
    new_w, new_mu, new_nu = {}, {}, {}
    c = count + 1
    for k in w:
        if k in FROZEN:
            new_w[k], new_mu[k], new_nu[k] = w[k], mu[k], nu[k]
            continue
        g = grads[k]
        m = ADAM_B1 * mu[k] + (1 - ADAM_B1) * g
        v = ADAM_B2 * nu[k] + (1 - ADAM_B2) * jnp.square(g)
        m_hat = m / (1 - ADAM_B1 ** c)
        v_hat = v / (1 - ADAM_B2 ** c)
        new_w[k] = w[k] - lr * m_hat / (jnp.sqrt(v_hat) + ADAM_EPS)
        new_mu[k], new_nu[k] = m, v
    return new_w, new_mu, new_nu


def make_train_step(*, bn_groups=1, precision="float32", blocks=9,
                    base_lr=1e-3, warmup=100, total=10 ** 9, remat=True,
                    keep_rows=None):
    """-> jitted ``step(w, mu, nu, count, video_u8, token_ids) ->
    (w, mu, nu, loss, grads)``.  ``keep_rows`` (a fault for the tests and
    the fault readings): the loss is the mean over the first
    ``keep_rows`` rows only, the rest of the batch left out."""

    def step(w, mu, nu, count, video_u8, token_ids):
        if keep_rows is not None:
            k = token_ids.shape[0] // video_u8.shape[0]
            video_u8, token_ids = (video_u8[:keep_rows],
                                   token_ids[:keep_rows * k])
        loss, grads = jax.value_and_grad(loss_fn)(
            w, video_u8, token_ids, bn_groups=bn_groups,
            precision=precision, blocks=blocks, remat=remat)
        lr = lr_at(count, base_lr, warmup, total)
        w2, mu2, nu2 = adam_update(w, grads, mu, nu, count, lr)
        return w2, mu2, nu2, loss, grads

    # the weights and both moments are consumed and returned: donated,
    # so that a float32 step at the cells' sizes fits beside nothing else
    return jax.jit(step, donate_argnums=(0, 1, 2))
