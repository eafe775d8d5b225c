"""The plain reference against the program at a small size on the CPU,
all nine Inception blocks and both pools between them; the benchmark's
weights against the program's parameter tree; Adam and the schedule
against optax and the program's; the brute-force scan and its numbers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import compare, weights
from benchmarks.reference import retrieval
from benchmarks.reference import s3dg_milnce as reference

MODEL = dict(inception_blocks=9, embedding_dim=512, vocab_size=128,
             word_embedding_dim=300, text_hidden_dim=2048)


def _program_loss_and_grads(flat, video, text):
    from milnce_tpu.config import ModelConfig
    from milnce_tpu.losses.milnce import milnce_loss
    from milnce_tpu.models.build import build_model

    model = build_model(ModelConfig(vocab_size=MODEL["vocab_size"]))
    variables = {"params": weights.nest(flat),
                 "batch_stats": weights.nest(weights.batch_stats_for(flat))}

    def loss_fn(params):
        (v, t), _ = model.apply(
            {"params": params, "batch_stats": variables["batch_stats"]},
            video.astype(jnp.float32) / 255.0, text, train=True,
            mutable=["batch_stats"])
        return milnce_loss(v, t)

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(variables["params"])
    return float(loss), weights.flatten(grads)


def test_weight_shapes_are_the_programs_parameter_tree():
    from milnce_tpu.config import full_preset
    from milnce_tpu.models.build import build_model

    cfg = full_preset()
    tree = jax.eval_shape(build_model(cfg.model).init, jax.random.PRNGKey(0),
                          jnp.zeros((2, 16, 224, 224, 3)),
                          jnp.zeros((10, 20), jnp.int32))["params"]
    theirs = {k: tuple(v.shape) for k, v in weights.flatten(tree).items()}
    mine = weights.weight_shapes(dict(MODEL, vocab_size=66250))
    assert mine == theirs


def test_reference_equals_the_program_in_float32_through_nine_blocks():
    flat = weights.make_weights(7, weights.weight_shapes(MODEL))
    rng = np.random.RandomState(0)
    video = jnp.asarray(rng.randint(0, 255, (8, 8, 64, 64, 3), np.uint8))
    text = jnp.asarray(rng.randint(0, 128, (16, 6), np.int32))
    p_loss, p_grads = _program_loss_and_grads(flat, video, text)
    with jax.default_matmul_precision("highest"):
        r_loss, r_grads = jax.jit(jax.value_and_grad(
            lambda w: reference.loss_fn(w, video, text)))(flat)
    # (at 32x32 the last blocks' batch norm sees four values a channel and
    # float32 rounding alone reads 5e-4: the size here keeps 32 a channel)
    assert p_loss == pytest.approx(float(r_loss), rel=1e-4)
    names = [n for n in r_grads if n not in reference.FROZEN]
    gaps = compare.norm_gaps({n: p_grads[n] for n in names},
                             {n: r_grads[n] for n in names}, names)
    assert max(gaps.values()) < 2e-2, max(gaps, key=gaps.get)


def test_adam_and_schedule_are_optax_and_the_programs():
    import optax

    from milnce_tpu.config import OptimConfig
    from milnce_tpu.train.schedule import build_schedule_total

    cfg = OptimConfig(lr=1e-3, warmup_steps=4)
    sched = build_schedule_total(cfg, 1000)
    for k in range(8):
        assert float(reference.lr_at(k, 1e-3, 4, 1000)) == pytest.approx(
            float(sched(k)), rel=1e-6, abs=1e-12)
    rng = np.random.RandomState(1)
    w = {"a/kernel": jnp.asarray(rng.randn(5, 3), jnp.float32)}
    opt = optax.adam(1e-2)
    state = opt.init(w)
    mu = nu = jax.tree_util.tree_map(jnp.zeros_like, w)
    w_ref = w
    for k in range(3):
        g = {"a/kernel": jnp.asarray(rng.randn(5, 3), jnp.float32)}
        updates, state = opt.update(g, state, w)
        w = optax.apply_updates(w, updates)
        w_ref, mu, nu = reference.adam_update(w_ref, g, mu, nu, k, 1e-2)
    np.testing.assert_allclose(w["a/kernel"], w_ref["a/kernel"], rtol=1e-5,
                               atol=1e-7)


def test_retrieval_reference_and_its_numbers():
    rng = np.random.RandomState(2)
    corpus = rng.randn(700, 16).astype(np.float32)
    q = jnp.asarray(rng.randn(5, 16).astype(np.float32))
    scores = np.asarray(q) @ corpus.T
    order = np.argsort(-scores, axis=1)[:, :3]
    blocks = [(0, jnp.asarray(corpus[:300])), (300, jnp.asarray(corpus[300:]))]
    got = retrieval.scan(q, blocks, order, 3)
    np.testing.assert_array_equal(got["top_idx"], order)
    np.testing.assert_allclose(got["at_served"],
                               np.take_along_axis(scores, order, 1),
                               rtol=1e-5)
    norms = np.linalg.norm(np.asarray(q), axis=1)
    served = np.take_along_axis(scores, order, 1)
    ok = compare.retrieval_numbers(order, served, got["at_served"],
                                   got["top_scores"], norms, 700)
    assert ok["rank_gap"] < 1e-5 and ok["score_err"] < 1e-5
    wrong = order.copy()
    wrong[0, 0] = int(np.argsort(-scores[0])[200])
    got = retrieval.scan(q, blocks, wrong, 3)
    bad = compare.retrieval_numbers(wrong, served, got["at_served"],
                                    got["top_scores"], norms, 700)
    assert bad["rank_gap"] > 0.1
    dup = order.copy()
    dup[:, 1] = dup[:, 0]
    assert compare.retrieval_numbers(dup, served, got["at_served"],
                                     got["top_scores"], norms,
                                     700)["rank_gap"] == float("inf")
