"""The language-model sentence tower (models/text_lm.py) at small widths on
the CPU, on seeded weights: against the plain reference
(benchmarks/reference/axk1_text.py) layer by layer and end to end; the
shares of an expert layer add up to the uncut layer; pads and batch-mates
change nothing; no token is dropped; the export keeps a leaf's own type;
and ``build_server`` + ``query_ids`` serve it with no side path."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import axk1_text as reference
from milnce_tpu.analysis.trace_invariants import iter_eqns
from milnce_tpu.config import ModelConfig, TextLMConfig, parse_cli
from milnce_tpu.models import text_lm
from milnce_tpu.models.build import build_model

WORDS = 8


def lm_config(**over) -> TextLMConfig:
    return dataclasses.replace(TextLMConfig(), **over)


def published(lm: TextLMConfig) -> dict:
    """The reference's view of the group: the published key names, with
    ``rope_scaling`` a group again."""
    d = dataclasses.asdict(lm)
    scaling = {k[len("rope_scaling_"):]: d.pop(k) for k in list(d)
               if k.startswith("rope_scaling_")}
    d["rope_scaling"] = scaling
    return d


def tower_and_params(lm: TextLMConfig, seed=0, dtype="float32"):
    model = build_model(ModelConfig(text_tower="lm", inception_blocks=1,
                                    dtype=dtype), text_lm=lm)
    variables = model.init(jax.random.PRNGKey(seed),
                           jnp.zeros((1, 4, 32, 32, 3)),
                           jnp.ones((1, WORDS), jnp.int32))
    params = variables["params"]
    # norm weights off 1 and a token table that is not all alike
    key = jax.random.PRNGKey(seed + 1)
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    moved = []
    for i, (path, leaf) in enumerate(flat):
        if str(path[-1].key) == "weight":
            leaf = leaf + 0.1 * jax.random.normal(
                jax.random.fold_in(key, i), leaf.shape)
        moved.append(leaf)
    params = jax.tree_util.tree_unflatten(tree, moved)
    return model, {"params": params,
                   "batch_stats": variables["batch_stats"]}


def reference_weights(params):
    """``get_weights(prefix)`` of the reference over the program's tree."""
    flat = {"/".join(str(p.key) for p in path): np.asarray(leaf, np.float32)
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                {"text_module": params["text_module"]})[0]}

    def get(prefix):
        return {n[len(prefix):]: v for n, v in flat.items()
                if n.startswith(prefix)}
    return get


def token_rows(rng, rows, lengths=None, vocab=128):
    ids = rng.integers(1, vocab, (rows, WORDS))
    lengths = (rng.integers(1, WORDS + 1, rows) if lengths is None
               else np.asarray(lengths))
    ids[np.arange(WORDS)[None, :] >= lengths[:, None]] = 0
    return ids.astype(np.int32)


def embed(model, variables, ids):
    return np.asarray(model.apply(variables, None, jnp.asarray(ids),
                                  mode="text"))


@pytest.mark.parametrize("share", [(0, 16), (4, 8)],
                         ids=["whole_layer", "experts_4_to_11"])
def test_tower_matches_the_reference_layer_by_layer(share):
    first, held = share
    lm = lm_config(first_expert=first, experts_held=held)
    model, variables = tower_and_params(lm)
    ids = token_rows(np.random.default_rng(3), 6)
    emb, state = model.apply(
        variables, None, jnp.asarray(ids), mode="text",
        capture_intermediates=lambda m, _: isinstance(m, text_lm.Layer))
    mine = [state["intermediates"]["text_module"][f"layers_{i}"]
            ["__call__"][0] for i in range(lm.num_hidden_layers)]
    ref_emb, ref_layers = reference.query_embeddings(
        reference_weights(variables["params"]), ids, published(lm),
        layers=lm.num_hidden_layers, first_expert=first,
        experts_held=held, per_layer=True)
    real = ids != 0
    for got, want in zip(mine, ref_layers):
        np.testing.assert_allclose(np.asarray(got)[real],
                                   np.asarray(want)[real],
                                   rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(emb), np.asarray(ref_emb),
                               rtol=2e-4, atol=2e-4)


def test_sixteen_shares_of_twelve_add_up_to_the_uncut_layer():
    """192 experts, 8 a token: the routed parts of the 16 shares, with
    the shared expert counted once, are the whole layer's output — in the
    program and in the reference."""
    lm = lm_config(n_routed_experts=192, num_experts_per_tok=8)
    rng = np.random.default_rng(11)
    tokens, hidden, width = 40, lm.hidden_size, lm.moe_intermediate_size
    h = jnp.asarray(rng.standard_normal((tokens, hidden)), jnp.float32)
    real = jnp.asarray(rng.random(tokens) < 0.8)
    w = {"moe/router": rng.standard_normal((hidden, 192)) / 8,
         "moe/w_gate": rng.standard_normal((192, hidden, width)) / 8,
         "moe/w_up": rng.standard_normal((192, hidden, width)) / 8,
         "moe/w_down": rng.standard_normal((192, width, hidden)) / 6,
         "moe/shared/w_gate": rng.standard_normal((hidden, width)) / 8,
         "moe/shared/w_up": rng.standard_normal((hidden, width)) / 8,
         "moe/shared/w_down": rng.standard_normal((width, hidden)) / 6}
    w = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
    dims = text_lm.lm_dims(lm)
    pub = published(lm)
    whole_ref, chosen, _ = reference.moe(h, w, real, pub, 0, 192)
    shared = reference.swiglu(h, w["moe/shared/w_gate"],
                              w["moe/shared/w_up"], w["moe/shared/w_down"])
    experts, weights = text_lm.route(h, w["moe/router"], dims)
    assert np.array_equal(np.sort(np.asarray(experts), axis=1),
                          np.sort(np.asarray(chosen), axis=1))
    program_sum, reference_sum, pairs = shared, shared, 0
    for share in range(16):
        lo = 12 * share
        part, n_held, _most, _rows = text_lm.held_expert_sum(
            h, experts, weights, real, w["moe/w_gate"][lo:lo + 12],
            w["moe/w_up"][lo:lo + 12], w["moe/w_down"][lo:lo + 12],
            first_expert=lo, dtype=jnp.float32)
        program_sum = program_sum + part
        pairs += int(n_held)
        ref_part = {**w, **{k: w[k][lo:lo + 12] for k in
                            ("moe/w_gate", "moe/w_up", "moe/w_down")}}
        reference_sum = reference_sum + reference.moe(
            h, ref_part, real, pub, lo, 12)[0] - shared
    assert pairs == int(real.sum()) * 8       # every pair met one share
    np.testing.assert_allclose(np.asarray(reference_sum),
                               np.asarray(whole_ref), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(program_sum),
                               np.asarray(whole_ref), rtol=1e-4, atol=1e-4)


def test_no_token_dropped_when_every_token_picks_the_same_experts():
    """Every token routed to the same k held experts: k x tokens pairs
    against ``tokens`` pairs a turn, so the loop takes k turns and every
    pair is multiplied."""
    rng = np.random.default_rng(5)
    tokens, hidden, width, held, k = 24, 16, 8, 6, 4
    h = jnp.asarray(rng.standard_normal((tokens, hidden)), jnp.float32)
    gate, up = (jnp.asarray(rng.standard_normal((held, hidden, width)),
                            jnp.float32) / 4 for _ in range(2))
    down = jnp.asarray(rng.standard_normal((held, width, hidden)),
                       jnp.float32) / 3
    experts = jnp.tile(jnp.asarray([[2, 3, 4, 5]], jnp.int32), (tokens, 1))
    weights = jnp.asarray(rng.random((tokens, k)), jnp.float32)
    real = jnp.ones((tokens,), bool).at[7].set(False)
    out, n_held, most, _rows = jax.jit(
        text_lm.held_expert_sum, static_argnames=("first_expert", "dtype"))(
        h, experts, weights, real, gate, up, down, first_expert=1,
        dtype=jnp.float32)
    assert int(n_held) == (tokens - 1) * k and int(most) == tokens - 1
    want = np.zeros((tokens, hidden), np.float32)
    for j, e in enumerate((1, 2, 3, 4)):           # held slots of 2..5
        y = reference.swiglu(h, gate[e], up[e], down[e])
        want += np.asarray(weights[:, j:j + 1] * y)
    want[7] = 0.0
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4, atol=1e-4)


def dense_held_sum(h, experts, weights, real, gate, up, down, first):
    """Every real token's sum over its held experts of weight x
    SwiGLU_e(h), expert by expert over every token: no sort, no turns."""
    experts, weights = np.asarray(experts), np.asarray(weights)
    out = np.zeros(h.shape, np.float32)
    for e in range(gate.shape[0]):
        w = np.sum(weights * (experts == first + e), axis=1)
        out += w[:, None] * np.asarray(
            reference.swiglu(h, gate[e], up[e], down[e]))
    return out * np.asarray(real)[:, None]


# 128 slots: a turn is 32 pairs, and so is a block of ranks.  name ->
# (which slots are real, True where every token picks the SAME held experts)
PUTBACK_CASES = {
    "seven_slots_in_ten_are_pads": (lambda rng: rng.random(128) < 0.3, False),
    "every_slot_is_real": (lambda rng: np.ones(128, bool), False),
    "no_slot_is_real": (lambda rng: np.zeros(128, bool), False),
    "every_token_picks_the_same_held_experts": (
        lambda rng: rng.random(128) < 0.6, True),
    "the_real_tokens_end_on_a_blocks_edge": (
        lambda rng: rng.permutation(np.arange(128) < 64), False),
    "real_tokens_first_then_pads_row_by_row": (
        lambda rng: (np.arange(32)[None, :] < np.asarray(
            [3, 32, 0, 17])[:, None]).reshape(-1), False),
}


@pytest.mark.parametrize("k", [8, 10])
@pytest.mark.parametrize("case", list(PUTBACK_CASES))
def test_the_routed_sum_against_the_dense_sum_token_by_token(case, k):
    """``held_expert_sum`` (sorted pairs, turns, the put-back over the
    real tokens' ranks, the gather back onto the slots) against the plain
    sum: pads read zero, a block of ranks that holds no real token is
    never multiplied, one that is full to its edge is, and a flush whose
    tokens agree takes more turns."""
    make_real, same = PUTBACK_CASES[case]
    rng = np.random.default_rng(len(case) + k)
    tokens, hidden, width, held, first, every = 128, 16, 8, 12, 2, 16
    assert text_lm.rank_block(32) == 32
    h = jnp.asarray(rng.standard_normal((tokens, hidden)), jnp.float32)
    gate, up = (jnp.asarray(rng.standard_normal((held, hidden, width)),
                            jnp.float32) / 4 for _ in range(2))
    down = jnp.asarray(rng.standard_normal((held, width, hidden)),
                       jnp.float32) / 3
    real = make_real(rng)
    experts = (np.tile(np.arange(first, first + k), (tokens, 1)) if same
               else np.stack([rng.permutation(every)[:k]
                              for _ in range(tokens)]))
    weights = rng.random((tokens, k)).astype(np.float32)
    out, n_held, most, _rows = jax.jit(
        text_lm.held_expert_sum, static_argnames=("first_expert", "dtype"))(
        h, jnp.asarray(experts, jnp.int32), jnp.asarray(weights),
        jnp.asarray(real), gate, up, down, first_expert=first,
        dtype=jnp.float32)
    here = (experts >= first) & (experts < first + held) & real[:, None]
    assert int(n_held) == here.sum()
    assert int(most) == max(int((here & (experts == first + e)).sum())
                            for e in range(held))
    if same:
        assert int(n_held) > 4 * 32           # more turns than the typical
    out = np.asarray(out)
    assert out.shape == (tokens, hidden) and out.dtype == np.float32
    assert (out[~real] == 0).all()
    np.testing.assert_allclose(
        out, dense_held_sum(h, experts, weights, real, gate, up, down,
                            first), rtol=1e-4, atol=1e-4)


def test_no_product_of_every_slot_by_a_turns_pairs():
    """At 8 rows x 512 slots, 10 experts a token: the put-back multiplies
    a block of ranks (512) by a turn's pairs (1,024), never every slot
    (4,096) by them, which is what a 0/1 matrix over the slots was; and
    nothing gathers ``slots x k`` rows of ``hidden``."""
    tokens, k, hidden, width, held = 8 * 512, 10, 64, 32, 4
    shapes = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((tokens, hidden), jnp.bfloat16), ((tokens, k), jnp.int32),
        ((tokens, k), jnp.float32), ((tokens,), jnp.bool_),
        ((held, hidden, width), jnp.bfloat16),
        ((held, hidden, width), jnp.bfloat16),
        ((held, width, hidden), jnp.bfloat16))]
    jaxpr = jax.make_jaxpr(lambda *a: text_lm.held_expert_sum(
        *a, first_expert=0, dtype=jnp.bfloat16))(*shapes)
    chunk, block = tokens // 4, text_lm.rank_block(tokens // 4)
    assert (chunk, block) == (1024, 512)
    eqns = list(iter_eqns(jaxpr.jaxpr))
    products = [tuple(v.aval.shape for v in eqn.invars) for eqn in eqns
                if eqn.primitive.name == "dot_general"]
    assert ((block, chunk), (chunk, hidden)) in products
    assert not [shapes for shapes in products
                if any(tokens in shape for shape in shapes)]
    gathered = [eqn.outvars[0].aval.shape for eqn in eqns
                if eqn.primitive.name == "gather"]
    assert not [shape for shape in gathered
                if len(shape) == 2 and shape[0] > tokens
                and shape[1] == hidden]


def test_pads_and_batch_mates_change_nothing():
    lm = lm_config()
    model, variables = tower_and_params(lm)
    rng = np.random.default_rng(9)
    row = token_rows(rng, 1, lengths=[3])
    alone = embed(model, variables, row)[0]
    crowd = token_rows(rng, 8)
    crowd[5] = row[0]
    np.testing.assert_allclose(embed(model, variables, crowd)[5], alone,
                               rtol=1e-5, atol=1e-5)
    # the same tokens in a narrower row: the padding's width does not show
    narrow = np.asarray(model.apply(variables, None,
                                    jnp.asarray(row[:, :4]), mode="text"))[0]
    np.testing.assert_allclose(narrow, alone, rtol=1e-5, atol=1e-5)
    # a row of pads only (the ladder's padding rows) is finite
    assert np.isfinite(embed(model, variables,
                             np.zeros((2, WORDS), np.int32))).all()


def test_counters_count_real_pairs_only():
    lm = lm_config(first_expert=0, experts_held=8)
    model, variables = tower_and_params(lm)
    ids = token_rows(np.random.default_rng(2), 5)
    _emb, sown = model.apply(variables, None, jnp.asarray(ids), mode="text",
                             mutable=[text_lm.COUNTERS])
    counters = text_lm.sum_counters(sown)
    assert tuple(counters) == text_lm.COUNTER_NAMES
    held, most, total, tile_rows = (int(v) for v in counters.values())
    moe_layers = lm.num_hidden_layers - lm.first_k_dense_replace
    assert total == int((ids != 0).sum()) * lm.num_experts_per_tok * moe_layers
    assert 0 < held < total and 0 < most <= int((ids != 0).sum())
    # every held pair lies in a tile the grouped products visited
    assert held <= tile_rows and tile_rows % moe_layers == 0


@pytest.mark.parametrize("field,value", [
    ("scoring_func", "softmax"), ("topk_method", "noaux_tc"),
    ("rope_scaling_type", "linear"), ("rope_scaling_type", ""),
    ("experts_held", 17), ("experts_held", 0)])
def test_a_value_the_tower_does_not_implement_is_an_error_at_build(field,
                                                                   value):
    with pytest.raises(ValueError, match="text_lm"):
        build_model(ModelConfig(text_tower="lm"),
                    text_lm=lm_config(**{field: value}))


def test_the_tower_is_chosen_by_name_and_never_falls_through():
    with pytest.raises(ValueError, match="text_tower"):
        build_model(ModelConfig(text_tower="transformer"))
    with pytest.raises(ValueError, match="text_lm group"):
        build_model(ModelConfig(text_tower="lm"))
    from milnce_tpu.train.loop import run_training

    cfg = parse_cli(["--preset", "tiny", "--model.text_tower", "lm",
                     "--parallel.platform", "cpu"])
    with pytest.raises(ValueError, match="cannot be trained"):
        run_training(cfg, max_steps=1)


# ---- export: a leaf's own type ------------------------------------------

def _export(tmp_path, name, params, stats, lm=None, tower="bow"):
    from milnce_tpu.serving.export import export_inference_checkpoint

    return export_inference_checkpoint(
        str(tmp_path / name), params, stats,
        ModelConfig(text_tower=tower, inception_blocks=1), max_words=WORDS,
        video_shape=(4, 32, 32, 3), text_lm=lm)


def test_bfloat16_export_round_trips_bit_for_bit(tmp_path):
    import ml_dtypes

    from milnce_tpu.serving.export import (load_inference_checkpoint,
                                           read_export_metadata)

    lm = lm_config(experts_held=4)
    _model, variables = tower_and_params(lm)
    tower = jax.tree_util.tree_map(
        lambda x: np.asarray(x.astype(jnp.bfloat16)),
        variables["params"]["text_module"])
    params = dict(jax.device_get(variables["params"]), text_module=tower)
    out = _export(tmp_path, "bf16", params,
                  jax.device_get(variables["batch_stats"]), lm, "lm")
    meta = read_export_metadata(out)
    assert meta["array_dtypes"]["params/text_module/proj"] == "bfloat16"
    assert meta["array_dtypes"]["params/fc/kernel"] == "float32"
    assert meta["text_lm"] == dataclasses.asdict(lm)
    assert TextLMConfig(**meta["text_lm"]) == lm
    _meta, loaded = load_inference_checkpoint(out)
    got = loaded["params"]["text_module"]
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(tower)):
        assert a.dtype == ml_dtypes.bfloat16
        assert np.array_equal(a.view(np.uint16), b.view(np.uint16))
    assert loaded["params"]["fc"]["kernel"].dtype == np.float32


def test_an_export_of_the_old_format_still_loads(tmp_path):
    """What PR 27 wrote: every float leaf float32, no ``text_tower`` key
    in the model's metadata, no ``text_lm`` group."""
    import json
    import os

    from milnce_tpu.parallel.mesh import build_mesh
    from milnce_tpu.serving.engine import InferenceEngine
    from milnce_tpu.serving.export import METADATA_FILE

    model = build_model(ModelConfig(inception_blocks=1, vocab_size=128))
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4, 32, 32, 3)),
                           jnp.zeros((1, WORDS), jnp.int32))
    out = str(tmp_path / "old")
    from milnce_tpu.serving.export import export_inference_checkpoint

    export_inference_checkpoint(
        out, jax.device_get(variables["params"]),
        jax.device_get(variables["batch_stats"]),
        ModelConfig(inception_blocks=1, vocab_size=128), max_words=WORDS,
        video_shape=(4, 32, 32, 3))
    path = os.path.join(out, METADATA_FILE)
    with open(path) as fh:
        meta = json.load(fh)
    assert set(meta["array_dtypes"].values()) == {"float32"}
    del meta["model"]["text_tower"]             # as the old writer left it
    with open(path, "w") as fh:
        json.dump(meta, fh)
    cfg = parse_cli(["--preset", "tiny", "--parallel.platform", "cpu"])
    mesh = build_mesh(cfg.parallel)
    n_dev = int(mesh.shape["data"])
    engine = InferenceEngine.from_export(out, mesh, dtype="bfloat16",
                                         max_batch=n_dev, min_bucket=n_dev)
    leaves = jax.tree_util.tree_leaves(engine._variables)
    assert {str(x.dtype) for x in leaves} == {"bfloat16"}
    ids = token_rows(np.random.default_rng(1), 3)
    assert np.isfinite(engine.embed_text(ids)).all()


# ---- served: build_server + query_ids ------------------------------------

@pytest.fixture(scope="module")
def served_lm(tmp_path_factory):
    from milnce_tpu.obs import spans
    from milnce_tpu.serving import service as serving
    from milnce_tpu.serving.export import export_inference_checkpoint

    work = tmp_path_factory.mktemp("served_lm")
    cfg = parse_cli([
        "--preset", "tiny", "--model.inception_blocks", "1",
        "--model.text_tower", "lm", "--text_lm.experts_held", "8",
        "--data.max_words", str(WORDS), "--parallel.platform", "cpu",
        "--serve.max_batch", "16", "--serve.topk", "3",
        "--serve.port", "0",
        "--serve.export_dir", str(work / "export"),
        "--serve.corpus_npz", str(work / "corpus.npz")])
    model, variables = tower_and_params(cfg.text_lm)
    d = cfg.data
    export_inference_checkpoint(
        cfg.serve.export_dir, jax.device_get(variables["params"]),
        jax.device_get(variables["batch_stats"]), cfg.model,
        max_words=d.max_words,
        video_shape=(d.num_frames, d.video_size, d.video_size, 3),
        text_lm=cfg.text_lm)
    corpus = np.random.default_rng(5).standard_normal(
        (40, cfg.model.embedding_dim)).astype(np.float32)
    np.savez(cfg.serve.corpus_npz, emb=corpus)
    rec = spans.SpanRecorder(ring=1 << 14)
    prev = spans.install(rec)
    built = serving.build_server(cfg)
    yield dict(cfg=cfg, rec=rec, built=built, model=model,
               variables=variables, corpus=corpus)
    serving.close_server(cfg, *built)
    spans.install(prev)


def test_build_server_serves_the_tower_through_query_ids(served_lm):
    _server, service, _index, engine = served_lm["built"]
    ids = token_rows(np.random.default_rng(21), 5)
    scores, idx = service.query_ids(ids)
    want = embed(served_lm["model"], served_lm["variables"], ids)
    ref_scores = want @ served_lm["corpus"].T
    order = np.argsort(-ref_scores, axis=1)[:, :3]
    assert np.array_equal(idx, order)
    np.testing.assert_allclose(
        scores, np.take_along_axis(ref_scores, order, axis=1),
        rtol=1e-4, atol=1e-4)
    assert engine.recompiles() == 0
    rec = served_lm["rec"]
    # the batcher pads the flush to its rung: the 5 rows ride in 8
    flushes = [e for e in rec.tail() if e.get("name") == "dispatch"
               and e.get("site") == "engine.text" and e.get("tokens")]
    assert flushes, "the tower's flush is an engine.text dispatch record"
    last = flushes[-1]
    assert last["tokens"] == int((ids != 0).sum())
    assert last["tokens"] + last["pad_tokens"] == last["bucket"] * WORDS
    moe_layers = 2
    assert last["moe_pairs_total"] == last["tokens"] * 4 * moe_layers
    assert 0 < last["moe_pairs_held"] < last["moe_pairs_total"]
    assert 0 < last["moe_expert_max"] <= last["tokens"]
    assert [e for e in rec.tail() if e.get("name") == "batcher.flush"]


def test_sentences_are_refused_with_the_reason(served_lm):
    _server, service, _index, _engine = served_lm["built"]
    with pytest.raises(ValueError, match="sub-word"):
        service.query_sentences(["how to fold a shirt"])


def test_the_routing_is_sown_for_whoever_asks_and_only_then():
    """``moe_routing``: each token's chosen experts, an expert layer —
    there when the collection is mutable, and equal to the reference's
    own choice; the served program (``mode='text'``, nothing mutable)
    returns the embeddings alone."""
    lm = lm_config(first_expert=4, experts_held=8)
    model, variables = tower_and_params(lm)
    ids = token_rows(np.random.default_rng(8), 5)
    emb, sown = model.apply(variables, None, jnp.asarray(ids), mode="text",
                            mutable=[text_lm.ROUTING])
    np.testing.assert_array_equal(np.asarray(emb),
                                  embed(model, variables, ids))
    layers = sown[text_lm.ROUTING]["text_module"]
    assert sorted(layers) == ["layers_1", "layers_2"]
    _ref, route = reference.query_embeddings(
        reference_weights(variables["params"]), ids, published(lm),
        layers=lm.num_hidden_layers, first_expert=4, experts_held=8,
        routing=True)
    real = ids != 0
    for name, want in zip(sorted(layers), route["experts"]):
        (got,) = layers[name]["moe"]["experts"]
        assert got.shape == ids.shape + (lm.num_experts_per_tok,)
        assert np.array_equal(np.sort(np.asarray(got)[real]),
                              np.sort(np.asarray(want)[real]))
    assert float(jnp.max(route["margin"])) == 0.0


def test_a_closed_service_is_collected_with_its_engine():
    """A metrics registry outlives a service (``build_server`` uses the
    process-wide one): ``close`` leaves it the gauges' last readings and
    no callback, so nothing keeps the closed service, its engine or the
    engine's weights."""
    import gc
    import weakref

    from milnce_tpu.obs import metrics as obs_metrics
    from milnce_tpu.parallel.mesh import build_mesh
    from milnce_tpu.serving.engine import InferenceEngine
    from milnce_tpu.serving.service import RetrievalService

    lm = lm_config(experts_held=4)
    model, variables = tower_and_params(lm)
    cfg = parse_cli(["--preset", "tiny", "--parallel.platform", "cpu"])
    mesh = build_mesh(cfg.parallel)
    n_dev = int(mesh.shape["data"])
    engine = InferenceEngine(model, variables, mesh, text_words=WORDS,
                             video_shape=(4, 32, 32, 3), max_batch=n_dev,
                             min_bucket=n_dev, precompile=False)
    del variables
    registry = obs_metrics.MetricsRegistry()
    service = RetrievalService(engine, registry=registry, max_inflight=4,
                               tiers="gold:1.0,bulk:0.5")
    ids = token_rows(np.random.default_rng(4), 2)
    assert service.embed_text_ids(ids).shape == (2, 512)
    leaf = weakref.ref(jax.tree_util.tree_leaves(engine._variables)[0])
    gone = [weakref.ref(engine), weakref.ref(service), leaf]
    service.close()
    uptime = registry.gauge("milnce_serve_uptime_seconds").value
    assert uptime > 0 and uptime == registry.gauge(
        "milnce_serve_uptime_seconds").value        # a reading, no clock
    del engine, service
    gc.collect()
    assert [ref() for ref in gone] == [None, None, None]
