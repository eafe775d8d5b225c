"""The hybrid sentence tower (models/text_hybrid.py: Mamba-2 and NoPE
attention layers, softmax-routed experts beside a shared MLP) at small
widths on the CPU, on seeded weights: against the plain reference
(benchmarks/reference/granite4h_text.py, the Mamba-2 layer as the
recurrence itself) per layer kind and end to end; pads and batch-mates
change nothing; the shares of an expert layer add up to the uncut layer;
the router soft-maxes over the logits it took; the counters count;
the export round-trips the group; and ``build_server`` + ``query_ids``
serve it with no side path.

The same for the kinds Solar-Open2-250B brings (``kda`` layers beside a
gated attention layer with heads wider than hidden / heads, a sigmoid
router): against benchmarks/reference/solar2_text.py, whose KDA layer is
the recurrence itself; and the granite configuration's program lowers to
the text it lowered to before those kinds existed."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import granite4h_text as reference
from benchmarks.reference import solar2_text
from milnce_tpu.config import (ModelConfig, TextHybridConfig, TextLMConfig,
                               parse_cli)
from milnce_tpu.models import text_hybrid, text_lm
from milnce_tpu.models.build import build_model

WORDS = 20          # two and a half chunks of 8
# sha256 of the granite configuration's lowered program text (16 x 512 rung,
# bfloat16, tests' default matmul precision) before the ``kda`` kind
GRANITE_PROGRAM = ("f10f4da6a8f2f47141fcfa0707e71d42"
                   "bf54316e486ce20d63763227e712e419")


def hybrid_config(**over) -> TextHybridConfig:
    return dataclasses.replace(TextHybridConfig(), **over)


def published(cfg: TextHybridConfig) -> dict:
    """The reference's view of the group: the published key names."""
    d = dataclasses.asdict(cfg)
    d["layer_types"] = d["layer_types"].split(",")
    return d


def kda_config(**over) -> TextHybridConfig:
    """Solar-Open2-250B's kinds at small widths: a gated attention layer
    of 4 heads of 24 (not 64 / 4), two KDA layers of 4 heads of 16 (chunks
    of 8), a sigmoid router scaled by 1.5, no multipliers."""
    return hybrid_config(**{
        "layer_types": "attention,kda,kda", "head_dim": 24,
        "use_gqa_gate": True, "scoring_func": "sigmoid",
        "routed_scaling_factor": 1.5, "kda_proj_rank": 8,
        "embedding_multiplier": 1.0, "residual_multiplier": 1.0,
        "attention_multiplier": 24 ** -0.5, **over})


def solar_published(cfg: TextHybridConfig) -> dict:
    """solar2_text's view of the group: Solar's published names."""
    return {**published(cfg), "linear_attn_config": {
        "num_heads": cfg.kda_num_heads, "head_dim": cfg.kda_head_dim,
        "short_conv_kernel_size": cfg.kda_short_conv_kernel_size,
        "num_kv_heads": None}}


# the reference of each family, with its view of the group
FAMILIES = {"granite": (hybrid_config, reference, published),
            "solar": (kda_config, solar2_text, solar_published)}


def moved(params, seed):
    """Norm weights off 1, and the scan's vectors and the conv's bias off
    their initial constants (zeros and ones tell nothing apart)."""
    key = jax.random.PRNGKey(seed)
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, leaf) in enumerate(flat):
        name = str(path[-1].key)
        noise = jax.random.normal(jax.random.fold_in(key, i), leaf.shape)
        if name in ("weight", "D", "conv_b"):
            leaf = leaf + 0.1 * noise
        elif name == "A_log":
            leaf = jnp.log(1.0 + 7.0 * jax.random.uniform(
                jax.random.fold_in(key, i), leaf.shape))
        elif name == "dt_bias":
            leaf = -2.0 + noise
        out.append(leaf)
    return jax.tree_util.tree_unflatten(tree, out)


def tower_and_params(cfg: TextHybridConfig, seed=0):
    tower = text_hybrid.TextHybrid(text_hybrid.hybrid_dims(cfg))
    params = tower.init(jax.random.PRNGKey(seed),
                        jnp.ones((1, WORDS), jnp.int32))["params"]
    return tower, moved(params, seed + 1)


def reference_weights(params):
    """``get_weights(prefix)`` of the reference over the tower's tree."""
    flat = {"/".join(str(p.key) for p in path): np.asarray(leaf, np.float32)
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                {"text_module": params})[0]}

    def get(prefix):
        return {n[len(prefix):]: v for n, v in flat.items()
                if n.startswith(prefix)}
    return get


def token_rows(rng, rows, lengths=None, vocab=128, words=WORDS):
    ids = rng.integers(1, vocab, (rows, words))
    lengths = (rng.integers(1, words + 1, rows) if lengths is None
               else np.asarray(lengths))
    ids[np.arange(words)[None, :] >= lengths[:, None]] = 0
    return ids.astype(np.int32)


@functools.lru_cache(maxsize=None)
def _jitted(tower):
    return jax.jit(lambda params, ids: tower.apply({"params": params}, ids))


def embed(tower, params, ids):
    return np.asarray(_jitted(tower)(params, jnp.asarray(ids)))


# ---- against the reference ------------------------------------------------

@pytest.mark.parametrize("share,family", [((0, 8), "granite"),
                                          ((2, 4), "granite"),
                                          ((4, 4), "solar")],
                         ids=["whole_layer", "experts_2_to_5",
                              "kda_gated_sigmoid_experts_4_to_7"])
def test_tower_matches_the_reference_per_layer_kind_and_end_to_end(share,
                                                                   family):
    """granite: layers 0 and 2 are Mamba-2, layer 1 attention; solar: layer
    0 gated attention, layers 1 and 2 KDA.  After each, the residual
    stream is the reference's, whose Mamba-2 / KDA is the recurrence."""
    first, held = share
    make, ref, view = FAMILIES[family]
    cfg = make(first_expert=first, experts_held=held)
    tower, params = tower_and_params(cfg)
    ids = token_rows(np.random.default_rng(3), 6, lengths=[20, 3, 9, 16, 1,
                                                           17])
    emb, state = tower.apply(
        {"params": params}, jnp.asarray(ids),
        capture_intermediates=lambda m, _: isinstance(m, text_hybrid.Layer))
    mine = [state["intermediates"][f"layers_{i}"]["__call__"][0]
            for i in range(cfg.num_hidden_layers)]
    ref_emb, ref_layers = ref.query_embeddings(
        reference_weights(params), ids, view(cfg),
        layers=cfg.num_hidden_layers, first_expert=first, experts_held=held,
        per_layer=True, block_rows=4)
    real = ids != 0
    for got, want in zip(mine, ref_layers):
        np.testing.assert_allclose(np.asarray(got)[real],
                                   np.asarray(want)[real],
                                   rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(emb), np.asarray(ref_emb),
                               rtol=3e-4, atol=3e-4)


def test_the_mixer_is_the_recurrence_at_any_chunk_size():
    cfg = hybrid_config()
    tower, params = tower_and_params(cfg, seed=4)
    ids = token_rows(np.random.default_rng(6), 4)
    want = embed(tower, params, ids)
    for chunk in (4, 64):
        other = text_hybrid.TextHybrid(text_hybrid.hybrid_dims(
            hybrid_config(mamba_chunk_size=chunk)))
        np.testing.assert_allclose(embed(other, params, ids), want,
                                   rtol=2e-5, atol=2e-5)


def test_pads_and_batch_mates_change_nothing():
    """A row's embedding is the same at any right padding and with any
    batch-mates: the conv and the state never read across rows, and no real
    position reads a later one."""
    pads_and_batch_mates_change_nothing(hybrid_config())


def test_pads_and_batch_mates_change_nothing_in_a_kda_tower():
    pads_and_batch_mates_change_nothing(kda_config())


def pads_and_batch_mates_change_nothing(cfg):
    tower, params = tower_and_params(cfg)
    rng = np.random.default_rng(9)
    row = token_rows(rng, 1, lengths=[11])
    alone = embed(tower, params, row)[0]
    crowd = token_rows(rng, 8)
    crowd[5] = row[0]
    np.testing.assert_allclose(embed(tower, params, crowd)[5], alone,
                               rtol=1e-5, atol=1e-5)
    for width in (11, 16, 40):           # no pad at all, a chunk's end, wider
        ids = np.zeros((1, width), np.int32)
        ids[0, :11] = row[0, :11]
        np.testing.assert_allclose(embed(tower, params, ids)[0], alone,
                                   rtol=1e-5, atol=1e-5)
    # a row of pads only (the ladder's padding rows) is finite
    assert np.isfinite(embed(tower, params,
                             np.zeros((2, WORDS), np.int32))).all()


def _moe_weights(rng, hidden, width, shared, experts):
    w = {"moe/router": rng.standard_normal((hidden, experts)) / 4,
         "moe/w_gate": rng.standard_normal((experts, hidden, width)) / 8,
         "moe/w_up": rng.standard_normal((experts, hidden, width)) / 8,
         "moe/w_down": rng.standard_normal((experts, width, hidden)) / 6,
         "shared/w_gate": rng.standard_normal((hidden, shared)) / 8,
         "shared/w_up": rng.standard_normal((hidden, shared)) / 8,
         "shared/w_down": rng.standard_normal((shared, hidden)) / 6}
    return {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}


def test_two_shares_of_four_add_up_to_the_uncut_layer():
    """8 experts, 3 a token: the routed parts of the 2 shares, with the
    shared MLP counted once, are the whole layer's output — in the program
    and in the reference."""
    shares_add_up_to_the_uncut_layer("granite", 2)


@pytest.mark.parametrize("chips", [2, 4])
def test_the_shares_of_a_sigmoid_routed_layer_add_up_to_the_uncut_layer(
        chips):
    """Solar's router (sigmoid, the top 3 of 8 normalised and scaled): the
    shares of 2 and of 4 chips, the shared expert counted once."""
    shares_add_up_to_the_uncut_layer("solar", chips)


def shares_add_up_to_the_uncut_layer(family, chips):
    make, ref, view = FAMILIES[family]
    cfg = make()
    dims, pub = text_hybrid.hybrid_dims(cfg), view(cfg)
    rng = np.random.default_rng(11)
    tokens, hidden = 40, cfg.hidden_size
    h = jnp.asarray(rng.standard_normal((tokens, hidden)), jnp.float32)
    real = jnp.asarray(rng.random(tokens) < 0.8)
    w = _moe_weights(rng, hidden, cfg.intermediate_size,
                     cfg.shared_intermediate_size, 8)
    shared = ref.swiglu(h, w["shared/w_gate"], w["shared/w_up"],
                        w["shared/w_down"])
    whole_ref = shared + ref.moe(h, w, real, pub, 0, 8)[0]
    experts, weights = text_hybrid.route(h, w["moe/router"], dims)
    program_sum, reference_sum, pairs = shared, shared, 0
    each = 8 // chips
    for lo in range(0, 8, each):
        part, n_held, _most, _rows = text_lm.held_expert_sum(
            h, experts, weights, real, w["moe/w_gate"][lo:lo + each],
            w["moe/w_up"][lo:lo + each], w["moe/w_down"][lo:lo + each],
            first_expert=lo, dtype=jnp.float32)
        program_sum = program_sum + part
        pairs += int(n_held)
        ref_part = {**w, **{k: w[k][lo:lo + each] for k in
                            ("moe/w_gate", "moe/w_up", "moe/w_down")}}
        reference_sum = reference_sum + ref.moe(
            h, ref_part, real, pub, lo, each)[0]
    assert pairs == int(real.sum()) * 3       # every pair met one share
    np.testing.assert_allclose(np.asarray(reference_sum),
                               np.asarray(whole_ref), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(program_sum),
                               np.asarray(whole_ref), rtol=1e-4, atol=1e-4)


def test_the_router_takes_the_largest_logits_and_softmaxes_over_them_only():
    cfg = hybrid_config()
    dims = text_hybrid.hybrid_dims(cfg)
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.standard_normal((30, cfg.hidden_size)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((cfg.hidden_size, 8)),
                         jnp.float32)
    experts, weights = text_hybrid.route(h, router, dims)
    logits = np.asarray(h @ router, np.float64)
    order = np.argsort(-logits, axis=1)[:, :3]
    assert np.array_equal(np.asarray(experts), order)
    top = np.take_along_axis(logits, order, axis=1)
    want = np.exp(top) / np.exp(top).sum(axis=1, keepdims=True)
    np.testing.assert_allclose(np.asarray(weights), want, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(axis=1), 1.0,
                               rtol=1e-6)
    # softmax over all 8 and then the 3 largest is another router: its
    # weights do not sum to 1
    soft = np.exp(logits) / np.exp(logits).sum(axis=1, keepdims=True)
    then_top = np.take_along_axis(soft, order, axis=1)
    assert np.abs(then_top - want).max() > 0.05


def test_the_sigmoid_router_is_the_language_model_towers():
    """``scoring_func='sigmoid'``: ``text_lm.route`` itself — the top k of
    the sigmoid scores, normalised over those k and scaled."""
    dims = text_hybrid.hybrid_dims(kda_config())
    rng = np.random.default_rng(4)
    h = jnp.asarray(rng.standard_normal((30, dims.hidden_size)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((dims.hidden_size, 8)),
                         jnp.float32)
    experts, weights = text_hybrid.route(h, router, dims)
    want_experts, want_weights = text_lm.route(h, router, dims)
    assert np.array_equal(np.asarray(experts), np.asarray(want_experts))
    np.testing.assert_array_equal(np.asarray(weights),
                                  np.asarray(want_weights))
    scores = 1.0 / (1.0 + np.exp(-np.asarray(h @ router, np.float64)))
    order = np.argsort(-scores, axis=1)[:, :3]
    assert np.array_equal(np.asarray(experts), order)
    top = np.take_along_axis(scores, order, axis=1)
    np.testing.assert_allclose(np.asarray(weights),
                               1.5 * top / top.sum(axis=1, keepdims=True),
                               rtol=1e-5)


def test_no_token_dropped_when_every_token_picks_the_same_experts():
    """A router of zeros: every logit ties, every token takes experts 0,
    1, 2 — three turns' worth of pairs for one turn's room, none lost."""
    cfg = hybrid_config(first_expert=0, experts_held=4)
    dims, pub = text_hybrid.hybrid_dims(cfg), published(cfg)
    layer = text_hybrid.RoutedExperts(dims)
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.standard_normal((2, 12, cfg.hidden_size)),
                    jnp.float32)
    real = jnp.ones((2, 12), bool).at[1, 7:].set(False)
    w = _moe_weights(rng, cfg.hidden_size, cfg.intermediate_size,
                     cfg.shared_intermediate_size, 4)
    params = {"router": jnp.zeros((cfg.hidden_size, 8)),
              "w_gate": w["moe/w_gate"], "w_up": w["moe/w_up"],
              "w_down": w["moe/w_down"]}
    (out, block), _ = layer.apply({"params": params}, h, real,
                                  mutable=[text_lm.ROUTING])
    n_real = int(real.sum())
    assert [int(v) for v in block[:3]] == [3 * n_real, n_real, 3 * n_real]
    want, _, _ = reference.moe(
        h.reshape(-1, cfg.hidden_size),
        {**w, "moe/router": params["router"]}, real.reshape(-1), pub, 0, 4)
    np.testing.assert_allclose(np.asarray(out).reshape(want.shape),
                               np.asarray(want), rtol=1e-4, atol=1e-4)


def test_counters_count_real_pairs_and_chunks():
    cfg = hybrid_config(first_expert=0, experts_held=4)
    tower, params = tower_and_params(cfg)
    ids = token_rows(np.random.default_rng(2), 5, lengths=[20, 8, 9, 1, 16])
    _emb, sown = tower.apply({"params": params}, jnp.asarray(ids),
                             mutable=[text_lm.COUNTERS])
    counters = text_lm.sum_counters(sown, text_hybrid.COUNTER_NAMES)
    assert tuple(counters) == text_hybrid.COUNTER_NAMES
    assert tuple(counters)[:4] == text_lm.COUNTER_NAMES
    held, most, total, tile_rows, run, real = (int(v) for v in
                                               counters.values())
    tokens = int((ids != 0).sum())
    assert total == tokens * cfg.num_experts_per_tok * 3   # every layer
    assert 0 < held < total and 0 < most <= tokens and held <= tile_rows
    # two Mamba layers, chunks of 8 over 20 slots: 3 a row; the real
    # tokens fill 3 + 1 + 2 + 1 + 2 of them
    assert run == 2 * 5 * 3 and real == 2 * 9


def test_the_routing_is_sown_for_whoever_asks_and_only_then():
    cfg = hybrid_config(first_expert=2, experts_held=4)
    tower, params = tower_and_params(cfg)
    ids = token_rows(np.random.default_rng(8), 5)
    emb, sown = tower.apply({"params": params}, jnp.asarray(ids),
                            mutable=[text_lm.ROUTING])
    np.testing.assert_allclose(np.asarray(emb), embed(tower, params, ids),
                               rtol=1e-5, atol=1e-5)
    layers = sown[text_lm.ROUTING]
    assert sorted(layers) == ["layers_0", "layers_1", "layers_2"]
    _ref, route = reference.query_embeddings(
        reference_weights(params), ids, published(cfg),
        layers=cfg.num_hidden_layers, first_expert=2, experts_held=4,
        routing=True)
    real = ids != 0
    followed = []
    for name, want in zip(sorted(layers), route["experts"]):
        (got,) = layers[name]["moe"]["experts"]
        assert got.shape == ids.shape + (cfg.num_experts_per_tok,)
        assert np.array_equal(np.sort(np.asarray(got)[real]),
                              np.sort(np.asarray(want)[real]))
        followed.append(np.asarray(got))
    assert float(jnp.max(route["margin"])) == 0.0
    # following the program's own choice changes nothing
    again, route2 = reference.query_embeddings(
        reference_weights(params), ids, published(cfg),
        layers=cfg.num_hidden_layers, first_expert=2, experts_held=4,
        follow=followed, routing=True)
    np.testing.assert_allclose(np.asarray(again), np.asarray(_ref),
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.max(route2["margin"])) == 0.0


# ---- the group: built by name, validated at build time --------------------

@pytest.mark.parametrize("field,value", [
    ("position_embedding_type", "rope"), ("mamba_n_groups", 8),
    ("hidden_act", "gelu"), ("layer_types", "mamba,attention,conv"),
    ("layer_types", "mamba,attention"), ("mamba_n_heads", 6),
    ("mamba_proj_bias", True), ("experts_held", 9), ("experts_held", 0),
    ("num_experts_per_tok", 9)])
def test_a_value_the_tower_does_not_implement_is_an_error_at_build(field,
                                                                   value):
    with pytest.raises(ValueError, match="text_hybrid"):
        build_model(ModelConfig(text_tower="hybrid"),
                    text_hybrid=hybrid_config(**{field: value}))


@pytest.mark.parametrize("field,value", [
    ("kda_use_full_proj", True), ("kda_num_kv_heads", 2),
    ("kda_allow_neg_eigval", False),
    ("kda_chunk_size", 24), ("scoring_func", "noaux_tc"),
    ("head_dim", 0)])
def test_a_kda_value_the_tower_does_not_implement_is_an_error_at_build(
        field, value):
    """head_dim 0 asks for hidden / heads: 64 is no multiple of 3 heads."""
    over = {field: value}
    if field == "head_dim":
        over.update(num_attention_heads=3, num_key_value_heads=1)
    with pytest.raises(ValueError, match="text_hybrid"):
        build_model(ModelConfig(text_tower="hybrid"),
                    text_hybrid=kda_config(**over))


def test_a_tower_without_mamba_layers_reads_no_mamba_key():
    """Solar's configuration gives no mamba_* key: the group's defaults
    (which do not fit its hidden size) are not checked."""
    dims = text_hybrid.hybrid_dims(kda_config(hidden_size=96))
    assert dims.layer_types == ("attention", "kda", "kda")
    assert text_hybrid.counter_names(dims) == (
        text_hybrid.COUNTER_NAMES + ("kda_chunks_run", "kda_chunks_real"))
    assert text_hybrid.counter_names(text_hybrid.hybrid_dims(
        hybrid_config())) == text_hybrid.COUNTER_NAMES


def test_the_granite_program_is_the_one_it_was_before_the_kda_kind():
    """The granite configuration's served program (16 rows x 512, from
    jax.eval_shape shapes, lowered on the CPU): its text is byte for byte
    the text it lowered to before the ``kda`` kind, the gate, ``head_dim``
    and the router's choice existed (sha256 of that text)."""
    import hashlib

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmarks import harness
    from benchmarks.drivers import serve_tower
    from milnce_tpu.train.step import make_text_embed_fn

    cell = harness.load_json(
        "benchmarks/configs/s3dg-granite4h-text-32f224.json")
    cfg = parse_cli(serve_tower.group_flags(cell) + [
        "--model.text_tower", "hybrid", "--model.dtype", "bfloat16"])
    model = build_model(cfg.model, text_hybrid=cfg.text_hybrid)
    tower = text_hybrid.TextHybrid(model.text_hybrid, embd_dim=512,
                                   dtype=jnp.bfloat16)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    repl, data = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    shapes = jax.eval_shape(tower.init, jax.random.PRNGKey(0),
                            jnp.zeros((16, 512), jnp.int32))["params"]
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16, sharding=repl),
        {"text_module": shapes})
    text = make_text_embed_fn(model, mesh).lower(
        {"params": params},
        jax.ShapeDtypeStruct((16, 512), jnp.int32, sharding=data)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == GRANITE_PROGRAM


def test_the_tower_is_chosen_by_name_and_is_served_only():
    with pytest.raises(ValueError, match="text_hybrid group"):
        build_model(ModelConfig(text_tower="hybrid"))
    from milnce_tpu.train.loop import run_training

    cfg = parse_cli(["--preset", "tiny", "--model.text_tower", "hybrid",
                     "--text_hybrid.layer_types", "attention,mamba",
                     "--text_hybrid.num_hidden_layers", "2",
                     "--parallel.platform", "cpu"])
    assert text_hybrid.hybrid_dims(cfg.text_hybrid).layer_types == (
        "attention", "mamba")
    with pytest.raises(ValueError, match="cannot be trained"):
        run_training(cfg, max_steps=1)


def test_the_tower_builds_the_first_layers_of_a_longer_published_list():
    cfg = hybrid_config(layer_types="mamba,mamba,attention,mamba,mamba",
                        num_hidden_layers=3)
    assert text_hybrid.hybrid_dims(cfg).layer_types == ("mamba", "mamba",
                                                        "attention")


# ---- export: the group round-trips, older kinds still load ----------------

def _model_and_variables(tower_kind, **groups):
    model = build_model(ModelConfig(text_tower=tower_kind,
                                    inception_blocks=1, vocab_size=128),
                        **groups)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4, 32, 32, 3)),
                           jnp.ones((1, WORDS), jnp.int32))
    return model, {"params": variables["params"],
                   "batch_stats": variables["batch_stats"]}


@pytest.mark.parametrize("kind", ["hybrid", "lm", "bow"])
def test_an_export_of_each_kind_round_trips_its_group(tmp_path, kind):
    from milnce_tpu.serving.engine import load_serving_model
    from milnce_tpu.serving.export import (export_inference_checkpoint,
                                           read_export_metadata)

    groups = {"hybrid": {"text_hybrid": hybrid_config(experts_held=4)},
              "lm": {"text_lm": TextLMConfig(experts_held=4)},
              "bow": {}}[kind]
    model, variables = _model_and_variables(kind, **groups)
    out = export_inference_checkpoint(
        str(tmp_path / kind), jax.device_get(variables["params"]),
        jax.device_get(variables["batch_stats"]),
        ModelConfig(text_tower=kind, inception_blocks=1, vocab_size=128),
        max_words=WORDS, video_shape=(4, 32, 32, 3), **groups)
    meta = read_export_metadata(out)
    assert {k for k in ("text_lm", "text_hybrid") if k in meta} == set(groups)
    for name, group in groups.items():
        assert meta[name] == dataclasses.asdict(group)
        assert type(group)(**meta[name]) == group
    loaded, loaded_vars, _meta = load_serving_model(out)
    assert (loaded.text_hybrid is not None) == (kind == "hybrid")
    assert (loaded.text_lm is not None) == (kind == "lm")
    ids = jnp.asarray(token_rows(np.random.default_rng(1), 3))
    np.testing.assert_allclose(
        np.asarray(loaded.apply(loaded_vars, None, ids, mode="text")),
        np.asarray(model.apply(variables, None, ids, mode="text")),
        rtol=1e-5, atol=1e-5)


# ---- served: build_server + query_ids -------------------------------------

@pytest.fixture(scope="module")
def served_hybrid(tmp_path_factory):
    from milnce_tpu.obs import spans
    from milnce_tpu.serving import service as serving
    from milnce_tpu.serving.export import export_inference_checkpoint

    work = tmp_path_factory.mktemp("served_hybrid")
    cfg = parse_cli([
        "--preset", "tiny", "--model.inception_blocks", "1",
        "--model.text_tower", "hybrid", "--text_hybrid.experts_held", "4",
        "--data.max_words", str(WORDS), "--parallel.platform", "cpu",
        "--serve.max_batch", "16", "--serve.topk", "3",
        "--serve.port", "0",
        "--serve.export_dir", str(work / "export"),
        "--serve.corpus_npz", str(work / "corpus.npz")])
    model = build_model(cfg.model, text_hybrid=cfg.text_hybrid)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4, 32, 32, 3)),
                           jnp.ones((1, WORDS), jnp.int32))
    variables = {"params": moved(variables["params"], 1),
                 "batch_stats": variables["batch_stats"]}
    d = cfg.data
    export_inference_checkpoint(
        cfg.serve.export_dir, jax.device_get(variables["params"]),
        jax.device_get(variables["batch_stats"]), cfg.model,
        max_words=d.max_words,
        video_shape=(d.num_frames, d.video_size, d.video_size, 3),
        text_hybrid=cfg.text_hybrid)
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


def test_build_server_serves_the_tower_through_query_ids(served_hybrid):
    _server, service, _index, engine = served_hybrid["built"]
    ids = token_rows(np.random.default_rng(21), 5)
    scores, idx = service.query_ids(ids)
    want = np.asarray(served_hybrid["model"].apply(
        served_hybrid["variables"], None, jnp.asarray(ids), mode="text"))
    ref_scores = want @ served_hybrid["corpus"].T
    order = np.argsort(-ref_scores, axis=1)[:, :3]
    assert np.array_equal(idx, order)
    np.testing.assert_allclose(
        scores, np.take_along_axis(ref_scores, order, axis=1),
        rtol=1e-4, atol=1e-4)
    assert engine.recompiles() == 0
    flushes = [e for e in served_hybrid["rec"].tail()
               if e.get("name") == "dispatch"
               and e.get("site") == "engine.text" and e.get("tokens")]
    assert flushes, "the tower's flush is an engine.text dispatch record"
    last = flushes[-1]
    assert last["tokens"] == int((ids != 0).sum())
    assert last["tokens"] + last["pad_tokens"] == last["bucket"] * WORDS
    assert last["moe_pairs_total"] == last["tokens"] * 3 * 3
    assert 0 < last["moe_pairs_held"] < last["moe_pairs_total"]
    # two Mamba layers x the rung's rows x ceil(20 / 8) chunks a row
    assert last["ssm_chunks_run"] == 2 * last["bucket"] * 3
    assert 0 < last["ssm_chunks_real"] <= 2 * 5 * 3


def test_the_served_program_has_its_own_name_and_its_scopes(served_hybrid):
    _server, _service, _index, engine = served_hybrid["built"]
    assert engine.jit_entries()["text"].__name__ == "text_hybrid_tower"
    text = engine.program_text("text", engine.buckets[0])
    assert "text_hybrid_tower" in text
    for scope in ("text_hybrid/mamba", "text_hybrid/ssd", "text_hybrid/attn",
                  "text_hybrid/shared", "text_hybrid/moe"):
        assert scope in text, scope
    assert engine.recompiles() == 0         # an ahead-of-time compile


def test_sentences_are_refused_with_the_reason(served_hybrid):
    _server, service, _index, _engine = served_hybrid["built"]
    with pytest.raises(ValueError, match="sub-word"):
        service.query_sentences(["how to fold a shirt"])


@pytest.fixture(scope="module")
def served_kda(tmp_path_factory):
    """``build_server`` over a tower of Solar's kinds (``kda_config``)."""
    from milnce_tpu.obs import spans
    from milnce_tpu.serving import service as serving
    from milnce_tpu.serving.export import export_inference_checkpoint

    work = tmp_path_factory.mktemp("served_kda")
    group = kda_config(experts_held=4)
    keys = ("layer_types", "head_dim", "use_gqa_gate", "scoring_func",
            "routed_scaling_factor", "kda_proj_rank", "embedding_multiplier",
            "residual_multiplier", "attention_multiplier", "experts_held")
    cfg = parse_cli([
        "--preset", "tiny", "--model.inception_blocks", "1",
        "--model.text_tower", "hybrid",
        *[x for k in keys for x in (f"--text_hybrid.{k}",
                                    str(getattr(group, k)))],
        "--data.max_words", str(WORDS), "--parallel.platform", "cpu",
        "--serve.max_batch", "8", "--serve.topk", "3",
        "--serve.port", "0",
        "--serve.export_dir", str(work / "export"),
        "--serve.corpus_npz", str(work / "corpus.npz")])
    assert cfg.text_hybrid == group
    model = build_model(cfg.model, text_hybrid=cfg.text_hybrid)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4, 32, 32, 3)),
                           jnp.ones((1, WORDS), jnp.int32))
    variables = {"params": moved(variables["params"], 1),
                 "batch_stats": variables["batch_stats"]}
    d = cfg.data
    export_inference_checkpoint(
        cfg.serve.export_dir, jax.device_get(variables["params"]),
        jax.device_get(variables["batch_stats"]), cfg.model,
        max_words=d.max_words,
        video_shape=(d.num_frames, d.video_size, d.video_size, 3),
        text_hybrid=cfg.text_hybrid)
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


def test_build_server_serves_a_kda_tower_and_counts_its_chunks(served_kda):
    """Through ``query_ids`` under the tower's scopes: the answers are the
    model's, and the flush's record counts the KDA layers' chunks — two
    layers x the rung's rows x ceil(20 / 8) run, the real rows' chunks
    real — beside the Mamba pair (no Mamba layer: 0)."""
    _server, service, _index, engine = served_kda["built"]
    lengths = [20, 3, 9, 16, 1]
    ids = token_rows(np.random.default_rng(21), 5, lengths=lengths)
    scores, idx = service.query_ids(ids)
    want = np.asarray(served_kda["model"].apply(
        served_kda["variables"], None, jnp.asarray(ids), mode="text"))
    ref_scores = want @ served_kda["corpus"].T
    order = np.argsort(-ref_scores, axis=1)[:, :3]
    assert np.array_equal(idx, order)
    np.testing.assert_allclose(
        scores, np.take_along_axis(ref_scores, order, axis=1),
        rtol=1e-4, atol=1e-4)
    assert engine.recompiles() == 0
    (last,) = [e for e in served_kda["rec"].tail()
               if e.get("name") == "dispatch"
               and e.get("site") == "engine.text" and e.get("tokens")][-1:]
    assert last["tokens"] == sum(lengths)
    assert last["kda_chunks_run"] == 2 * last["bucket"] * 3
    assert last["kda_chunks_real"] == 2 * (3 + 1 + 2 + 2 + 1)
    assert last["ssm_chunks_run"] == last["ssm_chunks_real"] == 0
    assert last["moe_pairs_total"] == sum(lengths) * 3 * 3
    assert engine.jit_entries()["text"].__name__ == "text_hybrid_tower"
    text = engine.program_text("text", engine.buckets[0])
    for scope in ("text_hybrid/kda", "text_hybrid/kda_scan",
                  "text_hybrid/attn", "text_hybrid/shared",
                  "text_hybrid/moe"):
        assert scope in text, scope
    assert "text_hybrid/mamba" not in text
