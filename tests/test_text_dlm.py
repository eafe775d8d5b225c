"""The block-diffusion sentence tower (models/text_dlm.py: rotary
grouped-query attention with q/k norms, softmax-routed experts, an
expansion of the query denoised block by block against a key/value cache)
at small widths on the CPU, on seeded weights: against the plain reference
(benchmarks/reference/sdar_text.py: no cache, every pass a full forward) —
free-running and teacher-forced; the block mask; both branches of the
commit rule and the counters they give; a row alone and in a full rung;
the shares of an expert layer add up to the uncut layer;
``held_expert_sum``'s turn where every expert is held, and pinned where
the other two towers' cells stand; the export round-trips the group; and
``build_server`` + ``query_ids`` serve it with no side path."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import sdar_text as reference
from milnce_tpu.config import ModelConfig, TextDLMConfig, parse_cli
from milnce_tpu.models import text_dlm, text_lm
from milnce_tpu.models.build import build_model
from milnce_tpu.ops import grouped_matmul as gm

WORDS = 16          # four blocks of 4
SERVICE = ("block_length", "expand_blocks", "denoising_steps",
           "confidence_threshold", "mask_token_id")


def dlm_config(**over) -> TextDLMConfig:
    return dataclasses.replace(TextDLMConfig(), **over)


def published(cfg: TextDLMConfig) -> dict:
    """The reference's view of the group: the published key names, the
    generation's settings in their own group."""
    d = dataclasses.asdict(cfg)
    d["text_dlm"] = {k: d[k] for k in SERVICE}
    return d


def moved(params, seed):
    """Norm weights off 1 (ones tell nothing apart)."""
    key = jax.random.PRNGKey(seed)
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    return jax.tree_util.tree_unflatten(tree, [
        leaf + 0.1 * jax.random.normal(jax.random.fold_in(key, i),
                                       leaf.shape) if leaf.ndim == 1
        else leaf for i, (_path, leaf) in enumerate(flat)])


def tower_and_params(cfg: TextDLMConfig, seed=0):
    tower = text_dlm.TextDLM(text_dlm.dlm_dims(cfg), embd_dim=32)
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
                if n.startswith(prefix) and "/" not in n[len(prefix):]}
    return get


def token_rows(rng, lengths, vocab=127, words=WORDS):
    lengths = np.asarray(lengths)
    ids = rng.integers(1, vocab, (len(lengths), words))
    ids[np.arange(words)[None, :] >= lengths[:, None]] = 0
    return ids.astype(np.int32)


@functools.lru_cache(maxsize=None)
def _jitted(tower, trace):
    return jax.jit(lambda params, ids: tower.apply(
        {"params": params}, ids, trace=trace, mutable=[text_lm.COUNTERS]))


def run(tower, params, ids, trace=True):
    """-> (embeddings, the trajectory with the queries first, counters)."""
    out, sown = _jitted(tower, trace)(params, jnp.asarray(ids))
    counters = {k: int(v) for k, v in text_lm.sum_counters(
        sown[text_lm.COUNTERS], text_dlm.COUNTER_NAMES).items()}
    if not trace:
        return np.asarray(out), None, counters
    emb, got = out
    got = jax.tree_util.tree_map(np.asarray, got)
    return np.asarray(emb), {
        "tokens": got["tokens"].transpose(1, 0, 2),
        "step": got["step"].transpose(1, 0, 2), "passes": got["passes"],
        "prefill": got["prefill_experts"].transpose(1, 0, 2, 3),
        "denoise": got["denoise_experts"].transpose(3, 0, 1, 2, 4, 5),
        "commit": got["commit_experts"].transpose(2, 0, 1, 3, 4),
        "logits": got["logits"].transpose(1, 0, 2, 3)}, counters


def ref_args(cfg, **over):
    return dict(layers=cfg.num_hidden_layers, first_expert=cfg.first_expert,
                experts_held=cfg.experts_held, **over)


LENGTHS = [5, 8, 13, 2, 16, 3]


# ---- against the reference ------------------------------------------------

@pytest.mark.parametrize("threshold", [0.9, 0.012],
                         ids=["one_position_a_pass", "threshold_met"])
def test_free_running_the_tower_writes_what_the_reference_writes(threshold):
    """Prefill, then every block through the cache, against the
    reference's full forwards: the same tokens at the same passes, the
    same embedding.  Seeds on which no choice is a near-tie."""
    cfg = dlm_config(confidence_threshold=threshold)
    tower, params = tower_and_params(cfg)
    ids = token_rows(np.random.default_rng(0), LENGTHS)
    emb, got, _ = run(tower, params, ids)
    free = reference.generate(reference_weights(params), ids, published(cfg),
                              **ref_args(cfg))
    assert np.array_equal(got["tokens"], free["tokens"])
    assert np.array_equal(got["step"], free["step"])
    np.testing.assert_allclose(emb, np.asarray(free["emb"]), rtol=3e-4,
                               atol=3e-4)
    np.testing.assert_allclose(got["logits"], free["logits"], rtol=3e-4,
                               atol=3e-4)
    # the query's last block is carried into the first written one
    for r, n in enumerate(LENGTHS):
        carried = n % cfg.block_length
        assert np.array_equal(got["tokens"][r, 0, :carried],
                              ids[r, n - carried:n])
        assert (got["step"][r, 0, :carried] == -1).all()
        assert (got["step"][r].reshape(-1)[carried:] >= 0).all()
    written = got["tokens"][:, :, :].reshape(len(ids), -1)
    assert not np.isin(written, (0, cfg.mask_token_id)).any()


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_teacher_forced_the_reference_finds_the_towers_choices_its_own(seed):
    """Whatever the seed: on the tower's trajectory and experts the
    reference's own router, rule and logits agree with every choice."""
    cfg = dlm_config(confidence_threshold=0.02)
    tower, params = tower_and_params(cfg, seed=seed)
    ids = token_rows(np.random.default_rng(seed), LENGTHS)
    emb, got, _ = run(tower, params, ids)
    forced = reference.teacher_forced(
        reference_weights(params), ids, got["tokens"], got["step"],
        published(cfg), experts={k: got[k] for k in ("prefill", "denoise",
                                                     "commit")},
        program_logits=got["logits"], **ref_args(cfg))
    for name in ("route_margin", "commit_margin"):
        assert float(np.max(forced[name])) < 1e-4, name
    assert float(np.max(forced["logit_err"])) < 1e-4
    np.testing.assert_allclose(emb, np.asarray(forced["emb"]), rtol=3e-4,
                               atol=3e-4)


def test_a_wrong_trajectory_is_measured_by_the_reference():
    """A committed token swapped for another id, and a position committed
    a pass early: ``commit_margin`` reads both; a wrong expert:
    ``route_margin``."""
    cfg = dlm_config()
    tower, params = tower_and_params(cfg)
    ids = token_rows(np.random.default_rng(0), LENGTHS)
    _emb, got, _ = run(tower, params, ids)
    experts = {k: got[k] for k in ("prefill", "denoise", "commit")}

    def forced(tokens, step, experts=experts):
        return reference.teacher_forced(
            reference_weights(params), ids, tokens, step, published(cfg),
            experts=experts, **ref_args(cfg))

    tokens = got["tokens"].copy()
    tokens[2, 1, 3] = 1 + (tokens[2, 1, 3] % 100)
    assert forced(tokens, got["step"])["commit_margin"][2] > 0.05
    step = got["step"].copy()
    late = np.argmax(step[4, 1])
    step[4, 1, late] = 0            # two positions at the first pass
    assert forced(got["tokens"], step)["commit_margin"][4] > 1e-3
    wrong = dict(experts, commit=(experts["commit"] + 1) % cfg.num_experts)
    assert forced(got["tokens"], got["step"], wrong)["route_margin"].max() \
        > 1e-3


# ---- the block mask -------------------------------------------------------

def test_a_later_block_moves_no_earlier_state_and_a_block_sees_both_ways():
    cfg = dlm_config()
    d = text_dlm.dlm_dims(cfg)
    _tower, params = tower_and_params(cfg)
    layers = [params[f"layers_{i}"] for i in range(cfg.num_hidden_layers)]
    cos, sin = text_dlm.rope_tables(d, 8)
    base = jnp.asarray([8])

    def states(ids):
        x = jnp.take(params["embed"], jnp.asarray(ids)[None], axis=0)
        out, *_ = text_dlm.layers_pass(
            layers, x, (cos[None], sin[None]),
            text_dlm.prefill_visible(base, 8, 4), jnp.ones((1, 8), bool),
            None, None, d, jnp.float32)
        return np.asarray(out[0])

    ids = np.arange(1, 9)
    first = states(ids)
    later = ids.copy()
    later[6] = 99                       # a token of the second block
    moved_later = states(later)
    np.testing.assert_array_equal(moved_later[:4], first[:4])
    assert np.abs(moved_later[4:] - first[4:]).min(axis=1).max() > 1e-3
    within = ids.copy()
    within[3] = 99                      # the LAST token of the first block
    assert np.abs(states(within)[0] - first[0]).max() > 1e-3    # seen
    visible = np.asarray(text_dlm.prefill_visible(jnp.asarray([4, 0]), 8, 4))
    assert visible[0, 1, 3] and not visible[0, 1, 4]
    assert not visible[0, 5, 4] and visible[0, 5, 5] and visible[0, 5, 2]
    assert np.array_equal(visible[1], np.eye(8, dtype=bool))


# ---- the commit rule and the counters -------------------------------------

def test_both_branches_of_the_rule():
    d = text_dlm.dlm_dims(dlm_config(confidence_threshold=0.5,
                                     denoising_steps=2))
    conf = jnp.asarray([[0.9, 0.6, 0.1, 0.7],      # three over: all three
                        [0.4, 0.6, 0.1, 0.3],      # one over: the best two
                        [0.2, 0.2, 0.2, 0.1],      # ties: the earlier two
                        [0.9, 0.9, 0.9, 0.9]])     # one masked: it alone
    masked = jnp.asarray([[True] * 4, [True] * 4, [True] * 4,
                          [False, False, True, False]])
    got = np.asarray(text_dlm.commit_rule(conf, masked, d))
    assert got.tolist() == [[True, True, False, True],
                            [True, True, False, False],
                            [True, True, False, False],
                            [False, False, True, False]]
    # the reference's own rule, row by row
    for r in range(4):
        want = reference.commit_choice(np.log(np.asarray(conf[r], float)),
                                       np.asarray(masked[r]), 2, 0.5)
        assert want.tolist() == got[r].tolist()


@pytest.mark.parametrize("threshold,steps", [(0.9, 4), (0.0, 4), (0.9, 2)])
def test_the_counters_count_passes_tokens_and_the_cache(threshold, steps):
    cfg = dlm_config(confidence_threshold=threshold, denoising_steps=steps)
    tower, params = tower_and_params(cfg)
    ids = token_rows(np.random.default_rng(4), [5, 8, 13, 2, 16, 3, 0, 0])
    _emb, got, c = run(tower, params, ids)
    span, blocks, layers = 4, cfg.expand_blocks, cfg.num_hidden_layers
    lengths = (ids != 0).sum(axis=1)
    real = lengths > 0
    carried = lengths[real] % span
    written = int((blocks * span - carried).sum())
    assert tuple(c) == text_dlm.COUNTER_NAMES
    assert c["gen_tokens"] == written == int((got["step"] >= 0).sum())
    assert c["gen_passes_commit"] == blocks
    least = span // steps
    if threshold == 0.0:        # every confidence is over it: one pass
        own = np.ones((real.sum(), blocks), int)
    else:                       # none is: ``least`` positions a pass
        masks = np.stack([span - carried] + [np.full_like(carried, span)]
                         * (blocks - 1), axis=1)
        own = -(-masks // least)
    assert np.array_equal(got["passes"], own.max(axis=0))
    assert c["gen_passes_denoise"] == int(own.max(axis=0).sum())
    assert c["gen_row_passes"] == int(own.sum()) + blocks * int(real.sum())
    assert c["gen_row_slots"] == len(ids) * (c["gen_passes_denoise"]
                                             + blocks)
    base = lengths[real] // span * span
    at = base[:, None] + span * np.arange(blocks)[None, :]
    assert c["kv_positions"] == int((at * (own + 1)).sum())
    tokens = int(base.sum()) + span * c["gen_row_passes"]
    assert c["moe_pairs_total"] == tokens * cfg.num_experts_per_tok * layers
    assert c["moe_pairs_held"] == c["moe_pairs_total"]      # all held
    passes = 1 + c["gen_passes_denoise"] + blocks
    assert 0 < c["moe_experts_touched"] <= cfg.num_experts * layers * passes
    assert 0 < c["moe_expert_max"] <= tokens
    # the served program counts the same and makes no trajectory
    emb, none, served = run(tower, params, ids, trace=False)
    assert none is None and served == c and emb.shape == (8, 32)


def test_a_row_is_the_same_alone_and_in_a_full_rung():
    """A row's result depends on the row alone: the rows of a flush finish
    their first block at different passes (and ride the remaining ones
    unchanged), stand at different positions of the cache, and share the
    experts' turns — and come out as each does alone, beside pads."""
    cfg = dlm_config()
    tower, params = tower_and_params(cfg)
    ids = token_rows(np.random.default_rng(7), [5, 8, 13, 2, 16, 3, 7, 10])
    emb, got, _ = run(tower, params, ids)
    assert len({int(s.max()) for s in got["step"][:, 0]}) > 1
    for r in (0, 3, 4):
        alone = np.zeros_like(ids)
        alone[0] = ids[r]
        emb1, got1, _ = run(tower, params, alone)
        assert np.array_equal(got1["tokens"][0], got["tokens"][r])
        assert np.array_equal(got1["step"][0], got["step"][r])
        np.testing.assert_allclose(emb1[0], emb[r], rtol=2e-5, atol=2e-5)


# ---- the chip's share of an expert layer ----------------------------------

@pytest.mark.parametrize("chips", [2, 4])
def test_the_shares_of_an_expert_layer_add_up_to_the_whole_layer(chips):
    cfg = dlm_config()
    _tower, params = tower_and_params(cfg)
    w = params["layers_1"]
    h = jax.random.normal(jax.random.PRNGKey(3), (3, 8, cfg.hidden_size))
    real = jnp.asarray(np.arange(8)[None, :] < np.array([8, 5, 0])[:, None])
    whole, counts, _ = text_dlm.routed_experts(
        w, h, real, text_dlm.dlm_dims(cfg), jnp.float32)
    held = cfg.num_experts // chips
    parts, pairs, touched = 0.0, 0, 0
    for chip in range(chips):
        d = text_dlm.dlm_dims(dlm_config(first_expert=chip * held,
                                         experts_held=held))
        mine = {**w, **{k: w[k][chip * held:(chip + 1) * held]
                        for k in ("w_gate", "w_up", "w_down")}}
        part, c, _ = text_dlm.routed_experts(mine, h, real, d, jnp.float32)
        parts = parts + part
        pairs += int(c["moe_pairs_held"])
        touched += int(c["moe_experts_touched"])
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=2e-5, atol=2e-5)
    assert pairs == int(counts["moe_pairs_held"]) == 13 * 2
    assert touched == int(counts["moe_experts_touched"])
    assert not np.asarray(whole)[2].any()           # a pad row: nothing


# ---- held_expert_sum's turn -----------------------------------------------

def test_the_turn_follows_the_share_held_and_stays_where_the_cells_stand():
    # every expert held: every pair in one turn
    assert text_lm.turn_pairs(256, 8, 128, 128) == 2048     # a denoise pass
    assert text_lm.turn_pairs(2048, 8, 128, 128) == 16384   # the prefill
    # the A.X-K1 cell (12 of 192 held, 8 a token, rungs of 16 / 32 / 64
    # rows x 32 slots) and the granite cell (36 of 72, 10 a token, 4 / 8 /
    # 16 rows x 512): a quarter of the slots, as before this rule
    for slots in (512, 1024, 2048):
        assert text_lm.turn_pairs(slots, 8, 12, 192) == slots // 4
    for slots in (2048, 4096, 8192):
        assert text_lm.turn_pairs(slots, 10, 36, 72) == slots // 4
    assert text_lm.turn_pairs(2048, 8, 12) == 512       # share not given
    # the tiles: the two cells' as they were, the new shapes' sound
    assert gm.tiling(512, 7168, 2048, jnp.bfloat16) == (128, 1024, 2048)
    assert gm.tiling(512, 2048, 7168, jnp.bfloat16) == (128, 256, 7168)
    assert gm.tiling(2048, 4096, 768, jnp.bfloat16) == (128, 2048, 768)
    assert gm.tiling(2048, 768, 4096, jnp.bfloat16) == (128, 384, 4096)
    for pairs in (2048, 16384):
        assert gm.tiling(pairs, 2048, 768, jnp.bfloat16) == (128, 2048, 768)
        assert gm.tiling(pairs, 768, 2048, jnp.bfloat16) == (128, 768, 2048)


def test_with_every_expert_held_a_pass_takes_one_turn():
    """The rows the grouped products were asked for are those of ONE walk
    over all the pairs (``tile_visits`` of the groups' sizes at a turn of
    ``tokens x k``), whatever the routing; with the share unknown the same
    call takes turns of a quarter of the slots."""
    tokens, k, held, hidden, width = 24, 2, 8, 16, 8
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((tokens, hidden)), jnp.float32)
    experts = jnp.asarray(rng.integers(0, held, (tokens, k)), jnp.int32)
    weights = jnp.full((tokens, k), 0.5, jnp.float32)
    real = jnp.asarray(np.arange(tokens) < 20)
    stacks = [jnp.asarray(rng.standard_normal(s), jnp.float32)
              for s in ((held, hidden, width), (held, hidden, width),
                        (held, width, hidden))]
    fn = jax.jit(text_lm.held_expert_sum,
                 static_argnames=("first_expert", "dtype", "num_experts"))
    one, n_held, _most, rows_one = fn(h, experts, weights, real, *stacks,
                                      first_expert=0, dtype=jnp.float32,
                                      num_experts=held)
    many, _, _, rows_many = fn(h, experts, weights, real, *stacks,
                               first_expert=0, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(one), np.asarray(many), rtol=1e-5,
                               atol=1e-5)
    assert int(n_held) == 20 * k
    tile = gm.tiling(tokens * k, hidden, width, jnp.float32)[0]
    counts = np.bincount(np.asarray(experts)[:20].reshape(-1),
                         minlength=held)
    visits = gm.tile_visits(jnp.asarray(counts, jnp.int32), tokens * k,
                            tile)[3]
    assert int(rows_one) == int(visits) * tile
    assert int(rows_many) != int(rows_one)      # seven turns of 6 pairs


# ---- the group: built by name, validated at build time --------------------

@pytest.mark.parametrize("field,value", [
    ("remasking", "random"), ("decoder_sparse_step", 2),
    ("attention_bias", True), ("hidden_act", "gelu"), ("head_dim", 15),
    ("num_key_value_heads", 3), ("experts_held", 9), ("experts_held", 0),
    ("num_experts_per_tok", 9), ("denoising_steps", 3),
    ("mask_token_id", 128), ("mask_token_id", 0), ("expand_blocks", 0)])
def test_a_value_the_tower_does_not_implement_is_an_error_at_build(field,
                                                                   value):
    with pytest.raises(ValueError, match="text_dlm"):
        build_model(ModelConfig(text_tower="dlm"),
                    text_dlm=dlm_config(**{field: value}))


def test_the_tower_is_chosen_by_name_and_is_served_only():
    with pytest.raises(ValueError, match="text_dlm group"):
        build_model(ModelConfig(text_tower="dlm"))
    from milnce_tpu.train.loop import run_training

    cfg = parse_cli(["--preset", "tiny", "--model.text_tower", "dlm",
                     "--text_dlm.expand_blocks", "3",
                     "--parallel.platform", "cpu"])
    assert text_dlm.dlm_dims(cfg.text_dlm).expand_blocks == 3
    with pytest.raises(ValueError, match="cannot be trained"):
        run_training(cfg, max_steps=1)
    with pytest.raises(ValueError, match="whole number of blocks"):
        tower, params = tower_and_params(dlm_config())
        tower.apply({"params": params}, jnp.ones((1, 6), jnp.int32))


def test_the_benchmark_names_the_cell_and_its_metrics():
    """By NAME, never by place or count: later PRs append."""
    from benchmarks import harness

    bench = harness.load_benchmark()
    cell = "query-expand-sdar-c64"
    assert cell in {w["name"] for w in bench["workloads"]}
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in ("denoise_time_share.serve", "dlm_tower_roofline",
                 "expert_product_roofline", "denoise_tokens_per_pass.serve",
                 "denoise_row_fill.serve"):
        assert listed[name]["workloads"] == [cell], name
        assert listed[name]["moves"] == "queries_per_s"


# ---- export: the group round-trips ----------------------------------------

def test_an_export_round_trips_the_group(tmp_path):
    from milnce_tpu.serving.engine import load_serving_model
    from milnce_tpu.serving.export import (export_inference_checkpoint,
                                           read_export_metadata)

    group = dlm_config(expand_blocks=3, confidence_threshold=0.5)
    model_cfg = ModelConfig(text_tower="dlm", inception_blocks=1,
                            vocab_size=128)
    model = build_model(model_cfg, text_dlm=group)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4, 32, 32, 3)),
                           jnp.ones((1, WORDS), jnp.int32))
    out = export_inference_checkpoint(
        str(tmp_path / "dlm"), jax.device_get(variables["params"]),
        jax.device_get(variables["batch_stats"]), model_cfg,
        max_words=WORDS, video_shape=(4, 32, 32, 3), text_dlm=group)
    meta = read_export_metadata(out)
    assert meta["text_dlm"] == dataclasses.asdict(group)
    assert "text_lm" not in meta and "text_hybrid" not in meta
    loaded, loaded_vars, _meta = load_serving_model(out)
    assert loaded.text_dlm == text_dlm.dlm_dims(group)
    assert loaded.text_lm is None and loaded.text_hybrid is None
    ids = jnp.asarray(token_rows(np.random.default_rng(1), [3, 9, 16]))
    np.testing.assert_allclose(
        np.asarray(loaded.apply(loaded_vars, None, ids, mode="text")),
        np.asarray(model.apply(variables, None, ids, mode="text")),
        rtol=1e-5, atol=1e-5)


# ---- served: build_server + query_ids -------------------------------------

@pytest.fixture(scope="module")
def served_dlm(tmp_path_factory):
    from milnce_tpu.obs import spans
    from milnce_tpu.serving import service as serving
    from milnce_tpu.serving.export import export_inference_checkpoint

    work = tmp_path_factory.mktemp("served_dlm")
    cfg = parse_cli([
        "--preset", "tiny", "--model.inception_blocks", "1",
        "--model.text_tower", "dlm", "--data.max_words", str(WORDS),
        "--parallel.platform", "cpu", "--serve.max_batch", "8",
        "--serve.min_bucket", "8", "--serve.topk", "3", "--serve.port", "0",
        "--serve.export_dir", str(work / "export"),
        "--serve.corpus_npz", str(work / "corpus.npz")])
    model = build_model(cfg.model, text_dlm=cfg.text_dlm)
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
        text_dlm=cfg.text_dlm)
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


def test_build_server_serves_the_tower_through_query_ids(served_dlm):
    _server, service, _index, engine = served_dlm["built"]
    ids = token_rows(np.random.default_rng(21), [5, 2, 16, 11, 8])
    scores, idx = service.query_ids(ids)
    padded = np.zeros((8, WORDS), np.int32)
    padded[:5] = ids
    want = np.asarray(served_dlm["model"].apply(
        served_dlm["variables"], None, jnp.asarray(padded),
        mode="text"))[:5]
    ref_scores = want @ served_dlm["corpus"].T
    order = np.argsort(-ref_scores, axis=1)[:, :3]
    assert np.array_equal(idx, order)
    np.testing.assert_allclose(
        scores, np.take_along_axis(ref_scores, order, axis=1),
        rtol=1e-4, atol=1e-4)
    assert engine.recompiles() == 0
    flushes = [e for e in served_dlm["rec"].tail()
               if e.get("name") == "dispatch"
               and e.get("site") == "engine.text" and e.get("tokens")]
    assert flushes, "the tower's flush is an engine.text dispatch record"
    last = flushes[-1]
    assert last["tokens"] == int((ids != 0).sum())
    assert last["tokens"] + last["pad_tokens"] == last["bucket"] * WORDS
    for name in text_dlm.COUNTER_NAMES:
        assert name in last, name
    group = served_dlm["cfg"].text_dlm
    shards = len(jax.devices())     # a pass is counted on every data shard
    assert last["gen_passes_commit"] in (group.expand_blocks,
                                         group.expand_blocks * shards)
    assert last["gen_tokens"] == int(
        (group.expand_blocks * 4 - (ids != 0).sum(axis=1) % 4).sum())
    assert 0 < last["gen_row_passes"] <= last["gen_row_slots"]


def test_the_served_program_has_its_own_name_and_its_scopes(served_dlm):
    _server, _service, _index, engine = served_dlm["built"]
    assert engine.jit_entries()["text"].__name__ == "text_dlm_tower"
    text = engine.program_text("text", engine.buckets[0])
    assert "text_dlm_tower" in text
    for scope in ("text_dlm/prefill", "text_dlm/denoise", "text_dlm/commit",
                  "text_dlm/attn", "text_dlm/moe", "text_dlm/head"):
        assert scope in text, scope
    assert engine.recompiles() == 0         # an ahead-of-time compile


def test_sentences_are_refused_with_the_reason(served_dlm):
    _server, service, _index, _engine = served_dlm["built"]
    with pytest.raises(ValueError, match="sub-word"):
        service.query_sentences(["how to fold a shirt"])
