"""The cell ``query-expand-sdar-c64`` rehearsed on the CPU at tiny widths
(``cells/`` entries ``tiny-query-expand`` / ``tiny-sdar`` /
``tiny-expand-c4``): the ``serve_gen`` driver end to end, traced, with the
program's trajectory replayed and the reference teacher-forced on it; the
controls (float8 products, the in-block mask made causal, the cache of
earlier blocks dropped, unrelated answers) held to the cell's limits
through ``harness.judge``; the five readers this configuration brings on
records made by hand; and the work counts against a hand count and the
module's own shapes."""

import numpy as np
import pytest

from benchmarks import flops_sdar, harness, weights_sdar
from benchmarks.tests.conftest import run_cell, tiny_benchmark

CELL = "query-expand-sdar-c64"
NEW = {"denoise_time_share.serve": ("%", "higher"),
       "dlm_tower_roofline": ("%", "higher"),
       "expert_product_roofline": ("%", "higher"),
       "denoise_tokens_per_pass.serve": ("tokens", "higher"),
       "denoise_row_fill.serve": ("%", "higher")}
# the catalog's row (model-configs guide), number for number
PUBLISHED = {
    "decoder_sparse_step": 1, "head_dim": 128, "hidden_size": 2048,
    "intermediate_size": 6144, "max_position_embeddings": 32768,
    "max_window_layers": 48, "moe_intermediate_size": 768,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_theta": 1000000,
    "vocab_size": 151936}


@pytest.fixture(scope="module")
def expand_bench():
    """The rehearsal benchmark with the tiny cell, and the five metrics
    listed for it as BENCHMARK.json lists them for the real one."""
    bench = tiny_benchmark()
    bench["configs"].append({"name": "tiny-sdar", "source": "rehearsal",
                             "file": "benchmarks/configs/tiny-sdar.json",
                             "reduced": [], "why": "rehearsal"})
    bench["workloads"].append({"name": "tiny-query-expand",
                               "config": "tiny-sdar",
                               "traffic": "tiny-expand-c4", "chips": 1,
                               "why": "rehearsal"})
    for m in bench["end_to_end"]:
        if m["name"] in ("queries_per_s", "query_p95_ms"):
            m["workloads"].append("tiny-query-expand")
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            m["workloads"] = ["tiny-query-expand"]
    return bench


def test_benchmark_json_has_the_cell_as_the_issue_names_it():
    """By name: later PRs append after these entries."""
    bench = harness.load_benchmark()
    (cfg,) = [c for c in bench["configs"]
              if c["name"] == "s3dg-sdar-text-32f224"]
    assert cfg["source"] == ("https://huggingface.co/JetLM/SDAR-30B-A3B-Chat"
                             "/blob/main/config.json")
    assert cfg["reduced"] == ["num_hidden_layers", "index_rows"]
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "s3dg-sdar-text-32f224", "text-expand16-c64", 1)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, (unit, better) in NEW.items():
        mod = harness.layer_metric_module(name)
        assert entries[name] == {
            "name": name, "unit": unit, "better": better,
            "source": mod.SOURCE, "layer": mod.LAYER, "moves": mod.MOVES,
            "workloads": [CELL]}
        assert mod.UNIT == unit
    loaded = harness.load_cell(bench, CELL)
    assert loaded.driver == "serve_gen" and set(loaded.limits) == {
        "rank_gap", "score_err", "route_margin", "commit_margin",
        "logit_err", "replay_err"}
    names = set(harness.metric_names_for(bench, loaded, "per_layer"))
    assert set(NEW) <= names
    assert {"flush_ms_p50.serve", "query_mfu", "index_scan_roofline",
            "device_idle_share.serve"} <= names     # the list-less ones
    # no reader of the other towers' cells is read here
    assert not {"text_tower_roofline", "hybrid_tower_roofline",
                "ssm_time_share.serve", "expert_tile_fill.serve"} & names
    traffic = loaded.traffic
    assert (traffic["callers"], traffic["rows_per_query"], traffic["pool"],
            traffic["rank_exponent"], traffic["compare_sample"],
            traffic["warmup_s"]) == (64, 1, 200000, 0.5, 64, 3.0)
    assert traffic["words"] == {"min": 2, "max": 32, "median": 12,
                                "sigma": 0.6}


def test_configuration_file_keeps_every_published_width():
    cfg = harness.load_cell(harness.load_benchmark(), CELL).config
    assert {k: cfg[k] for k in PUBLISHED} == PUBLISHED
    assert cfg["mlp_only_layers"] == [] and cfg["norm_topk_prob"] is True
    assert cfg["tie_word_embeddings"] is False
    assert cfg["num_hidden_layers"] == 6
    assert cfg["published"] == {"num_hidden_layers": 48}
    assert cfg["reduced"] == ["num_hidden_layers", "index_rows"]
    share = cfg["share"]
    assert (share["experts_held"], share["first_expert"],
            share["chips_sharing_a_layer"]) == (cfg["num_experts"], 0, 1)
    assert share["pipeline_stages"] * cfg["num_hidden_layers"] == 48
    assert (cfg["data"]["max_words"], cfg["serve"]["min_bucket"],
            cfg["serve"]["max_batch"]) == (32, 16, 64)
    assert cfg["text_dlm"] == {
        "expand_blocks": 4, "block_length": 4, "denoising_steps": 4,
        "remasking": "low_confidence_dynamic", "confidence_threshold": 0.9,
        "mask_token_id": 151669}
    for key in ("qk_norm", "rotary", "block_length", "denoising_steps",
                "confidence_threshold", "sampling", "mask_token_id",
                "excluded_ids", "pooling", "projection", "weights",
                "serve.dtype"):
        assert key in cfg["assumed"], key
    shapes = weights_sdar.weight_shapes(cfg)
    params = sum(int(np.prod(s)) for s in shapes.values())
    assert params == 4_362_104_320              # 8.72 GB of bfloat16
    assert "4,362,104,320" in cfg["notes"]["parameters"]
    # the work count reads the same shapes: every matrix once
    vectors = sum(int(np.prod(s)) for s in shapes.values() if len(s) == 1)
    assert flops_sdar.tower_params(cfg) == params - vectors


def test_weight_shapes_are_the_modules_own():
    """``weights_sdar.weight_shapes`` = ``jax.eval_shape`` of the module,
    at the full file (4,362,104,320) and at the rehearsal's."""
    import jax
    import jax.numpy as jnp

    from benchmarks.drivers import serve_tower
    from milnce_tpu.config import parse_cli
    from milnce_tpu.models import text_dlm

    for cfg in (harness.load_cell(harness.load_benchmark(), CELL).config,
                harness.load_json("benchmarks/tests/cells/configs/"
                                  "tiny-sdar.json")):
        group = parse_cli(serve_tower.group_flags(cfg)).text_dlm
        tower = text_dlm.TextDLM(text_dlm.dlm_dims(group),
                                 embd_dim=cfg["model"]["embedding_dim"])
        shapes = jax.eval_shape(
            tower.init, jax.random.PRNGKey(0),
            jnp.zeros((1, cfg["data"]["max_words"]), jnp.int32))["params"]
        mine = {"text_module/" + "/".join(str(p.key) for p in path):
                tuple(leaf.shape) for path, leaf in
                jax.tree_util.tree_flatten_with_path(shapes)[0]}
        assert mine == {k: tuple(v) for k, v in
                        weights_sdar.weight_shapes(cfg).items()}


def test_the_programs_group_is_made_from_the_files_own_keys():
    from benchmarks.drivers import serve_tower
    from milnce_tpu.config import parse_cli

    cfg = harness.load_cell(harness.load_benchmark(), CELL).config
    group = parse_cli(serve_tower.group_flags(cfg)).text_dlm
    assert (group.num_experts, group.experts_held, group.first_expert,
            group.num_hidden_layers, group.vocab_size, group.head_dim) == (
        128, 128, 0, 6, 151936, 128)
    assert {k: getattr(group, k) for k in cfg["text_dlm"]} == cfg["text_dlm"]
    assert {k: getattr(group, k) for k in PUBLISHED
            if hasattr(group, k)} == {k: v for k, v in PUBLISHED.items()
                                      if hasattr(group, k)}


def test_flops_and_bytes_against_a_hand_count():
    cfg = harness.load_cell(harness.load_benchmark(), CELL).config
    assert flops_sdar.attention_params(cfg) == (
        2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048)
    assert flops_sdar.expert_params(cfg) == 3 * 2048 * 768
    assert flops_sdar.cache_bytes_per_position(cfg) == 6 * 2 * 4 * 128 * 2
    # one denoise pass of 64 rows x 4 positions, every expert touched in
    # every layer, 300 positions of cache read by each row
    w = flops_sdar.work(
        cfg, tokens=256, pairs_held=256 * 8 * 6, attended=256 * (300 + 4),
        logit_positions=256, passes=1, head_passes=1,
        experts_touched=128 * 6, cache_positions=64 * 300, rows=0)
    experts = 128 * 6 * 3 * 2048 * 768 * 2
    fixed = 6 * (18_874_368 + 2048 * 128) * 2
    head = 2048 * 151936 * 2
    assert w["bytes"] == experts + fixed + head + 256 * 2048 * 2 \
        + 64 * 300 * 12288
    assert 8.2e9 < w["bytes"] < 8.4e9           # ISSUE 34: ~8.1 GB a pass
    assert w["flops"] == (2.0 * 256 * fixed / 2 + 2.0 * 256 * 8 * 6
                          * 3 * 2048 * 768 + 4.0 * 256 * 304 * 4096 * 6
                          + 2.0 * 256 * 2048 * 151936)
    assert w["bytes"] / 819e9 > 5 * w["flops"] / 197e12     # bytes-bound
    # a flush record: 32 rows of 12 tokens (3 whole blocks), the worst case
    record = {"rows": 32, "gen_passes_denoise": 16, "gen_passes_commit": 4,
              "gen_row_passes": 32 * 20, "gen_tokens": 32 * 16,
              "moe_pairs_total": (32 * 12 + 4 * 32 * 20) * 8 * 6,
              "moe_pairs_held": (32 * 12 + 4 * 32 * 20) * 8 * 6,
              "moe_experts_touched": 128 * 6 * 21,
              "kv_positions": 32 * 5 * (12 + 16 + 20 + 24)}
    flush = flops_sdar.flush_work(cfg, record)
    assert flush["bytes"] == (
        21 * (experts + fixed) + 16 * head
        + (32 * 12 + 4 * 32 * 20) * 2048 * 2
        + record["kv_positions"] * 12288)
    assert 0.19 < flush["bytes"] / 819e9 < 0.215    # ISSUE 34: 204 ms
    # a query's passes under the rule's fall-back
    one = flops_sdar.query_passes(cfg, 12.0)
    assert one["denoise"] == 2.5 + 12 and one["row_passes"] == 18.5
    assert one["masked"] == (10 + 6 + 3 + 1) / 4 + 3 * 10
    assert 60e9 < flops_sdar.tower_flops(cfg, 13.9, 1.0) < 100e9


def test_expand_cell_runs_traced_and_every_reader_reads(expand_bench,
                                                        bench_dir, tmp_path):
    result, out = run_cell(expand_bench, bench_dir, "tiny-query-expand",
                           tmp_path, trace=True, seconds=3.0)
    assert result["correct"], out["compared"]
    assert set(out["compared"]) == {
        "rank_gap", "score_err", "route_margin", "commit_margin",
        "logit_err", "replay_err", "unanswered"}
    assert result["failed"] == 0 and result["attempted"] > 20
    assert out["notes"]["recompiles"] == {"engine": 0, "index": 0}
    got = result["metrics"]
    # the CPU's trace has no line of programs: the three device_trace
    # readers find nothing there and the line leaves them out
    assert {"denoise_tokens_per_pass.serve", "denoise_row_fill.serve",
            "flush_rows_mean.serve", "scan_rows_mean.serve",
            "query_mfu"} <= set(got), sorted(got)
    assert not {"denoise_time_share.serve", "dlm_tower_roofline",
                "expert_product_roofline"} & set(got)
    assert "scope_error" not in out["notes"]
    # a low threshold: more than one position a pass, fewer than all
    assert 0.8 < got["denoise_tokens_per_pass.serve"]["value"] < 3.2
    assert 0 < got["denoise_row_fill.serve"]["value"] <= 100
    flushes = [e for e in out["record"].events
               if e.get("name") == "dispatch"
               and e.get("site") == "engine.text"]
    assert flushes and all(
        e["tokens"] + e["pad_tokens"] == e["bucket"] * 16 for e in flushes)
    assert all(e["gen_tokens"] <= 8 * e["rows"] for e in flushes)
    assert out["notes"]["gen_tokens"] == sum(e["gen_tokens"]
                                             for e in flushes)
    assert out["notes"]["cache"]["misses"] > 100    # calls pass the tower


def _zeroed_head(service):
    """The timed path broken underneath: the first layer's value
    projection zeroed in the engine's resident weights."""
    import jax

    variables = service.engine._variables
    layer = variables["params"]["text_module"]["layers_0"]
    layer["wv"] = jax.device_put(layer["wv"] * 0.0, layer["wv"].sharding)


def test_a_broken_attention_comes_out_not_correct(expand_bench, bench_dir,
                                                  tmp_path):
    result, out = run_cell(expand_bench, bench_dir, "tiny-query-expand",
                           tmp_path, fault=_zeroed_head)
    assert not result["correct"], out["numbers"]


@pytest.mark.parametrize("kind,correct", [
    ("program", True), ("float8", False), ("causal_in_block", False),
    ("cache_dropped", False), ("unrelated", False)])
def test_controls_in_the_served_place_are_judged_as_a_run_is(
        expand_bench, bench_dir, kind, correct):
    """``program``: the sound program's second run as what was served.
    ``float8``: the reference generating in the program's place with its
    products in float8_e4m3fn, one step below the bfloat16 the
    configuration states.  ``causal_in_block``: the program whose blocks
    see only backwards.  ``cache_dropped``: the program that sees no
    earlier block.  ``unrelated``: another query's answers.  On the chip
    they are read at the cell's own sizes and limits (PERF.md); here at
    the rehearsal's."""
    from benchmarks import traffic_gen

    cell = harness.load_cell(expand_bench, "tiny-query-expand",
                             bench_dir=bench_dir)
    driver = harness.load_driver("serve_gen", bench_dir)
    pool = traffic_gen.query_pool(5, cell.traffic, cell.config["vocab_size"],
                                  cell.config["data"]["max_words"])[:12]
    compared = driver.control(cell, 5, pool, kind)
    assert set(compared) == set(cell.limits)
    assert harness.judge(compared) == correct, compared


# ---- the five readers on records made by hand -----------------------------

class _Trace:
    def __init__(self, modules, busy_s):
        self.module_seconds = modules
        self.busy_s, self.chips, self.window_s = busy_s, 1, 3.0


def _record(events, trace=None, trace_window=None, scopes=None):
    from benchmarks import peaks

    cell = harness.load_cell(harness.load_benchmark(), CELL)
    return harness.RunRecord(cell=cell, peaks=peaks.PEAKS["TPU v5 lite"],
                             events=events, window_s=20.0, trace=trace,
                             extra={"trace_window": trace_window,
                                    "scope_seconds": scopes})


def _flush(rows, bucket, mono, denoise=16, touched=128 * 6 * 21):
    tokens = rows * 12
    through = tokens + 4 * rows * (denoise + 4)
    return {"kind": "span", "name": "dispatch", "site": "engine.text",
            "mono": mono, "rows": rows, "bucket": bucket, "tokens": tokens,
            "pad_tokens": bucket * 32 - tokens, "hold_ms": 300.0,
            "moe_pairs_held": through * 48, "moe_pairs_total": through * 48,
            "moe_expert_max": 40, "moe_tile_rows": 9 * through * 48,
            "gen_passes_denoise": denoise, "gen_passes_commit": 4,
            "gen_tokens": rows * 16, "gen_row_passes": rows * (denoise + 4),
            "gen_row_slots": bucket * (denoise + 4),
            "moe_experts_touched": touched,
            "kv_positions": rows * 5 * (12 + 16 + 20 + 24)}


def _read(name, run):
    return harness.layer_metric_module(name).read(run)


@pytest.mark.parametrize("name", sorted(NEW))
def test_nothing_to_read_is_none(name):
    """Another tower's records (the parent's program): a text flush
    without the generation's counters, a trace without the tower's
    program, no scopes.  None, nothing raised."""
    old = [{"kind": "span", "name": "dispatch", "site": "engine.text",
            "mono": 1.0, "rows": 8, "bucket": 16, "hold_ms": 2.0,
            "tokens": 90, "pad_tokens": 422, "moe_pairs_held": 50},
           {"kind": "span", "name": "batcher.flush", "mono": 1.0, "rows": 3}]
    trace = _Trace({"jit_local_topk(1)": [0.004],
                    "jit_text_lm_tower(2)": [0.03]}, 0.5)
    scopes = {"inside": {"text_lm/moe": 0.2, "*": 0.4},
              "whole": {"text_lm/moe": 0.2, "*": 0.4}, "ops": {}}
    assert _read(name, _record(old, trace, (0.5, 3.5), scopes)) is None
    assert _read(name, _record(old, trace, (0.5, 3.5))) is None
    assert _read(name, _record([], None)) is None


def test_tokens_per_pass_and_row_fill_from_the_flush_records():
    events = [_flush(32, 32, 1.0), _flush(20, 32, 2.0, denoise=15)]
    run = _record(events)
    assert _read("denoise_tokens_per_pass.serve", run) == pytest.approx(
        (32 + 20) * 16 / (32 * 20 + 20 * 19))
    assert _read("denoise_row_fill.serve", run) == pytest.approx(
        100.0 * (32 * 20 + 20 * 19) / (32 * 20 + 32 * 19))


def test_the_three_device_readers_from_a_trace_and_its_scopes():
    """The rooflines are taken over the flushes of the traced window, each
    by the share of its hold inside it (the flush that ended before the
    trace began is left out, the one cut by the window's end counts by a
    third), over the program's device time inside the window; the block
    loop's share is of the window's busy time."""
    events = [_flush(32, 32, 4.0),                      # before the trace
              _flush(32, 32, 10.4), _flush(30, 32, 10.9),
              _flush(33, 64, 13.2)]                     # held from 12.9 on
    trace = _Trace({"jit_text_dlm_tower(9)": [0.300, 0.310],
                    "jit_text_dlm_tower(7)": [0.320],
                    "jit_local_topk(1)": [0.006] * 3}, busy_s=2.8)
    scopes = {"inside": {"text_dlm/denoise": 2.0, "text_dlm/commit": 0.45,
                         "text_dlm/prefill": 0.2, "grouped_matmul": 0.56,
                         "*": 0.72},
              "whole": {"text_dlm/denoise": 2.2, "text_dlm/commit": 0.5,
                        "grouped_matmul": 0.72, "*": 3.0}, "ops": {}}
    run = _record(events, trace, (10.0, 13.0), scopes)
    cfg = run.cell.config
    shares = [(events[1], 1.0), (events[2], 1.0), (events[3], 1.0 / 3.0)]
    least = 0.0
    for e, share in shares:
        work = flops_sdar.flush_work(cfg, e)
        assert work["bytes"] / 819e9 > work["flops"] / 197e12
        least += share * work["bytes"] / 819e9
    assert _read("dlm_tower_roofline", run) == pytest.approx(
        100.0 * least / 0.72)
    assert 60 < _read("dlm_tower_roofline", run) < 70
    assert _read("expert_product_roofline", run) == pytest.approx(
        100.0 * (2 + 1.0 / 3.0) * 128 * 6 * 21 * 3 * 2048 * 768 * 2 / 819e9
        / 0.56)
    assert _read("denoise_time_share.serve", run) == pytest.approx(
        100.0 * 2.45 / 2.8)
    # a driver that gives no traced window's instants: nothing to pair
    for name in ("dlm_tower_roofline", "expert_product_roofline"):
        assert _read(name, _record(events, trace, None, scopes)) is None
