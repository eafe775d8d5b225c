"""The cell ``query-step-solar2-c32`` rehearsed on the CPU at tiny widths
(``cells/`` entries ``tiny-query-kda`` / ``tiny-solar2`` /
``tiny-steps-c4``): the ``serve_tower`` driver end to end, traced, over a
tower of Kimi Delta Attention layers beside a gated attention layer, with
what binds to the tower read from the configuration's ``bench`` group; the
three readers this configuration brings on records made by hand, and on a
parent-like record without its scopes and counters; the controls (float8
products, the delta rule's state between chunks dropped, unrelated
answers) held to the cell's limits through ``harness.judge``; the weights'
shapes against ``jax.eval_shape`` of the module; the work counts."""

import numpy as np
import pytest

from benchmarks import flops_solar2, harness, weights_solar2
from benchmarks.tests.conftest import run_cell, tiny_benchmark

CELL = "query-step-solar2-c32"
NEW = {"kda_time_share.serve": ("%", "higher"),
       "kda_scan_roofline": ("%", "higher"),
       "kda_chunk_fill.serve": ("%", "higher")}
# the catalog's row (model-configs guide), number for number
PUBLISHED = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_attention_heads": 64, "head_dim": 128,
    "num_key_value_heads": 8, "vocab_size": 196608,
    "intermediate_size": 10240, "moe_intermediate_size": 1280,
    "rms_norm_eps": 1e-05, "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3,
    "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_shared_experts": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 1,
    "num_experts_per_tok": 8}


@pytest.fixture(scope="module")
def kda_bench():
    """The rehearsal benchmark with the tiny KDA cell, and the three
    metrics listed for it as BENCHMARK.json lists them for the real one."""
    bench = tiny_benchmark()
    bench["configs"].append({"name": "tiny-solar2", "source": "rehearsal",
                             "file": "benchmarks/configs/tiny-solar2.json",
                             "reduced": [], "why": "rehearsal"})
    bench["workloads"].append({"name": "tiny-query-kda",
                               "config": "tiny-solar2",
                               "traffic": "tiny-steps-c4", "chips": 1,
                               "why": "rehearsal"})
    for m in bench["end_to_end"]:
        if m["name"] in ("queries_per_s", "query_p95_ms"):
            m["workloads"].append("tiny-query-kda")
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            m["workloads"] = ["tiny-query-kda"]
    return bench


def test_benchmark_json_has_the_cell_as_the_issue_names_it():
    bench = harness.load_benchmark()
    (cfg,) = [c for c in bench["configs"]
              if c["name"] == "s3dg-solar2-text-32f224"]
    assert cfg["source"] == ("https://huggingface.co/upstage/Solar-Open2-"
                             "250B/blob/main/config.json")
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "index_rows"]
    assert len(cfg["why"]) <= 200
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "s3dg-solar2-text-32f224", "text-steps512-c32", 1)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, (unit, better) in NEW.items():
        mod = harness.layer_metric_module(name)
        assert entries[name] == {
            "name": name, "unit": unit, "better": better,
            "source": mod.SOURCE, "layer": mod.LAYER, "moves": mod.MOVES,
            "workloads": [CELL]}
        assert mod.UNIT == unit
    loaded = harness.load_cell(bench, CELL)
    assert loaded.driver == "serve_tower" and set(loaded.limits) == {
        "rank_gap", "score_err", "route_margin", "replay_err"}
    names = harness.metric_names_for(bench, loaded, "per_layer")
    assert set(NEW) <= set(names)
    # no reader of another tower's cell is read here
    assert not {"ssd_scan_roofline", "ssm_time_share.serve",
                "hybrid_tower_roofline", "text_tower_roofline",
                "dlm_tower_roofline"} & set(names)
    # nor a reader of this one in any other cell
    for other in bench["workloads"]:
        if other["name"] != CELL:
            assert not set(NEW) & set(harness.metric_names_for(
                bench, harness.load_cell(bench, other["name"]), "per_layer"))


def test_configuration_file_keeps_every_published_number():
    import jax
    import jax.numpy as jnp

    from benchmarks.drivers import serve_tower
    from milnce_tpu.config import parse_cli
    from milnce_tpu.models import text_hybrid

    cfg = harness.load_cell(harness.load_benchmark(), CELL).config
    assert {k: cfg[k] for k in PUBLISHED} == PUBLISHED
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "n_routed_experts": 320}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"]) == (4, 40)
    assert cfg["layer_types"] == [
        "attention" if i in cfg["gqa_layers"] else "kda" for i in range(48)]
    assert cfg["n_routed_experts"] * cfg["share"][
        "chips_sharing_a_layer"] == cfg["published"]["n_routed_experts"]
    assert cfg["share"]["experts_held"] == cfg["n_routed_experts"]
    assert (cfg["data"]["max_words"], cfg["serve"]["min_bucket"],
            cfg["serve"]["max_batch"]) == (512, 4, 16)
    shapes = weights_solar2.weight_shapes(cfg)
    params = sum(int(np.prod(s)) for s in shapes.values())
    assert params == 3_914_428_992             # 7.83 GB of bfloat16
    # the module's own tree, by jax.eval_shape, leaf for leaf
    group = parse_cli(serve_tower.group_flags(cfg)).text_hybrid
    tower = text_hybrid.TextHybrid(text_hybrid.hybrid_dims(group),
                                   embd_dim=cfg["model"]["embedding_dim"])
    tree = jax.eval_shape(tower.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]
    flat = {"/".join([weights_solar2.PREFIX] + [str(p.key) for p in path]):
            tuple(leaf.shape) for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert flat == {k: tuple(v) for k, v in shapes.items()}


def test_the_programs_group_is_made_from_the_files_own_keys():
    from benchmarks.drivers import serve_tower
    from milnce_tpu.config import parse_cli
    from milnce_tpu.models import text_hybrid

    cfg = harness.load_cell(harness.load_benchmark(), CELL).config
    group = parse_cli(serve_tower.group_flags(cfg)).text_hybrid
    assert (group.num_local_experts, group.experts_held, group.first_expert,
            group.num_hidden_layers, group.vocab_size) == (320, 40, 0, 4,
                                                           196608)
    # a routed expert's width and the shared one's: moe_intermediate_size,
    # never the published dense width
    assert (group.intermediate_size, group.shared_intermediate_size) == (
        1280, 1280)
    assert (group.kda_num_heads, group.kda_head_dim,
            group.kda_short_conv_kernel_size, group.kda_proj_rank,
            group.kda_chunk_size) == (64, 128, 4, 128, 64)
    assert (group.head_dim, group.use_gqa_gate, group.scoring_func,
            group.norm_topk_prob, group.routed_scaling_factor) == (
        128, True, "sigmoid", True, 1.0)
    assert (group.embedding_multiplier, group.residual_multiplier) == (1, 1)
    assert group.attention_multiplier == pytest.approx(128 ** -0.5)
    dims = text_hybrid.hybrid_dims(group)
    assert dims.layer_types == ("attention", "kda", "kda", "kda")


def test_kda_cell_runs_traced_and_every_reader_reads(kda_bench, bench_dir,
                                                     tmp_path):
    result, out = run_cell(kda_bench, bench_dir, "tiny-query-kda", tmp_path,
                           trace=True, seconds=3.0)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 20
    assert out["notes"]["recompiles"] == {"engine": 0, "index": 0}
    got = result["metrics"]
    # the CPU's trace has no line of programs: the two device_trace
    # readers find nothing there and the line leaves them out
    assert {"kda_chunk_fill.serve", "flush_rows_mean.serve",
            "query_mfu"} <= set(got), sorted(got)
    assert not {"kda_time_share.serve", "kda_scan_roofline"} & set(got)
    assert "scope_error" not in out["notes"]
    assert 0 < got["kda_chunk_fill.serve"]["value"] < 100
    flushes = [e for e in out["record"].events
               if e.get("name") == "dispatch"
               and e.get("site") == "engine.text"]
    # two KDA layers of the three, three chunks of 8 a 20-slot row
    assert flushes and all(e["kda_chunks_run"] == 2 * 3 * e["bucket"]
                           for e in flushes)
    assert out["notes"]["cache"]["misses"] > 100    # calls pass the tower


@pytest.mark.parametrize("kind,correct", [
    ("program", True), ("float8", False), ("state_dropped", False),
    ("unrelated", False)])
def test_controls_in_the_served_place_are_judged_as_a_run_is(
        kda_bench, bench_dir, kind, correct):
    """``program``: the sound program's second run as what was served.
    ``float8``: the reference with the mixers', the router's and the
    routed experts' products in float8_e4m3fn.  ``state_dropped``: the
    program whose delta rule hands no state from a chunk to the next.
    ``unrelated``: another query's answers.  On the chip they are read at
    the cell's own sizes and limits (PERF.md); here at the rehearsal's."""
    from benchmarks import controls_kda, traffic_gen

    cell = harness.load_cell(kda_bench, "tiny-query-kda",
                             bench_dir=bench_dir)
    pool = traffic_gen.query_pool(5, cell.traffic, cell.config["vocab_size"],
                                  cell.config["data"]["max_words"])[:16]
    assert ((pool != 0).sum(axis=1) > 8).sum() >= 4     # over a chunk
    compared = controls_kda.control(cell, 5, pool, kind)
    assert set(compared) == set(cell.limits)
    assert harness.judge(compared) == correct, compared


# ---- the three readers on records made by hand ----------------------------

class _Trace:
    def __init__(self, busy_s):
        self.module_seconds, self.busy_s, self.window_s = {}, busy_s, 3.0


def _record(events, trace=None, trace_window=None, scopes=None):
    cell = harness.load_cell(harness.load_benchmark(), CELL)
    from benchmarks import peaks

    return harness.RunRecord(cell=cell, peaks=peaks.PEAKS["TPU v5 lite"],
                             events=events, window_s=20.0, trace=trace,
                             extra={"trace_window": trace_window,
                                    "scope_seconds": scopes})


def _flush(tokens, bucket, real_chunks, mono, hold_ms=200.0):
    return {"kind": "span", "name": "dispatch", "site": "engine.text",
            "mono": mono, "rows": bucket, "bucket": bucket,
            "tokens": tokens, "pad_tokens": bucket * 512 - tokens,
            "moe_pairs_held": tokens * 4, "moe_expert_max": 90,
            "moe_pairs_total": tokens * 8 * 4, "moe_tile_rows": tokens * 8,
            "ssm_chunks_run": 0, "ssm_chunks_real": 0,
            "kda_chunks_run": 3 * 8 * bucket,
            "kda_chunks_real": real_chunks, "hold_ms": hold_ms}


def _read(name, run):
    return harness.layer_metric_module(name).read(run)


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_parent_like_record_reads_none(name):
    """What the parent's program leaves: text flushes without the KDA
    counters, a trace whose scopes hold no ``text_hybrid/kda``, no scopes
    at all.  None, nothing raised."""
    old = [{"kind": "span", "name": "dispatch", "site": "engine.text",
            "mono": 1.0, "rows": 16, "bucket": 16, "tokens": 2600,
            "pad_tokens": 5592, "ssm_chunks_run": 288,
            "ssm_chunks_real": 150, "hold_ms": 380.0},
           {"kind": "span", "name": "batcher.flush", "mono": 1.0, "rows": 3}]
    scopes = {"inside": {"text_hybrid/mamba": 1.2, "text_hybrid/ssd": 0.2,
                         "*": 2.3},
              "whole": {"text_hybrid/mamba": 1.5, "*": 2.6}, "ops": {}}
    assert _read(name, _record(old, _Trace(2.4), (0.5, 3.5), scopes)) is None
    assert _read(name, _record(old, _Trace(2.4), (0.5, 3.5), None)) is None
    assert _read(name, _record([], None)) is None


def test_the_three_readers_from_flush_records_and_scopes():
    """The chunk fill over every flush of the window; the roofline over
    the TRACED window, a flush counted by the share of its hold inside it
    (the one that ended before the trace began is left out, the one held
    from 12.9 s to 13.1 s counts half); the KDA share of the window's
    busy time."""
    events = [_flush(2400, 16, 3 * 48, mono=4.0),
              _flush(2560, 16, 3 * 50, mono=10.4),
              _flush(1100, 8, 3 * 22, mono=10.9),
              _flush(2700, 16, 3 * 52, mono=13.1)]
    scopes = {"inside": {"text_hybrid/kda": 1.5, "text_hybrid/kda_scan": 0.1,
                         "*": 2.3},
              "whole": {"text_hybrid/kda": 1.7, "text_hybrid/kda_scan": 0.12,
                        "*": 2.6}, "ops": {}}
    run = _record(events, _Trace(busy_s=2.4), (10.0, 13.0), scopes)
    cfg = run.cell.config
    assert _read("kda_chunk_fill.serve", run) == pytest.approx(
        100.0 * 3 * (48 + 50 + 22 + 52) / (3 * 8 * (16 + 16 + 8 + 16)))
    least = 0.0
    for e, share in ((events[1], 1.0), (events[2], 1.0), (events[3], 0.5)):
        work = flops_solar2.scan_work(cfg, e["kda_chunks_real"])
        assert work["bytes"] / 819e9 > work["flops"] / 197e12   # bytes-bound
        least += share * work["bytes"] / 819e9
    assert _read("kda_scan_roofline", run) == pytest.approx(
        100.0 * least / 0.1)
    assert _read("kda_time_share.serve", run) == pytest.approx(
        100.0 * 1.5 / 2.4)
    # a driver that gives no traced window's instants: nothing to pair
    assert _read("kda_scan_roofline", _record(events, _Trace(2.4), None,
                                              scopes)) is None


def test_the_work_counts_read_the_shapes():
    cfg = harness.load_cell(harness.load_benchmark(), CELL).config
    shapes = weights_solar2.weight_shapes(cfg)
    kda_layer = sum(int(np.prod(s)) for n, s in shapes.items()
                    if n.startswith("text_module/layers_1/kda/")
                    and len(s) == 2 and "/conv_" not in n)
    assert flops_solar2.kda_params(cfg) == kda_layer
    attn_layer = sum(int(np.prod(s)) for n, s in shapes.items()
                     if n.startswith("text_module/layers_0/attn/"))
    assert flops_solar2.attention_params(cfg) == attn_layer
    per_token = flops_solar2.tower_flops(cfg, 1.0, 1.0)
    assert 1.3e9 < per_token < 1.4e9           # 1.34 GFLOP a real token
    kda = 3 * 2 * flops_solar2.kda_params(cfg)
    assert 0.6 < kda / per_token < 0.65        # the KDA mixers: 62% of it
    # the rule itself, a token a layer at chunks of 64 (64 heads): 8.9 MFLOP
    assert 8.8e6 < flops_solar2.chunk_flops(cfg) * 64 / 64 < 9e6
