"""The cell ``query-text-axk1-c64`` rehearsed on the CPU at tiny widths
(``cells/`` entries ``tiny-query-lm`` / ``tiny-axk1`` / ``tiny-tail-c4``):
the ``serve_lm`` driver end to end, traced, with the four readers this
configuration brings beside the twelve that were there; the readers on
records made by hand; the controls (float8 products, a broken expert,
unrelated answers) held to the cell's limits through ``harness.judge``;
and the work counts against the shapes."""

import numpy as np
import pytest

from benchmarks import flops_axk1, harness, weights_axk1
from benchmarks.tests.conftest import run_cell, tiny_benchmark

CELL = "query-text-axk1-c64"
NEW = {"text_tower_roofline": ("%", "higher"),
       "text_tower_time_share.serve": ("%", "higher"),
       "text_pad_share.serve": ("%", "lower"),
       "expert_tokens_mean.serve": ("tokens", "higher")}


@pytest.fixture(scope="module")
def lm_bench():
    """The rehearsal benchmark with the tiny language-model cell, and the
    four metrics listed for it as BENCHMARK.json lists them for the real
    one."""
    bench = tiny_benchmark()
    bench["configs"].append({"name": "tiny-axk1", "source": "rehearsal",
                             "file": "benchmarks/configs/tiny-axk1.json",
                             "reduced": [], "why": "rehearsal"})
    bench["workloads"].append({"name": "tiny-query-lm",
                               "config": "tiny-axk1",
                               "traffic": "tiny-tail-c4", "chips": 1,
                               "why": "rehearsal"})
    for m in bench["end_to_end"]:
        if m["name"] in ("queries_per_s", "query_p95_ms"):
            m["workloads"].append("tiny-query-lm")
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            m["workloads"] = ["tiny-query-lm"]
    return bench


def test_benchmark_json_has_the_cell_as_the_issue_names_it():
    bench = harness.load_benchmark()
    (cfg,) = [c for c in bench["configs"]
              if c["name"] == "s3dg-axk1-text-32f224"]
    assert cfg["source"].startswith(
        "https://huggingface.co/skt/A.X-K1/blob/main/config.json")
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size", "index_rows"]
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "s3dg-axk1-text-32f224", "text-tail32-c64", 1)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, (unit, better) in NEW.items():
        mod = harness.layer_metric_module(name)
        assert entries[name] == {
            "name": name, "unit": unit, "better": better,
            "source": mod.SOURCE, "layer": mod.LAYER, "moves": mod.MOVES,
            "workloads": [CELL]}
        assert mod.UNIT == unit
    loaded = harness.load_cell(bench, CELL)
    assert loaded.driver == "serve_lm" and set(loaded.limits) == {
        "rank_gap", "score_err", "route_margin", "replay_err"}
    # the cell reports every per-layer metric that moves what it reports
    assert set(NEW) <= set(harness.metric_names_for(bench, loaded,
                                                    "per_layer"))
    assert len(harness.metric_names_for(bench, loaded, "per_layer")) == 16


def test_configuration_file_keeps_every_published_width():
    cfg = harness.load_cell(harness.load_benchmark(), CELL).config
    widths = {"hidden_size": 7168, "intermediate_size": 18432,
              "moe_intermediate_size": 2048, "q_lora_rank": 1536,
              "kv_lora_rank": 512, "qk_nope_head_dim": 128,
              "qk_rope_head_dim": 64, "v_head_dim": 128,
              "num_attention_heads": 64, "num_experts_per_tok": 8,
              "n_shared_experts": 1}
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["published"] == {"num_hidden_layers": 61,
                                "n_routed_experts": 192,
                                "vocab_size": 163840}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (8, 12, 20480)
    assert cfg["n_routed_experts"] * cfg["share"][
        "chips_sharing_a_layer"] == cfg["published"]["n_routed_experts"]
    shapes = weights_axk1.weight_shapes(cfg)
    params = sum(int(np.prod(s)) for s in shapes.values())
    assert params == 5_373_238_272             # 10.75 GB of bfloat16
    # the work count reads the same shapes: every matrix once, not the
    # token table (its touched rows only) and not the norms' vectors
    vectors = sum(int(np.prod(s)) for n, s in shapes.items()
                  if n.endswith("/weight"))
    assert flops_axk1.tower_bytes(cfg, 0) == 2 * (
        params - cfg["vocab_size"] * cfg["hidden_size"] - vectors)
    per_token = flops_axk1.tower_flops(cfg, 1.0, 1.0)
    assert 3.3e9 < per_token < 3.4e9           # 3.35 GFLOP a token


def test_lm_cell_runs_traced_and_every_reader_reads(lm_bench, bench_dir,
                                                    tmp_path):
    result, out = run_cell(lm_bench, bench_dir, "tiny-query-lm", tmp_path,
                           trace=True, seconds=3.0)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 20
    assert out["notes"]["recompiles"] == {"engine": 0, "index": 0}
    got = result["metrics"]
    # the CPU's trace has no module line: the two device_trace readers of
    # the tower find nothing there and the line leaves them out
    assert {"text_pad_share.serve", "expert_tokens_mean.serve",
            "flush_rows_mean.serve", "scan_rows_mean.serve",
            "query_mfu"} <= set(got), sorted(got)
    assert 0 < got["text_pad_share.serve"]["value"] < 100
    assert got["expert_tokens_mean.serve"]["value"] > 0
    flushes = [e for e in out["record"].events
               if e.get("name") == "dispatch"
               and e.get("site") == "engine.text"]
    assert flushes and all(
        e["tokens"] + e["pad_tokens"] == e["bucket"] * 8 for e in flushes)
    # most calls pass through the tower
    assert out["notes"]["cache"]["hits"] < out["notes"]["cache"]["misses"]


def _altered_expert(service):
    """The timed path broken underneath: one held expert's down-projection
    zeroed in the engine's resident weights."""
    import jax

    variables = service.engine._variables
    leaf = variables["params"]["text_module"]["layers_1"]["moe"]["w_down"]
    variables["params"]["text_module"]["layers_1"]["moe"]["w_down"] = \
        jax.device_put(leaf.at[0].set(0.0), leaf.sharding)


def test_a_broken_expert_comes_out_not_correct(lm_bench, bench_dir,
                                               tmp_path):
    result, out = run_cell(lm_bench, bench_dir, "tiny-query-lm", tmp_path,
                           fault=_altered_expert)
    assert not result["correct"], out["numbers"]


@pytest.mark.parametrize("kind,correct", [
    ("program", True), ("float8", False), ("broken_expert", False),
    ("unrelated", False)])
def test_controls_in_the_served_place_are_judged_as_a_run_is(
        lm_bench, bench_dir, kind, correct):
    """``program``: the sound program's second run as what was served.
    ``float8``: the reference put in the program's place with the
    router's and the routed experts' products in float8_e4m3fn, one step
    below the bfloat16 the configuration states.  ``broken_expert``: one
    held expert's down-projection zeroed.  ``unrelated``: another query's
    answers.  On the chip they are read at the cell's own sizes and
    limits (PERF.md); here at the rehearsal's."""
    from benchmarks import traffic_gen

    cell = harness.load_cell(lm_bench, "tiny-query-lm", bench_dir=bench_dir)
    driver = harness.load_driver("serve_lm", bench_dir)
    pool = traffic_gen.query_pool(5, cell.traffic, cell.config["vocab_size"],
                                  cell.config["data"]["max_words"])[:16]
    compared = driver.control(cell, 5, pool, kind)
    assert set(compared) == set(cell.limits)
    assert harness.judge(compared) == correct, compared


def test_the_reference_follows_a_choice_and_measures_it(lm_bench,
                                                        bench_dir):
    """The reference given the program's experts takes them and reads 0
    margin where they are its own; given a token's worst choice swapped
    for an expert far down its own order it reads that distance, and
    only that query's embedding moves."""
    from benchmarks import traffic_gen

    cell = harness.load_cell(lm_bench, "tiny-query-lm", bench_dir=bench_dir)
    driver = harness.load_driver("serve_lm", bench_dir)
    pool = traffic_gen.query_pool(7, cell.traffic, cell.config["vocab_size"],
                                  cell.config["data"]["max_words"])[:8]
    empty = np.zeros((8, 5), np.int64)
    own = driver.reference_numbers(cell, 7, pool, empty, empty)
    assert own["numbers"]["route_margin"] == 0.0
    replays = driver.program_routing(cell, 7, pool)
    assert len(replays) == 2                    # the ladder's rungs: 4, 8
    for mine, theirs in zip(own["experts"], replays[-1]["experts"]):
        real = pool != 0
        assert np.array_equal(np.sort(mine[real]), np.sort(theirs[real]))
    same = driver.reference_numbers(cell, 7, pool, empty, empty,
                                    routing=own["experts"])
    assert same["numbers"]["route_margin"] == 0.0
    np.testing.assert_array_equal(np.asarray(same["emb"]),
                                  np.asarray(own["emb"]))
    swapped = [e.copy() for e in own["experts"]]
    taken = set(swapped[0][3, 0].tolist())
    absent = next(e for e in range(24) if e not in taken)
    swapped[0][3, 0, -1] = absent
    moved = driver.reference_numbers(cell, 7, pool, empty, empty,
                                     routing=swapped)
    assert moved["numbers"]["route_margin"] > 0.0
    differs = np.abs(np.asarray(moved["emb"])
                     - np.asarray(own["emb"])).max(axis=1) > 0
    assert differs.tolist() == [i == 3 for i in range(8)]


def test_each_query_is_matched_to_the_replay_it_was_served_from(
        lm_bench, bench_dir):
    """Two replays that differ (two rungs on the chip): a query answered
    from the first takes the first's experts, one answered from the second
    the second's, and nothing is left over; answers that came from neither
    leave their distance in ``replay_err``."""
    import jax.numpy as jnp

    from benchmarks.reference import retrieval

    cell = harness.load_cell(lm_bench, "tiny-query-lm", bench_dir=bench_dir)
    driver = harness.load_driver("serve_lm", bench_dir)
    rng = np.random.default_rng(0)
    replays = [{"emb": rng.standard_normal((6, 512)).astype(np.float32),
                "experts": [np.full((6, 8, 4), r), np.full((6, 8, 4), 10 + r)]}
               for r in range(2)]
    source = np.array([0, 1, 1, 0, 1, 0])
    served = np.stack([replays[r]["emb"][i] for i, r in enumerate(source)])
    empty = np.zeros((6, 5), np.int64)
    top = retrieval.scan(jnp.asarray(served), driver.corpus_blocks(
        3, cell.config["index"]), empty, 5)
    experts, err = driver.match_replay(cell, 3, replays, top["top_idx"],
                                       top["top_scores"])
    assert err < 1e-5
    assert [e[:, 0, 0].tolist() for e in experts] == [
        source.tolist(), (10 + source).tolist()]
    _, err = driver.match_replay(cell, 3, replays, top["top_idx"],
                                 top["top_scores"] + 22.6)  # ~1 norm off
    assert 0.8 < err < 1.2


# ---- the four readers on records made by hand ----------------------------

class _Trace:
    def __init__(self, modules, inside, busy_s, chips=1):
        self.module_seconds, self.module_inside = modules, inside
        self.busy_s, self.chips, self.window_s = busy_s, chips, 3.0


def _record(events, trace=None, trace_window=None):
    cell = harness.load_cell(harness.load_benchmark(), CELL)
    from benchmarks import peaks

    return harness.RunRecord(cell=cell, peaks=peaks.PEAKS["TPU v5 lite"],
                             events=events, window_s=20.0, trace=trace,
                             extra={"trace_window": trace_window})


def _flush(tokens, bucket, pairs, rows=None, mono=1.0):
    return {"kind": "span", "name": "dispatch", "site": "engine.text",
            "mono": mono, "rows": rows or bucket, "bucket": bucket,
            "tokens": tokens, "pad_tokens": bucket * 32 - tokens,
            "moe_pairs_held": pairs, "moe_expert_max": 40,
            "moe_pairs_total": tokens * 8 * 7, "hold_ms": 30.0}


def _read(name, run):
    return harness.layer_metric_module(name).read(run)


@pytest.mark.parametrize("name", sorted(NEW))
def test_nothing_to_read_is_none(name):
    """The parent commit's records: a text flush without ``tokens``, a
    trace without the tower's module.  None, and nothing raised."""
    old = [{"kind": "span", "name": "dispatch", "site": "engine.text",
            "mono": 1.0, "rows": 8, "bucket": 16, "hold_ms": 2.0},
           {"kind": "span", "name": "batcher.flush", "mono": 1.0, "rows": 3}]
    trace = _Trace({"jit_local_topk(1)": [0.004], "jit_local(2)": [0.0002]},
                   {"jit_local_topk(1)": 1.0, "jit_local(2)": 1.0}, 0.5)
    assert _read(name, _record(old, trace, (0.5, 3.5))) is None
    assert _read(name, _record([], None)) is None


def test_pad_share_and_expert_tokens_from_the_flush_records():
    events = [_flush(300, 32, 1050), _flush(212, 16, 742)]
    run = _record(events)
    slots = 32 * 32 + 16 * 32
    assert _read("text_pad_share.serve", run) == pytest.approx(
        100.0 * (slots - 512) / slots)
    assert _read("expert_tokens_mean.serve", run) == pytest.approx(
        (1050 + 742) / 2 / (12 * 7))


def test_tower_roofline_and_time_share_from_a_trace():
    """The roofline share is taken over the flushes of the traced window,
    sum over sum: the flush that ended before the trace began is left
    out, and which rung is the median moves nothing."""
    events = [_flush(290, 32, 1000, mono=4.0),      # before the trace
              _flush(300, 32, 1050, mono=10.2), _flush(900, 64, 3150,
                                                       mono=11.0),
              _flush(310, 32, 1085, mono=12.99),
              _flush(150, 16, 520, mono=13.02)]     # held from 12.99 on
    trace = _Trace({"jit_text_lm_tower(9)": [0.030, 0.032],
                    "jit_text_lm_tower(11)": [0.050],
                    "jit_text_lm_tower(7)": [0.020],
                    "jit_local_topk(1)": [0.004] * 3},
                   {"jit_text_lm_tower(9)": 2.0, "jit_text_lm_tower(11)": 1.0,
                    "jit_text_lm_tower(7)": 0.5, "jit_local_topk(1)": 3.0},
                   busy_s=0.150)
    run = _record(events, trace, trace_window=(10.0, 13.0))
    cfg = run.cell.config
    # under ~770 tokens the weights' bytes bound a flush; 900 are over
    by_bytes = flops_axk1.tower_bytes(cfg, 310) / 819e9
    assert flops_axk1.tower_flops(cfg, 310, 32, 1085) / 197e12 < by_bytes
    by_flops = flops_axk1.tower_flops(cfg, 900, 64, 3150) / 197e12
    assert by_flops > flops_axk1.tower_bytes(cfg, 900) / 819e9
    least = (flops_axk1.tower_bytes(cfg, 300) / 819e9 + by_flops + by_bytes
             + flops_axk1.tower_bytes(cfg, 150) / 819e9)
    assert _read("text_tower_roofline", run) == pytest.approx(
        100.0 * least / (0.030 + 0.032 + 0.050 + 0.020))
    inside = 0.031 * 2.0 + 0.050 * 1.0 + 0.020 * 0.5
    assert _read("text_tower_time_share.serve", run) == pytest.approx(
        100.0 * inside / 0.150)
    # a record of the window without the traced window's instants (a
    # driver that gives none): nothing to pair, nothing read
    assert _read("text_tower_roofline", _record(events, trace)) is None
