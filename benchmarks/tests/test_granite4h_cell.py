"""The cell ``query-step-granite4h-c32`` rehearsed on the CPU at tiny widths
(``cells/`` entries ``tiny-query-step`` / ``tiny-granite4h`` /
``tiny-steps-c4``): the ``serve_tower`` driver end to end, traced, with
what binds to the tower read from the configuration's ``bench`` group; the
five readers this configuration brings on records made by hand; the
reduction of a trace by named scope on a trace made by hand; the controls
(float8 products, the state between chunks dropped, unrelated answers)
held to the cell's limits through ``harness.judge``; and the work counts
against the shapes."""

import types

import numpy as np
import pytest

from benchmarks import flops_granite4h, harness, scope_times
from benchmarks import trace_reduce, weights_granite4h
from benchmarks.tests.conftest import run_cell, tiny_benchmark

CELL = "query-step-granite4h-c32"
NEW = {"ssd_scan_roofline": ("%", "higher"),
       "ssm_time_share.serve": ("%", "higher"),
       "hybrid_tower_roofline": ("%", "higher"),
       "ssm_chunk_fill.serve": ("%", "higher"),
       "hybrid_expert_tokens_mean.serve": ("tokens", "higher")}
# the catalog's row (model-configs guide), number for number
PUBLISHED = {
    "attention_multiplier": 0.0078125, "embedding_multiplier": 12,
    "hidden_size": 4096, "intermediate_size": 768, "logits_scaling": 16,
    "mamba_chunk_size": 256, "mamba_d_conv": 4, "mamba_d_head": 64,
    "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_n_heads": 128, "max_position_embeddings": 131072,
    "num_attention_heads": 32, "num_experts_per_tok": 10,
    "num_key_value_heads": 8, "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_theta": 10000,
    "shared_intermediate_size": 1536}


@pytest.fixture(scope="module")
def step_bench():
    """The rehearsal benchmark with the tiny hybrid cell, and the five
    metrics listed for it as BENCHMARK.json lists them for the real one."""
    bench = tiny_benchmark()
    bench["configs"].append({"name": "tiny-granite4h", "source": "rehearsal",
                             "file": "benchmarks/configs/tiny-granite4h.json",
                             "reduced": [], "why": "rehearsal"})
    bench["workloads"].append({"name": "tiny-query-step",
                               "config": "tiny-granite4h",
                               "traffic": "tiny-steps-c4", "chips": 1,
                               "why": "rehearsal"})
    for m in bench["end_to_end"]:
        if m["name"] in ("queries_per_s", "query_p95_ms"):
            m["workloads"].append("tiny-query-step")
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            m["workloads"] = ["tiny-query-step"]
    return bench


def test_benchmark_json_has_the_cell_as_the_issue_names_it():
    bench = harness.load_benchmark()
    (cfg,) = [c for c in bench["configs"]
              if c["name"] == "s3dg-granite4h-text-32f224"]
    assert cfg["source"].startswith(
        "https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/"
        "config.json")
    assert len(cfg["source"]) <= 200
    assert cfg["reduced"] == ["num_hidden_layers", "num_local_experts",
                              "vocab_size", "index_rows"]
    assert bench["configs"][-1] is cfg          # appended, nothing moved
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert bench["workloads"][-1] is cell and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "s3dg-granite4h-text-32f224", "text-steps512-c32", 1)
    assert [m["name"] for m in bench["per_layer"][-5:]] == list(NEW)
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
    # the five with the thirteen that list no cells
    names = harness.metric_names_for(bench, loaded, "per_layer")
    assert set(NEW) <= set(names) and len(names) == 18
    # no reader of the other language-model cell is read here
    assert not {"text_tower_roofline", "expert_tokens_mean.serve",
                "text_pad_share.serve"} & set(names)
    traffic = loaded.traffic
    assert (traffic["callers"], traffic["rows_per_query"], traffic["pool"],
            traffic["rank_exponent"], traffic["compare_sample"]) == (
        32, 1, 200000, 0.5, 128)
    assert traffic["words"] == {"min": 16, "max": 512, "median": 128,
                                "sigma": 0.7}


def test_configuration_file_keeps_every_published_width():
    cfg = harness.load_cell(harness.load_benchmark(), CELL).config
    assert {k: cfg[k] for k in PUBLISHED} == PUBLISHED
    assert cfg["layer_types"] == (["mamba"] * 5 + ["attention"]
                                  + ["mamba"] * 4) * 4      # as published
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "num_local_experts": 72,
                                "vocab_size": 100352}
    assert (cfg["num_hidden_layers"], cfg["num_local_experts"],
            cfg["vocab_size"]) == (10, 36, 50176)
    assert cfg["num_local_experts"] * cfg["share"][
        "chips_sharing_a_layer"] == cfg["published"]["num_local_experts"]
    assert cfg["share"]["experts_held"] == cfg["num_local_experts"]
    assert (cfg["data"]["max_words"], cfg["serve"]["min_bucket"],
            cfg["serve"]["max_batch"]) == (512, 4, 16)
    shapes = weights_granite4h.weight_shapes(cfg)
    params = sum(int(np.prod(s)) for s in shapes.values())
    assert params == 4_759_308_928              # 9.52 GB of bfloat16
    kinds = weights_granite4h.layer_kinds(cfg)
    assert (kinds.count("mamba"), kinds.count("attention")) == (9, 1)
    # the work count reads the same shapes: every matrix once; not the
    # token table (its touched rows only), not the vectors, not the conv
    small = sum(int(np.prod(s)) for n, s in shapes.items()
                if len(s) == 1 or n.endswith("/conv_w"))
    assert flops_granite4h.tower_bytes(cfg, 0) == 2 * (
        params - cfg["vocab_size"] * cfg["hidden_size"] - small)
    per_token = flops_granite4h.tower_flops(cfg, 1.0, 1.0)
    assert 3.25e9 < per_token < 3.4e9          # 3.31 GFLOP a token
    mamba = 9 * 2 * flops_granite4h.mamba_params(cfg)
    assert 0.5 < mamba / per_token < 0.6       # the mixers: 56% of it
    # a scan's bytes bound holds at a 16-row flush of 2,560 real tokens
    work = flops_granite4h.scan_work(cfg, 2560, 16, 8192)
    assert work["bytes"] / 819e9 > work["flops"] / 197e12


def test_the_programs_group_is_made_from_the_files_own_keys():
    from benchmarks.drivers import serve_tower
    from milnce_tpu.config import parse_cli
    from milnce_tpu.models import text_hybrid

    cfg = harness.load_cell(harness.load_benchmark(), CELL).config
    group = parse_cli(serve_tower.group_flags(cfg)).text_hybrid
    assert (group.num_local_experts, group.experts_held, group.first_expert,
            group.num_hidden_layers, group.vocab_size) == (72, 36, 0, 10,
                                                           50176)
    dims = text_hybrid.hybrid_dims(group)
    assert dims.layer_types == ("mamba",) * 5 + ("attention",) + (
        "mamba",) * 4
    assert {k: getattr(group, k) for k in PUBLISHED
            if hasattr(group, k)} == {k: v for k, v in PUBLISHED.items()
                                      if hasattr(group, k)}


def test_step_cell_runs_traced_and_every_reader_reads(step_bench, bench_dir,
                                                      tmp_path):
    result, out = run_cell(step_bench, bench_dir, "tiny-query-step",
                           tmp_path, trace=True, seconds=3.0)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 20
    assert out["notes"]["recompiles"] == {"engine": 0, "index": 0}
    got = result["metrics"]
    # the CPU's trace has no line of programs: the three device_trace
    # readers find nothing there and the line leaves them out
    assert {"ssm_chunk_fill.serve", "hybrid_expert_tokens_mean.serve",
            "flush_rows_mean.serve", "scan_rows_mean.serve",
            "query_mfu"} <= set(got), sorted(got)
    assert not {"ssd_scan_roofline", "ssm_time_share.serve",
                "hybrid_tower_roofline"} & set(got)
    assert "scope_error" not in out["notes"]
    assert 0 < got["ssm_chunk_fill.serve"]["value"] < 100
    assert got["hybrid_expert_tokens_mean.serve"]["value"] > 0
    flushes = [e for e in out["record"].events
               if e.get("name") == "dispatch"
               and e.get("site") == "engine.text"]
    assert flushes and all(
        e["tokens"] + e["pad_tokens"] == e["bucket"] * 20 for e in flushes)
    # two Mamba layers of the three, three chunks of 8 a 20-slot row
    assert all(e["ssm_chunks_run"] == 2 * 3 * e["bucket"] for e in flushes)
    assert out["notes"]["compared_tokens_max"] == 20
    assert out["notes"]["cache"]["misses"] > 100    # calls pass the tower


def _zeroed_out_projection(service):
    """The timed path broken underneath: the first Mamba mixer's output
    projection zeroed in the engine's resident weights."""
    import jax

    variables = service.engine._variables
    mixer = variables["params"]["text_module"]["layers_0"]["mamba"]
    mixer["w_out"] = jax.device_put(mixer["w_out"] * 0.0,
                                    mixer["w_out"].sharding)


def test_a_broken_mixer_comes_out_not_correct(step_bench, bench_dir,
                                              tmp_path):
    result, out = run_cell(step_bench, bench_dir, "tiny-query-step",
                           tmp_path, fault=_zeroed_out_projection)
    assert not result["correct"], out["numbers"]


@pytest.mark.parametrize("kind,correct", [
    ("program", True), ("float8", False), ("state_dropped", False),
    ("unrelated", False)])
def test_controls_in_the_served_place_are_judged_as_a_run_is(
        step_bench, bench_dir, kind, correct):
    """``program``: the sound program's second run as what was served.
    ``float8``: the reference put in the program's place with the mixers',
    the router's and the routed experts' products in float8_e4m3fn, one
    step below the bfloat16 the configuration states.  ``state_dropped``:
    the program whose scan hands no state from a chunk to the next.
    ``unrelated``: another query's answers.  On the chip they are read at
    the cell's own sizes and limits (PERF.md); here at the rehearsal's."""
    from benchmarks import traffic_gen

    cell = harness.load_cell(step_bench, "tiny-query-step",
                             bench_dir=bench_dir)
    driver = harness.load_driver("serve_tower", bench_dir)
    pool = traffic_gen.query_pool(5, cell.traffic, cell.config["vocab_size"],
                                  cell.config["data"]["max_words"])[:16]
    assert ((pool != 0).sum(axis=1) > 8).sum() >= 4     # over a chunk
    compared = driver.control(cell, 5, pool, kind)
    assert set(compared) == set(cell.limits)
    assert harness.judge(compared) == correct, compared


def test_the_compared_sample_always_holds_long_queries():
    from benchmarks.drivers import serve_tower

    rng = np.random.default_rng(0)
    lengths = rng.integers(16, 200, 900)
    lengths[[5, 77, 300, 640]] = [300, 512, 257, 400]
    traffic = {"compare_sample": 16,
               "compare_long": {"over_tokens": 256, "at_least": 3}}
    picks = serve_tower.compared_sample(9, lengths, traffic)
    assert len(picks) == 16 and len(set(picks.tolist())) == 16
    assert {77, 5, 300} <= set(picks.tolist())      # longest, then 3 long
    assert len(serve_tower.compared_sample(
        9, lengths, {"compare_sample": 16})) == 16


# ---- the trace by named scope, on a trace made by hand --------------------

HLO = '''HloModule jit_text_hybrid_tower
%fused_computation.1 (p: bf16[4,8]) -> bf16[4,8] {
  %p = bf16[4,8]{1,0} parameter(0)
}
ENTRY %main {
  %fusion.1 = bf16[4,8]{1,0} fusion(bf16[4,8]{1,0} %a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(text_hybrid_tower)/jit(main)/layers_0/text_hybrid/mamba/mamba/dot_general" source_file="x.py" source_line=3}
  %fusion.2 = f32[4,8]{1,0} fusion(bf16[4,8]{1,0} %fusion.1), kind=kOutput, calls=%fused_computation.2, metadata={op_name="jit(text_hybrid_tower)/jit(main)/layers_0/text_hybrid/mamba/mamba/text_hybrid/ssd/exp"}
  %while.3 = (s32[], f32[4,8]{1,0}) while(%tuple.1), condition=%cond, body=%body, metadata={op_name="jit(text_hybrid_tower)/jit(main)/layers_0/text_hybrid/moe/moe/while"}
  ROOT %custom-call.4 = f32[4,8]{1,0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(text_hybrid_tower)/jit(main)/layers_0/text_hybrid/moe/moe/while/body/jit(_grouped_matmul)/pallas_call"}
}
'''


def test_instructions_are_mapped_to_their_op_names():
    ops = scope_times.instruction_ops(HLO)
    assert set(ops) == {"%p", "%fusion.1", "%fusion.2", "%while.3",
                        "%custom-call.4"}
    assert ops["%fusion.2"][0][1].endswith("text_hybrid/ssd/exp")
    assert ", metadata=" not in ops["%fusion.1"][0][0]
    assert ops["%custom-call.4"][0][0].startswith("%custom-call.4 = f32")
    event = ("%fusion.1 = bf16[4,8]{1,0} fusion(bf16[4,8]{1,0} %a), "
             "kind=kLoop, calls=%fused_computation.1")
    assert "text_hybrid/mamba" in scope_times.op_name_of(event, ops)
    assert scope_times.op_name_of("%fusion.99 = f32[] fusion()", ops) == ""
    # the same name in another rung's program: the line that starts alike
    other = scope_times.instruction_ops(HLO.replace(
        "bf16[4,8]{1,0} fusion(bf16[4,8]{1,0} %a)",
        "bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %a)").replace(
        "text_hybrid/mamba/mamba/dot_general", "text_hybrid/attn/attn/dot"))
    both = scope_times.merge([ops, other])
    assert "text_hybrid/mamba" in scope_times.op_name_of(event, both)
    assert "text_hybrid/attn" in scope_times.op_name_of(
        event.replace("[4,8]", "[8,8]"), both)


def _event(name, start, end):
    return types.SimpleNamespace(name=name, start_ns=start * 1e9,
                                 duration_ns=(end - start) * 1e9)


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=n, events=evs) for n, evs in lines])


def test_device_time_by_scope_is_the_union_inside_the_programs_executions(
        monkeypatch):
    """Two executions of the tower, one of them cut by the window's end; a
    ``while`` that spans its body's kernel counts once; an operation of
    another program with the same instruction name counts nowhere."""
    tower = "jit_text_hybrid_tower(17)"
    trace = types.SimpleNamespace(planes=[
        _plane("/host:CPU", [("", [_event(trace_reduce.WINDOW_SPAN, 10.0,
                                          13.0)])]),
        _plane("/device:TPU:0", [
            ("XLA Modules", [_event(tower, 10.5, 11.5),
                             _event("jit_local_topk(3)", 11.6, 11.7),
                             _event(tower, 12.5, 13.5),
                             _event(tower, 20.0, 21.0)]),
            ("XLA Ops", [
                _event("%fusion.1 = bf16[4,8]{1,0} fusion(...)", 10.5, 10.9),
                _event("%fusion.2 = f32[4,8]{1,0} fusion(...)", 10.9, 11.0),
                _event("%while.3 = (s32[]) while(...)", 11.0, 11.5),
                _event("%custom-call.4 = f32[4,8] custom-call(...)", 11.1,
                       11.3),
                _event("%fusion.1 = f32[9] fusion(...)", 11.6, 11.7),
                _event("%fusion.1 = bf16[4,8]{1,0} fusion(...)", 12.5, 12.9),
                _event("%fusion.2 = f32[4,8]{1,0} fusion(...)", 12.9, 13.2),
                _event("%while.3 = (s32[]) while(...)", 13.2, 13.5),
                _event("%fusion.1 = bf16[4,8]{1,0} fusion(...)", 20.0,
                       20.4)])])])
    monkeypatch.setattr(trace_reduce, "load_profile", lambda path: trace)
    got = scope_times.scope_seconds(
        "x", "text_hybrid_tower", scope_times.instruction_ops(HLO),
        ("text_hybrid/mamba", "text_hybrid/ssd", "text_hybrid/moe",
         "grouped_matmul", "text_hybrid/attn"))
    whole, inside = got["whole"], got["inside"]
    assert whole["text_hybrid/mamba"] == pytest.approx(0.5 + 0.7)
    assert inside["text_hybrid/mamba"] == pytest.approx(0.5 + 0.5)
    assert whole["text_hybrid/ssd"] == pytest.approx(0.1 + 0.3)
    assert inside["text_hybrid/ssd"] == pytest.approx(0.1 + 0.1)
    assert whole["text_hybrid/moe"] == pytest.approx(0.5 + 0.3)
    assert inside["text_hybrid/moe"] == pytest.approx(0.5)
    assert whole["grouped_matmul"] == pytest.approx(0.2)
    assert whole["text_hybrid/attn"] == 0.0
    assert whole["*"] == pytest.approx(2.0) and inside["*"] == pytest.approx(
        1.5)
    assert got["ops"]["%custom-call.4"][0] == pytest.approx(0.2)
    # a trace without the program, or without a line of programs (the CPU)
    assert scope_times.scope_seconds("x", "text_lm_tower", {}, ()) is None
    assert scope_times.scope_seconds(
        "x", "text_hybrid_tower", {}, (),
        layout=trace_reduce.CPU_LAYOUT) is None


# ---- the five readers on records made by hand -----------------------------

class _Trace:
    def __init__(self, modules, busy_s, chips=1):
        self.module_seconds = modules
        self.module_inside = {k: float(len(v)) for k, v in modules.items()}
        self.busy_s, self.chips, self.window_s = busy_s, chips, 3.0


def _record(events, trace=None, trace_window=None, scopes=None):
    cell = harness.load_cell(harness.load_benchmark(), CELL)
    from benchmarks import peaks

    return harness.RunRecord(cell=cell, peaks=peaks.PEAKS["TPU v5 lite"],
                             events=events, window_s=20.0, trace=trace,
                             extra={"trace_window": trace_window,
                                    "scope_seconds": scopes})


def _flush(tokens, bucket, pairs, real_chunks, rows=None, mono=1.0):
    return {"kind": "span", "name": "dispatch", "site": "engine.text",
            "mono": mono, "rows": rows or bucket, "bucket": bucket,
            "tokens": tokens, "pad_tokens": bucket * 512 - tokens,
            "moe_pairs_held": pairs, "moe_expert_max": 400,
            "moe_pairs_total": tokens * 10 * 10, "moe_tile_rows": 2 * pairs,
            "ssm_chunks_run": 9 * 2 * bucket, "ssm_chunks_real": real_chunks,
            "hold_ms": 300.0}


def _read(name, run):
    return harness.layer_metric_module(name).read(run)


@pytest.mark.parametrize("name", sorted(NEW))
def test_nothing_to_read_is_none(name):
    """Another tower's records: a text flush without the scan's counters,
    a trace without the tower's program, no scopes.  None, nothing
    raised."""
    old = [{"kind": "span", "name": "dispatch", "site": "engine.text",
            "mono": 1.0, "rows": 8, "bucket": 16, "hold_ms": 2.0},
           {"kind": "span", "name": "batcher.flush", "mono": 1.0, "rows": 3}]
    trace = _Trace({"jit_local_topk(1)": [0.004],
                    "jit_text_lm_tower(2)": [0.03]}, 0.5)
    assert _read(name, _record(old, trace, (0.5, 3.5))) is None
    assert _read(name, _record([], None)) is None


def test_chunk_fill_and_expert_tokens_from_the_flush_records():
    events = [_flush(2560, 16, 12800, 9 * 19), _flush(900, 8, 4400, 9 * 9)]
    run = _record(events)
    assert _read("ssm_chunk_fill.serve", run) == pytest.approx(
        100.0 * (19 + 9) / (32 + 16))
    assert _read("hybrid_expert_tokens_mean.serve", run) == pytest.approx(
        (12800 + 4400) / 2 / (36 * 10))


def test_the_three_device_readers_from_a_trace_and_its_scopes():
    """The rooflines are taken over the flushes of the traced window, sum
    over sum (the flush that ended before the trace began is left out);
    the scan's least time is its bytes at the rung's slots; the Mamba
    share is of the window's busy time."""
    events = [_flush(2400, 16, 12000, 170, mono=4.0),   # before the trace
              _flush(2560, 16, 12800, 171, mono=10.4),
              _flush(1100, 8, 5500, 81, mono=10.9),
              _flush(2700, 16, 13500, 180, mono=13.2)]  # held from 12.9 on
    trace = _Trace({"jit_text_hybrid_tower(9)": [0.300, 0.310],
                    "jit_text_hybrid_tower(7)": [0.160],
                    "jit_local_topk(1)": [0.006] * 3}, busy_s=2.4)
    scopes = {"inside": {"text_hybrid/mamba": 1.2, "text_hybrid/ssd": 0.2,
                         "*": 2.3},
              "whole": {"text_hybrid/mamba": 1.5, "text_hybrid/ssd": 0.25,
                        "*": 2.6}, "ops": {}}
    run = _record(events, trace, (10.0, 13.0), scopes)
    cfg = run.cell.config
    traced = events[1:]
    least = 0.0
    for e in traced:
        by_flops = flops_granite4h.tower_flops(
            cfg, e["tokens"], e["rows"], e["moe_pairs_held"]) / 197e12
        assert by_flops > flops_granite4h.tower_bytes(cfg, e["tokens"]) / 819e9
        least += by_flops
    assert _read("hybrid_tower_roofline", run) == pytest.approx(
        100.0 * least / (0.300 + 0.310 + 0.160))
    scan_least = sum(9 * flops_granite4h.scan_bytes(cfg, e["bucket"] * 512)
                     / 819e9 for e in traced)
    assert _read("ssd_scan_roofline", run) == pytest.approx(
        100.0 * scan_least / 0.25)
    assert _read("ssm_time_share.serve", run) == pytest.approx(
        100.0 * 1.2 / 2.4)
    # a driver that gives no traced window's instants: nothing to pair
    assert _read("ssd_scan_roofline", _record(events, trace, None,
                                              scopes)) is None
    assert _read("hybrid_tower_roofline", _record(events, trace, None,
                                                  scopes)) is None
