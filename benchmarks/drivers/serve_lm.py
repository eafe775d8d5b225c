"""``drivers/serve.py`` for a configuration whose sentence tower is a
language model (``model.text_tower = 'lm'``): the same ``build_server``,
``RetrievalService.query_ids``, callers, window and trace; what differs is
what binds to the tower — the weights (``benchmarks/weights_axk1.py``, leaf
by leaf in bfloat16, beside the video tower's of ``benchmarks/weights.py``),
the program's ``text_lm`` group made from the configuration's published
keys, the ids' vocabulary, the work count (``benchmarks/flops_axk1.py``)
and the plain reference (``benchmarks/reference/axk1_text.py``, one layer's
float32 weights resident at a time).

``correct`` (the cell's file gives each limit and its readings).  A top-k
router is discontinuous, so a comparison of outputs alone reads the
router's near-ties and not the arithmetic (the reference's docstring).
After the window the driver therefore runs the program's tower again
over the sampled queries, from the seed's weights, with the routing its
expert layers sow made mutable (:func:`program_routing`) — once at every
rung of the engine's ladder, because on the chip a rung is a program of
its own whose bfloat16 bits, and so some of whose choices, are its own,
while within a rung a row's bits depend on the row alone, not on its
place or its batch-mates, and not on the routing being an output (my
chip run, PR 28) — and compares in three links:

- ``replay_err``: the served scores against those of the replay that
  comes closest, query by query (:func:`match_replay`) — the program
  agrees with itself at the rung a query was served at, so the routing
  read is the routing served;
- ``route_margin``: each choice against the reference's own router;
- ``rank_gap`` / ``score_err``: the served answers against the reference
  that took the same experts — maxima over every sampled query that now
  read arithmetic, and are held near it.

The run is ``drivers/serve.py``'s, step for step (its docstring).  A
checkout whose program has no ``text_lm`` group refuses the first flag of
it and exits at once, before anything is made.
"""

from __future__ import annotations

import gc
import os
import shutil
import time

import numpy as np

from benchmarks import compare, flops_axk1, harness, trace_reduce
from benchmarks import traffic_gen, weights, weights_axk1
from benchmarks.drivers import serve
from benchmarks.reference import axk1_text as reference_lm
from benchmarks.reference import retrieval as reference

# the program's own annotations beside the benchmark's two: an idle gap of
# the device is named by the holder's phase (``engine.text.get``: the host
# waits for the tower)
SPAN_NAMES = serve.SPAN_NAMES + tuple(
    f"{site}.{phase}" for site in ("engine.text", "index.topk")
    for phase in ("lock_wait", "put", "call", "get"))


def text_lm_flags(cfg: dict) -> list:
    """The program's ``text_lm`` group from the configuration's published
    keys: the router keeps its published width, ``n_routed_experts`` of
    the file is what this chip holds."""
    keys = ("hidden_size", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "num_experts_per_tok", "n_shared_experts",
            "moe_intermediate_size", "intermediate_size",
            "first_k_dense_replace", "routed_scaling_factor",
            "scoring_func", "norm_topk_prob", "topk_method", "rope_theta",
            "rms_norm_eps", "num_hidden_layers", "vocab_size")
    group = {k: cfg[k] for k in keys}
    group["n_routed_experts"] = cfg["published"]["n_routed_experts"]
    group["experts_held"] = cfg["n_routed_experts"]
    group["first_expert"] = cfg["share"]["first_expert"]
    for key, value in cfg["rope_scaling"].items():
        group[f"rope_scaling_{key}"] = value
    return harness.group_flags({"text_lm": group}, ("text_lm",))


def build_argv(cell, work: str, platform: str) -> list:
    return text_lm_flags(cell.config) + serve.build_argv(cell, work,
                                                         platform)


def video_tower_shapes(model: dict) -> dict:
    """The S3D-G leaves of ``benchmarks/weights.py`` without its
    bag-of-words sentence tower."""
    sized = dict(model, vocab_size=1, word_embedding_dim=1,
                 text_hidden_dim=1)
    return {n: s for n, s in weights.weight_shapes(sized).items()
            if not n.startswith("text_module/")}


def write_inputs(cell, cfg, seed: int, work: str) -> None:
    """The export and the corpus file, from the seed.  The language
    model's leaves come down from the device one at a time, bfloat16."""
    import jax

    from milnce_tpu.serving.export import export_inference_checkpoint

    flat = weights.make_weights(seed, video_tower_shapes(
        cell.config["model"]))
    host = jax.device_get(flat)
    stats = jax.device_get(weights.batch_stats_for(flat))
    del flat
    for name, shape in weights_axk1.weight_shapes(cell.config).items():
        host[name] = jax.device_get(
            weights_axk1.make_leaf(seed, name, shape, cell.config))
    export_inference_checkpoint(
        cfg.serve.export_dir, weights.nest(host), weights.nest(stats),
        cfg.model, max_words=cfg.data.max_words,
        video_shape=(cfg.data.num_frames, cfg.data.video_size,
                     cfg.data.video_size, 3),
        source="benchmarks/weights_axk1.py", text_lm=cfg.text_lm)
    del host, stats
    index = cell.config["index"]
    rows, dim = int(index["rows"]), int(index["dim"])
    emb = np.empty((rows, dim), np.dtype(index["stored_dtype"]))
    for block, (first, n) in enumerate(traffic_gen.corpus_blocks(rows)):
        emb[first:first + n] = jax.device_get(traffic_gen.corpus_block(
            seed, block, n, dim, index["stored_dtype"]))
    np.savez(cfg.serve.corpus_npz, emb=emb)


def corpus_blocks(seed: int, index: dict):
    rows, dim = int(index["rows"]), int(index["dim"])
    for b, (first, n) in enumerate(traffic_gen.corpus_blocks(rows)):
        yield first, traffic_gen.corpus_block(seed, b, n, dim,
                                              index["stored_dtype"])


def program_routing(cell, seed: int, tokens, alter=None) -> list:
    """The program's tower (``milnce_tpu/models/text_lm.py``, the seed's
    weights, the configuration's type) run again over ``tokens`` (S, W)
    at every rung of the engine's ladder, with the collection its expert
    layers sow their choices into made mutable.  -> a rung:
    {"emb" (S, D) float32, "experts": [(S, W, k) int32 an expert layer]}.
    ``alter`` (controls): a function of the parameter tree, applied
    before the runs."""
    import jax
    import jax.numpy as jnp

    from milnce_tpu.config import parse_cli
    from milnce_tpu.models import text_lm
    from milnce_tpu.serving.engine import bucket_ladder

    cfg = parse_cli(text_lm_flags(cell.config) + harness.group_flags(
        cell.config, ("model", "serve")))
    tower = text_lm.TextLM(text_lm.lm_dims(cfg.text_lm),
                           embd_dim=cfg.model.embedding_dim,
                           dtype=jnp.dtype(cfg.serve.dtype
                                           or cfg.model.dtype))
    params = weights.nest({
        name[len(weights_axk1.PREFIX) + 1:]: weights_axk1.make_leaf(
            seed, name, shape, cell.config)
        for name, shape in weights_axk1.weight_shapes(cell.config).items()})
    if alter is not None:
        params = alter(params)
    fn = jax.jit(lambda p, ids: tower.apply({"params": p}, ids,
                                            mutable=[text_lm.ROUTING]))
    tokens = np.asarray(tokens, np.int32)
    out = []
    for rung in bucket_ladder(cell.chips, cfg.serve.min_bucket,
                              cfg.serve.max_batch):
        embs, experts = [], []
        for first in range(0, len(tokens), rung):
            rows = tokens[first:first + rung]
            ids = np.zeros((rung, tokens.shape[1]), np.int32)
            ids[:len(rows)] = rows
            emb, sown = fn(params, jnp.asarray(ids))
            sown = sown[text_lm.ROUTING]
            layers = sorted(sown, key=lambda n: int(n.rsplit("_", 1)[1]))
            embs.append(np.asarray(emb[:len(rows)], np.float32))
            experts.append([np.asarray(sown[n]["moe"]["experts"][0])
                            [:len(rows)] for n in layers])
        out.append({"emb": np.concatenate(embs),
                    "experts": [np.concatenate(per)
                                for per in zip(*experts)]})
    del params
    return out


def match_replay(cell, seed: int, replays: list, served_idx,
                 served_scores):
    """Query by query, the replay whose float32 scores of the served rows
    lie closest to the served scores.  -> (each query's experts in that
    replay, an expert layer; ``replay_err``: the widest distance any query
    is left with, in units of its embedding's norm)."""
    import jax.numpy as jnp

    n, k = len(replays), served_idx.shape[1]
    emb = np.concatenate([np.asarray(r["emb"], np.float32)
                          for r in replays])
    at = reference.scan(jnp.asarray(emb), corpus_blocks(
        seed, cell.config["index"]), np.tile(served_idx, (n, 1)),
        k)["at_served"]
    err = (np.abs(np.asarray(at, np.float64).reshape(n, -1, k)
                  - np.asarray(served_scores, np.float64)[None]).max(axis=2)
           / np.linalg.norm(emb.astype(np.float64), axis=1).reshape(n, -1))
    pick, rows = err.argmin(axis=0), np.arange(err.shape[1])
    experts = [np.stack(per)[pick, rows]
               for per in zip(*(r["experts"] for r in replays))]
    return experts, float(err.min(axis=0).max())


def reference_numbers(cell, seed: int, tokens, served_idx, served_scores,
                      routing=None, precision: str = "float32") -> dict:
    """The reference over the sampled queries -> what is compared.
    ``routing``: the experts the program chose (:func:`program_routing`),
    which the reference measures against its own router and then takes.
    ``precision`` reaches the router's and the routed experts' products
    (the control); the scan stays float32."""
    import jax

    cfg, index = cell.config, cell.config["index"]
    q, route = reference_lm.query_embeddings(
        lambda prefix: weights_axk1.leaves_under(seed, cfg, prefix,
                                                 as_float32=True),
        tokens, cfg,
        layers=cfg["num_hidden_layers"],
        first_expert=cfg["share"]["first_expert"],
        experts_held=cfg["n_routed_experts"], precision=precision,
        follow=routing, routing=True)
    got = reference.scan(q, corpus_blocks(seed, index), served_idx,
                         served_idx.shape[1])
    q_norm = np.linalg.norm(np.asarray(jax.device_get(q), np.float64),
                            axis=1)
    got.update(q_norm=q_norm, emb=q,
               experts=[np.asarray(e) for e in route["experts"]])
    got["numbers"] = compare.retrieval_numbers(
        served_idx, served_scores, got["at_served"], got["top_scores"],
        q_norm, int(index["rows"]))
    got["numbers"]["route_margin"] = float(np.max(np.asarray(
        route["margin"])))
    return got


def judged(cell, seed: int, tokens, served_idx, served_scores,
           replays: list) -> dict:
    """-> ``compared`` (name -> value and limit, without ``unanswered``)
    for answers put in the served place and the program's replays."""
    experts, replay_err = match_replay(cell, seed, replays, served_idx,
                                       served_scores)
    numbers = reference_numbers(cell, seed, tokens, served_idx,
                                served_scores, routing=experts)["numbers"]
    numbers["replay_err"] = replay_err
    return {k: {"value": numbers[k], "limit": cell.limits[k]}
            for k in ("rank_gap", "score_err", "route_margin",
                      "replay_err")}


def control(cell, seed: int, tokens, kind: str) -> dict:
    """A control put in the served place over ``tokens``, judged as a run
    is -> ``compared``.  ``float8``: the reference with the router's and
    the routed experts' products in float8_e4m3fn, its own routing and
    answers (it replays as itself).  ``program``: the sound program's
    top-rung replay as what was served (no window: the readings behind
    the limits).  ``unrelated``: the same, its answers handed to the next
    query of the sample.  ``broken_expert``: the program with one held
    expert's down-projection zeroed, replayed by the sound one."""
    k = int(cell.config["serve"]["topk"])
    empty = np.zeros((len(tokens), k), np.int64)
    if kind == "float8":
        low = reference_numbers(cell, seed, tokens, empty, empty,
                                precision="float8")
        return judged(cell, seed, tokens, low["top_idx"],
                      low["top_scores"], [low])
    replays = program_routing(cell, seed, tokens)
    served = replays[-1]["emb"]
    if kind == "broken_expert":
        served = program_routing(cell, seed, tokens,
                                 alter=zero_an_expert)[-1]["emb"]
    elif kind == "unrelated":
        served = np.roll(served, 1, axis=0)
    elif kind != "program":
        raise ValueError(f"control {kind!r}")
    import jax.numpy as jnp

    top = reference.scan(jnp.asarray(served, jnp.float32),
                         corpus_blocks(seed, cell.config["index"]), empty, k)
    return judged(cell, seed, tokens, top["top_idx"], top["top_scores"],
                  replays)


def zero_an_expert(params: dict) -> dict:
    """The first held expert's down-projection of the first expert layer
    zeroed (a parameter tree, nested)."""
    moe = params["layers_1"]["moe"]
    moe["w_down"] = moe["w_down"].at[0].set(0.0)
    return params


def run(cell, *, seed: int, seconds: float, trace: bool, work: str,
        platform: str = "", t_start: float | None = None,
        fault=None) -> dict:
    """One run of a serving cell.  ``fault`` (tests only): a function of
    the built service, applied before the callers start, that breaks the
    timed path underneath."""
    import jax

    from milnce_tpu.config import parse_cli
    from milnce_tpu.obs import spans as obs_spans
    from milnce_tpu.serving import service as serving
    from milnce_tpu.utils.compile_cache import configure_compile_cache

    t_start = time.monotonic() if t_start is None else t_start
    cfg = parse_cli(build_argv(cell, work, platform))   # refuses at once
    #                       where the program has no text_lm group
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    configure_compile_cache()
    devices = jax.devices()[:cell.chips]
    traffic = cell.traffic
    write_inputs(cell, cfg, seed, work)

    recorder = obs_spans.SpanRecorder(ring=1 << 20)
    prev_recorder = obs_spans.install(recorder)
    server, svc, index, engine = serving.build_server(cfg)
    os.remove(cfg.serve.corpus_npz)
    shutil.rmtree(cfg.serve.export_dir, ignore_errors=True)
    try:
        if fault is not None:
            fault(svc)
        if trace:
            serve._annotate_index(index)
        pool = traffic_gen.query_pool(seed, traffic, cfg.text_lm.vocab_size,
                                      cfg.data.max_words)
        draws = traffic_gen.caller_draws(seed, traffic,
                                         per_caller=traffic.get(
                                             "draws_per_caller", 50_000))
        rows_per_call = int(traffic.get("rows_per_query", 1))
        callers = serve.Callers(svc, pool, draws, rows_per_call,
                                annotate=trace)
        callers.start()
        time.sleep(traffic["warmup_s"])
        t_open = time.monotonic()
        t_close = t_open + seconds
        trace_dir = os.path.join(work, "trace")
        trace_window = None
        if trace:
            time.sleep(serve.TRACE_START_S)
            trace_reduce.start_trace(trace_dir)
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
                t_from = time.monotonic()
                time.sleep(min(traffic.get("trace_s", 3.0),
                               max(0.5, t_close - t_from - 1.0)))
                t_to = time.monotonic()
            jax.profiler.stop_trace()
            trace_window = (t_from, t_to)
        time.sleep(max(0.0, t_close - time.monotonic()))
        never_came = callers.finish(traffic["answer_wait_s"])
        peak = harness.peak_bytes_in_use(devices)
        recompiles = {"engine": engine.recompiles(),
                      "index": index.recompiles()}
        cache_stats = svc.cache.stats()
        scan_queries = index.bucket_for(rows_per_call)
    finally:
        serving.close_server(cfg, server, svc, index, engine)
        obs_spans.install(prev_recorder)
    events = recorder.tail()
    # the second run and the reference need the chip's memory: nothing
    # may keep the closed service (and its 10.75 GB of weights)
    records = callers.records
    del server, svc, index, engine, callers
    gc.collect()
    jax.clear_caches()

    # ---- the window: calls of ``rows_per_call`` queries each -------------
    sent = [r for rec in records for r in rec
            if t_open <= r[0] < t_close]
    answered = [r for r in sent if r[3] is not None]
    failed = (len(sent) - len(answered) + never_came) * rows_per_call
    inside = [r for r in answered if r[1] <= t_close]
    if len(answered) < 20:
        errors = sorted({r[4] for r in sent if r[3] is None})[:3]
        raise RuntimeError(f"{len(answered)} calls answered in the "
                           "window: it is too short for this cell"
                           + (f"; refusals: {errors}" if errors else ""))
    lat_ms = [(r[1] - r[0]) * 1e3 for r in answered]
    metrics = {"queries_per_s": len(inside) * rows_per_call / seconds,
               "query_p95_ms": harness.percentile(lat_ms, 95),
               "setup_s": t_open - t_start}
    rows, dim = int(cell.config["index"]["rows"]), int(
        cell.config["index"]["dim"])
    asked = np.concatenate([r[2] for r in answered])
    mean_tokens = float((pool[asked] != 0).sum(axis=1).mean())
    tower_flops = flops_axk1.tower_flops(cell.config, mean_tokens, 1.0)
    record = harness.RunRecord(
        cell=cell, peaks=None, window_s=seconds,
        events=[e for e in events
                if t_open <= e.get("mono", 0.0) <= t_close],
        extra={"index_rows": rows, "index_dim": dim,
               "scan_queries": scan_queries,
               "mean_query_tokens": mean_tokens,
               "trace_window": trace_window,
               "work_per_item_flops": 2.0 * rows * dim + tower_flops})
    if trace:
        red = trace_reduce.reduce_trace(
            trace_reduce.find_xplane(trace_dir),
            layout=(trace_reduce.TPU_LAYOUT if devices[0].platform == "tpu"
                    else trace_reduce.CPU_LAYOUT),
            span_names=SPAN_NAMES,
            chips=cell.chips if devices[0].platform == "tpu" else None)
        record.trace = red
        record.traced_work = float(rows_per_call * sum(
            1 for r in answered
            if trace_window[0] <= r[1] <= trace_window[1]))

    # ---- correct: a sample of the answers against the reference ----------
    longest = int(np.argmax((pool[asked] != 0).sum(axis=1)))
    picks = traffic_gen.compare_sample(seed, len(asked),
                                       traffic["compare_sample"],
                                       always=(longest,))
    tokens = pool[asked[picks]]
    served_scores = np.concatenate([r[3] for r in answered])[picks]
    served_idx = np.concatenate([r[4] for r in answered])[picks]
    compared = judged(cell, seed, tokens, served_idx, served_scores,
                      program_routing(cell, seed, tokens))
    numbers = {k: v["value"] for k, v in compared.items()}
    compared["unanswered"] = {"value": float(failed), "limit": 0.0}
    text_flushes = [e for e in record.events
                    if e.get("name") == "dispatch"
                    and e.get("site") == "engine.text"]
    return {"metrics": metrics, "attempted": len(sent) * rows_per_call,
            "failed": failed,
            "record": record, "compared": compared, "peak_bytes": peak,
            "numbers": numbers,
            "notes": {"recompiles": recompiles, "cache": cache_stats,
                      "compared_answers": int(len(picks)),
                      "answered_in_window": len(inside) * rows_per_call,
                      "latency_p50_ms": harness.percentile(lat_ms, 50),
                      "mean_query_tokens": mean_tokens,
                      "text_flushes": len(text_flushes),
                      "text_flush_hold_ms_p50": (harness.percentile(
                          [e["hold_ms"] for e in text_flushes], 50)
                          if text_flushes else None),
                      "text_flush_buckets": {
                          str(b): sum(1 for e in text_flushes
                                      if e.get("bucket") == b)
                          for b in sorted({e.get("bucket")
                                           for e in text_flushes})}}}
