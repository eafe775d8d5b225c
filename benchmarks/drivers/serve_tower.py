"""``drivers/serve.py`` for a configuration whose sentence tower is a
language model, whichever: the same ``build_server``,
``RetrievalService.query_ids``, callers, window, trace and replay as
``drivers/serve_lm.py``, with what binds to the tower READ FROM THE
CONFIGURATION'S FILE (its ``bench`` group) instead of imported by name:

    "bench": {"weights": "weights_granite4h",   benchmarks/<weights>.py
              "flops": "flops_granite4h",       benchmarks/<flops>.py
              "reference": "granite4h_text",    benchmarks/reference/<..>.py
              "group": "text_hybrid",           the program's config group
              "module": "text_hybrid",          milnce_tpu/models/<..>.py
              "tower": "TextHybrid", "dims": "hybrid_dims",
              "program": "text_hybrid_tower",   the jitted program's name
              "scopes": [...],                  named scopes to time
              "program_keys": {"experts_held": "num_local_experts", ...}}

The program's group is made from the file's top-level keys of the same
names (a list comma-joined); ``program_keys`` names the keys that come
from elsewhere in the file (a dotted path).  The weights module gives
``PREFIX``, ``weight_shapes``, ``make_leaf``, ``leaves_under``; the flops
module ``tower_flops``; the reference ``query_embeddings``.

``correct`` is ``serve_lm.py``'s, link for link (its docstring):
``replay_err`` (the served scores against the replay at the rung a query
was served at), ``route_margin`` (each choice of the program against the
reference's own router), ``rank_gap`` / ``score_err`` (the served answers
against the float32 reference that took the same experts).

In a traced run the driver also reduces the trace by the tower's named
scopes (``benchmarks/scope_times.py``: ``Reduction`` holds no scopes) and
hands the seconds over in ``run.extra["scope_seconds"]``.

A checkout whose program has no such group refuses the first flag of it
and exits at once, before anything is made.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import os
import resource
import shutil
import time

import numpy as np

from benchmarks import compare, harness, scope_times, trace_reduce
from benchmarks import traffic_gen, weights
from benchmarks.drivers import serve, serve_lm
from benchmarks.reference import retrieval as reference

SPAN_NAMES = serve_lm.SPAN_NAMES


def bench(cfg: dict, what: str):
    """The module the configuration's ``bench`` group names for ``what``
    ('weights' | 'flops' | 'reference')."""
    name = cfg["bench"][what]
    where = "benchmarks.reference." if what == "reference" else "benchmarks."
    return importlib.import_module(where + name)


def _at(cfg: dict, path: str):
    for key in path.split("."):
        cfg = cfg[key]
    return cfg


def group_flags(cfg: dict) -> list:
    """The program's group (``bench.group``) from the configuration's
    published keys: every field of the program's group that the file has
    at its top level, and those ``bench.program_keys`` names."""
    from milnce_tpu import config as program

    name = cfg["bench"]["group"]
    if not hasattr(program.Config(), name):
        raise SystemExit(f"benchmarks/drivers/serve_tower.py: this "
                         f"checkout's program has no {name!r} group")
    fields = [f.name for f in dataclasses.fields(
        getattr(program.Config(), name))]
    elsewhere = cfg["bench"].get("program_keys", {})
    group = {}
    for field in fields:
        if field in elsewhere:
            value = _at(cfg, elsewhere[field])
        elif field in cfg:
            value = cfg[field]
        else:
            continue
        group[field] = (",".join(value) if isinstance(value, list)
                        else value)
    return harness.group_flags({name: group}, (name,))


def build_argv(cell, work: str, platform: str) -> list:
    return group_flags(cell.config) + serve.build_argv(cell, work, platform)


def tower_params(cell, seed: int) -> dict:
    """The tower's parameter tree on the device, from the seed."""
    w = bench(cell.config, "weights")
    return weights.nest({
        name[len(w.PREFIX) + 1:]: w.make_leaf(seed, name, shape, cell.config)
        for name, shape in w.weight_shapes(cell.config).items()})


def write_inputs(cell, cfg, seed: int, work: str) -> None:
    """The export and the corpus file, from the seed.  The language
    model's leaves come down from the device one at a time, in their own
    type."""
    import jax

    from milnce_tpu.serving.export import export_inference_checkpoint

    w = bench(cell.config, "weights")
    flat = weights.make_weights(seed, serve_lm.video_tower_shapes(
        cell.config["model"]))
    host = jax.device_get(flat)
    stats = jax.device_get(weights.batch_stats_for(flat))
    del flat
    for name, shape in w.weight_shapes(cell.config).items():
        host[name] = jax.device_get(w.make_leaf(seed, name, shape,
                                                cell.config))
    group = cell.config["bench"]["group"]
    export_inference_checkpoint(
        cfg.serve.export_dir, weights.nest(host), weights.nest(stats),
        cfg.model, max_words=cfg.data.max_words,
        video_shape=(cfg.data.num_frames, cfg.data.video_size,
                     cfg.data.video_size, 3),
        source=f"benchmarks/{cell.config['bench']['weights']}.py",
        **{group: getattr(cfg, group)})
    del host, stats
    index = cell.config["index"]
    rows, dim = int(index["rows"]), int(index["dim"])
    emb = np.empty((rows, dim), np.dtype(index["stored_dtype"]))
    for first, block in serve_lm.corpus_blocks(seed, index):
        emb[first:first + block.shape[0]] = jax.device_get(block)
    np.savez(cfg.serve.corpus_npz, emb=emb)


def program_routing(cell, seed: int, tokens) -> list:
    """The program's tower (the seed's weights, the configuration's type)
    run again over ``tokens`` (S, W) at every rung of the engine's ladder,
    with the collection its expert layers sow their choices into made
    mutable.  -> a rung: {"emb" (S, D) float32, "experts": [(S, W, k)
    int32 a layer]}."""
    import jax
    import jax.numpy as jnp

    from milnce_tpu.config import parse_cli
    from milnce_tpu.models import text_lm
    from milnce_tpu.serving.engine import bucket_ladder

    names = cell.config["bench"]
    cfg = parse_cli(group_flags(cell.config) + harness.group_flags(
        cell.config, ("model", "serve")))
    module = importlib.import_module(f"milnce_tpu.models.{names['module']}")
    tower = getattr(module, names["tower"])(
        getattr(module, names["dims"])(getattr(cfg, names["group"])),
        embd_dim=cfg.model.embedding_dim,
        dtype=jnp.dtype(cfg.serve.dtype or cfg.model.dtype))
    params = tower_params(cell, seed)
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


def reference_numbers(cell, seed: int, tokens, served_idx, served_scores,
                      routing=None, precision: str = "float32") -> dict:
    """The reference over the sampled queries -> what is compared.
    ``routing``: the experts the program chose (:func:`program_routing`),
    which the reference measures against its own router and then takes.
    ``precision`` reaches the mixers', the router's and the routed
    experts' products (the control); the scan stays float32."""
    import jax

    cfg, index = cell.config, cell.config["index"]
    w = bench(cfg, "weights")
    share = cfg["share"]
    q, route = bench(cfg, "reference").query_embeddings(
        lambda prefix: w.leaves_under(seed, cfg, prefix, as_float32=True),
        tokens, cfg, layers=cfg["num_hidden_layers"],
        first_expert=share["first_expert"],
        experts_held=share["experts_held"], precision=precision,
        follow=routing, routing=True)
    got = reference.scan(q, serve_lm.corpus_blocks(seed, index), served_idx,
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
    experts, replay_err = serve_lm.match_replay(cell, seed, replays,
                                                served_idx, served_scores)
    numbers = reference_numbers(cell, seed, tokens, served_idx,
                                served_scores, routing=experts)["numbers"]
    numbers["replay_err"] = replay_err
    return {k: {"value": numbers[k], "limit": cell.limits[k]}
            for k in ("rank_gap", "score_err", "route_margin",
                      "replay_err")}


@contextlib.contextmanager
def state_dropped():
    """The program's scan with the state that a chunk hands the next one
    DROPPED: every chunk starts from zero (each chunk scanned as a row of
    its own).  Controls only: the benchmark swaps the function the tower
    calls; the program has no such option."""
    from milnce_tpu.models import text_hybrid
    from milnce_tpu.ops import ssd

    def each_chunk_alone(x, dt, a, b, c, d, *, chunk):
        import jax.numpy as jnp

        rows, s = x.shape[:2]
        pad = -s % chunk

        def cut(t):
            t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            return t.reshape((rows * ((s + pad) // chunk), chunk)
                             + t.shape[2:])

        y = ssd.ssd_scan(cut(x), cut(dt), a, cut(b), cut(c), d, chunk=chunk)
        return y.reshape((rows, s + pad) + y.shape[2:])[:, :s]

    sound = text_hybrid.ssd_scan
    text_hybrid.ssd_scan = each_chunk_alone
    try:
        yield
    finally:
        text_hybrid.ssd_scan = sound


def control(cell, seed: int, tokens, kind: str) -> dict:
    """A control put in the served place over ``tokens``, judged as a run
    is -> ``compared``.  ``float8``: the reference with the mixers', the
    router's and the routed experts' products in float8_e4m3fn, its own
    routing and answers (it replays as itself).  ``program``: the sound
    program's top-rung replay as what was served (no window: the readings
    behind the limits).  ``state_dropped``: the program whose scan drops
    the state between chunks, replayed by the sound one.  ``unrelated``:
    the sound replay's answers handed to the next query of the sample."""
    import jax.numpy as jnp

    k = int(cell.config["serve"]["topk"])
    empty = np.zeros((len(tokens), k), np.int64)
    if kind == "float8":
        low = reference_numbers(cell, seed, tokens, empty, empty,
                                precision="float8")
        return judged(cell, seed, tokens, low["top_idx"],
                      low["top_scores"], [low])
    replays = program_routing(cell, seed, tokens)
    served = replays[-1]["emb"]
    if kind == "state_dropped":
        with state_dropped():
            served = program_routing(cell, seed, tokens)[-1]["emb"]
    elif kind == "unrelated":
        served = np.roll(served, 1, axis=0)
    elif kind != "program":
        raise ValueError(f"control {kind!r}")
    top = reference.scan(jnp.asarray(served, jnp.float32),
                         serve_lm.corpus_blocks(seed, cell.config["index"]),
                         empty, k)
    return judged(cell, seed, tokens, top["top_idx"], top["top_scores"],
                  replays)


def compared_sample(seed: int, lengths: np.ndarray, traffic: dict):
    """Which answers of a window are compared: ``compare_sample`` drawn
    from the seed, with the longest query and, where the traffic's file
    has ``compare_long``, ``at_least`` queries of more than ``over_tokens``
    real tokens (those there are: queries that reach into a second chunk
    of the scan) always among them."""
    always = [int(np.argmax(lengths))]
    long = traffic.get("compare_long")
    if long:
        always += [int(i) for i in np.flatnonzero(
            lengths > long["over_tokens"])[:long["at_least"]]]
    return traffic_gen.compare_sample(seed, len(lengths),
                                      traffic["compare_sample"],
                                      always=tuple(always))


def traced_scopes(engine, trace_dir: str, bench_names: dict, layout: dict):
    """Device seconds by the tower's named scopes, from the trace and the
    compiled text of the tower's program at every rung."""
    ops = scope_times.merge(
        scope_times.instruction_ops(engine.program_text("text", rung))
        for rung in engine.buckets)
    return scope_times.scope_seconds(
        trace_reduce.find_xplane(trace_dir), bench_names["program"], ops,
        bench_names["scopes"], layout)


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
    phases = {}

    def phase(name, since):
        phases[name] = round(time.monotonic() - since, 1)
        return time.monotonic()

    cfg = parse_cli(build_argv(cell, work, platform))   # refuses at once
    #                       where the program lacks the tower's group
    names = cell.config["bench"]
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    configure_compile_cache()
    devices = jax.devices()[:cell.chips]
    on_tpu = devices[0].platform == "tpu"
    layout = trace_reduce.TPU_LAYOUT if on_tpu else trace_reduce.CPU_LAYOUT
    traffic = cell.traffic
    write_inputs(cell, cfg, seed, work)
    t_phase = phase("inputs_written", t_start)

    recorder = obs_spans.SpanRecorder(ring=1 << 20)
    prev_recorder = obs_spans.install(recorder)
    server, svc, index, engine = serving.build_server(cfg)
    t_phase = phase("build_server", t_phase)
    os.remove(cfg.serve.corpus_npz)
    shutil.rmtree(cfg.serve.export_dir, ignore_errors=True)
    try:
        if fault is not None:
            fault(svc)
        if trace:
            serve._annotate_index(index)
        pool = traffic_gen.query_pool(
            seed, traffic, getattr(cfg, names["group"]).vocab_size,
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
        t_phase = time.monotonic()
        never_came = callers.finish(traffic["answer_wait_s"])
        peak = harness.peak_bytes_in_use(devices)
        recompiles = {"engine": engine.recompiles(),
                      "index": index.recompiles()}
        cache_stats = svc.cache.stats()
        scan_queries = index.bucket_for(rows_per_call)
        scopes, scope_error = None, None
        if trace:
            try:
                scopes = traced_scopes(engine, trace_dir, names, layout)
            except Exception as exc:        # the line says so, and the
                scope_error = repr(exc)     # readers of scopes read nothing
    finally:
        serving.close_server(cfg, server, svc, index, engine)
        obs_spans.install(prev_recorder)
    events = recorder.tail()
    # the replay and the reference need the chip's memory: nothing may
    # keep the closed service (and its weights)
    records = callers.records
    del server, svc, index, engine, callers
    gc.collect()
    jax.clear_caches()
    t_phase = phase("closed", t_phase)

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
    lengths = (pool[asked] != 0).sum(axis=1)
    mean_tokens = float(lengths.mean())
    tower_flops = bench(cell.config, "flops").tower_flops(
        cell.config, mean_tokens, 1.0)
    record = harness.RunRecord(
        cell=cell, peaks=None, window_s=seconds,
        events=[e for e in events
                if t_open <= e.get("mono", 0.0) <= t_close],
        extra={"index_rows": rows, "index_dim": dim,
               "scan_queries": scan_queries,
               "mean_query_tokens": mean_tokens,
               "trace_window": trace_window,
               "scope_seconds": scopes,
               "work_per_item_flops": 2.0 * rows * dim + tower_flops})
    if trace:
        record.trace = trace_reduce.reduce_trace(
            trace_reduce.find_xplane(trace_dir), layout=layout,
            span_names=SPAN_NAMES, chips=cell.chips if on_tpu else None)
        record.traced_work = float(rows_per_call * sum(
            1 for r in answered
            if trace_window[0] <= r[1] <= trace_window[1]))

    # ---- correct: a sample of the answers against the reference ----------
    picks = compared_sample(seed, lengths, traffic)
    tokens = pool[asked[picks]]
    served_scores = np.concatenate([r[3] for r in answered])[picks]
    served_idx = np.concatenate([r[4] for r in answered])[picks]
    replays = program_routing(cell, seed, tokens)
    t_phase = phase("replays", t_phase)
    compared = judged(cell, seed, tokens, served_idx, served_scores, replays)
    phase("reference", t_phase)
    numbers = {k: v["value"] for k, v in compared.items()}
    compared["unanswered"] = {"value": float(failed), "limit": 0.0}
    text_flushes = [e for e in record.events
                    if e.get("name") == "dispatch"
                    and e.get("site") == "engine.text"]
    slots = sum(e.get("tokens", 0) + e.get("pad_tokens", 0)
                for e in text_flushes)
    boot = {e["name"]: round(e["dur_ms"] * 1e-3, 1) for e in events
            if e.get("name") in ("engine.load", "corpus.load", "index.build",
                                 "ladder.warmup") and "dur_ms" in e}
    notes = {"recompiles": recompiles, "cache": cache_stats,
             "phases_s": phases, "boot_s": boot,
             "host_peak_rss_gb": round(resource.getrusage(
                 resource.RUSAGE_SELF).ru_maxrss / 1e6, 2),
             "compared_answers": int(len(picks)),
             "compared_tokens_max": int(lengths[picks].max()),
             "compared_over_256": int((lengths[picks] > 256).sum()),
             "answered_in_window": len(inside) * rows_per_call,
             "latency_p50_ms": harness.percentile(lat_ms, 50),
             "mean_query_tokens": mean_tokens,
             "text_flushes": len(text_flushes),
             "text_pad_share": (sum(e.get("pad_tokens", 0)
                                    for e in text_flushes) / slots
                                if slots else None),
             "text_flush_hold_ms_p50": (harness.percentile(
                 [e["hold_ms"] for e in text_flushes], 50)
                 if text_flushes else None),
             "text_flush_buckets": {
                 str(b): sum(1 for e in text_flushes
                             if e.get("bucket") == b)
                 for b in sorted({e.get("bucket")
                                  for e in text_flushes})}}
    if scope_error:
        notes["scope_error"] = scope_error
    if scopes:
        notes["scope_ms_inside"] = {k: round(v * 1e3, 3)
                                    for k, v in scopes["inside"].items()}
        notes["tower_top_ops_ms"] = [
            [name, round(s * 1e3, 3), op[-90:]] for name, (s, op) in sorted(
                scopes["ops"].items(), key=lambda kv: -kv[1][0])[:40]]
    return {"metrics": metrics, "attempted": len(sent) * rows_per_call,
            "failed": failed,
            "record": record, "compared": compared, "peak_bytes": peak,
            "numbers": numbers, "notes": notes}
