"""``drivers/serve_tower.py`` for a sentence tower that WRITES before it
embeds (``milnce_tpu/models/text_dlm.py``: an expansion of the query
denoised block by block, then the embedding of query and expansion): the
run IS ``serve_tower.run`` — the same ``build_server``,
``RetrievalService.query_ids``, callers, window, trace and scopes, with
what binds to the tower read from the configuration's ``bench`` group —
with this module's replay and comparison in the places of that module's
(:func:`run`).  What differs is what is replayed and compared: a
generation, not one forward pass.

``correct``, link for link (the cell's file gives each limit and its
readings).  An argmax over 151,936 logits of random weights, like a top-k
router, is discontinuous: the largest logit changes on rounding, so a
comparison of outputs alone reads near-ties and not arithmetic.  After the
window the driver therefore runs the program's tower again over the sampled
queries, from the seed's weights, with its TRAJECTORY made an output
(:func:`program_trajectory`: which token each written position took at
which pass, every pass's experts, the logits of each block's first pass) —
once at every rung of the engine's ladder, as ``serve_lm.py`` does and for
its reason — and compares:

- ``replay_err``: the served scores against those of the replay that comes
  closest, query by query (``serve_lm.match_replay``): the program agrees
  with itself at the rung a query was served at, so the trajectory read is
  the trajectory served;
- the float32 reference (``benchmarks/reference/sdar_text.py``: no cache,
  every pass a full forward of the whole row) TEACHER-FORCED on that
  trajectory and those experts measures each choice against its own
  numbers: ``route_margin`` (every choice of every pass against the
  reference's own router), ``commit_margin`` (in the reference's logits of
  each pass, how far the committed token lies under the reference's best
  and the committed positions under the rule applied to the reference's
  confidences; 0 = the reference's own choices), ``logit_err`` (the
  program's logits of each block's first pass at its masked positions
  against the reference's: RMS over the vocabulary in units of the
  reference's spread there: "compare logits, not sampled tokens");
- ``rank_gap`` / ``score_err``: the served answers against the reference
  that took the same trajectory and experts.

Because the reference has no cache, agreement is also "prefill, then
decoding through the cache, against the full forward pass".

A checkout whose program has no such group refuses the first flag of it
and exits at once, before anything is made.
"""

from __future__ import annotations

import contextlib
import importlib

import numpy as np

from benchmarks import compare, harness
from benchmarks.drivers import serve_lm, serve_tower
from benchmarks.drivers.serve_tower import bench, group_flags
from benchmarks.reference import retrieval as reference

# what a replay hands over beside its embeddings, each with the queries
# first: the written tokens and the pass each was committed at (S, blocks,
# L); the experts of the prefill (S, layers, W, k), of every denoise pass
# (S, blocks, L passes, layers, L, k) and of every commit pass (S, blocks,
# layers, L, k); the logits of each block's first pass (S, blocks, L, V)
TRAJECTORY = ("tokens", "step", "prefill", "denoise", "commit", "logits")
COMPARED = ("rank_gap", "score_err", "route_margin", "commit_margin",
            "logit_err", "replay_err")


def program_trajectory(cell, seed: int, tokens) -> list:
    """The program's tower (the seed's weights, the configuration's type)
    run again over ``tokens`` (S, W) at every rung of the engine's ladder
    with its trajectory made an output.  -> a rung: {"emb" (S, D) float32,
    "experts": ``TRAJECTORY``'s arrays} (the key is ``serve_lm
    .match_replay``'s: what a query takes from the replay that matches
    it)."""
    import jax
    import jax.numpy as jnp

    from milnce_tpu.config import parse_cli
    from milnce_tpu.serving.engine import bucket_ladder

    names = cell.config["bench"]
    cfg = parse_cli(group_flags(cell.config) + harness.group_flags(
        cell.config, ("model", "serve")))
    module = importlib.import_module(f"milnce_tpu.models.{names['module']}")
    tower = getattr(module, names["tower"])(
        getattr(module, names["dims"])(getattr(cfg, names["group"])),
        embd_dim=cfg.model.embedding_dim,
        dtype=jnp.dtype(cfg.serve.dtype or cfg.model.dtype))
    params = serve_tower.tower_params(cell, seed)
    fn = jax.jit(lambda p, ids: tower.apply({"params": p}, ids, trace=True))
    tokens = np.asarray(tokens, np.int32)
    out = []
    for rung in bucket_ladder(cell.chips, cfg.serve.min_bucket,
                              cfg.serve.max_batch):
        embs, parts = [], {k: [] for k in TRAJECTORY}
        for first in range(0, len(tokens), rung):
            rows = tokens[first:first + rung]
            ids = np.zeros((rung, tokens.shape[1]), np.int32)
            ids[:len(rows)] = rows
            emb, got = fn(params, jnp.asarray(ids))
            n = len(rows)
            embs.append(np.asarray(emb[:n], np.float32))
            for key, value in (
                    ("tokens", np.asarray(got["tokens"]).transpose(1, 0, 2)),
                    ("step", np.asarray(got["step"]).transpose(1, 0, 2)),
                    ("prefill", np.asarray(got["prefill_experts"])
                     .transpose(1, 0, 2, 3)),
                    ("denoise", np.asarray(got["denoise_experts"])
                     .transpose(3, 0, 1, 2, 4, 5)),
                    ("commit", np.asarray(got["commit_experts"])
                     .transpose(2, 0, 1, 3, 4)),
                    ("logits", np.asarray(got["logits"])
                     .transpose(1, 0, 2, 3))):
                parts[key].append(value[:n])
            del got
        out.append({"emb": np.concatenate(embs),
                    "experts": [np.concatenate(parts[k])
                                for k in TRAJECTORY]})
    del params
    return out


def reference_numbers(cell, seed: int, tokens, served_idx, served_scores,
                      trajectory: dict) -> dict:
    """The float32 reference teacher-forced on ``trajectory``
    (``TRAJECTORY``'s keys) -> what is compared."""
    import jax

    cfg, index = cell.config, cell.config["index"]
    w = bench(cfg, "weights")
    experts = {k: trajectory[k] for k in ("prefill", "denoise", "commit")}
    got = bench(cfg, "reference").teacher_forced(
        lambda prefix: w.leaves_under(seed, cfg, prefix, as_float32=True),
        tokens, trajectory["tokens"], trajectory["step"], cfg,
        layers=cfg["num_hidden_layers"],
        first_expert=cfg["share"]["first_expert"],
        experts_held=cfg["share"]["experts_held"], experts=experts,
        program_logits=trajectory["logits"])
    q = got["emb"]
    scanned = reference.scan(q, serve_lm.corpus_blocks(seed, index),
                             served_idx, served_idx.shape[1])
    q_norm = np.linalg.norm(np.asarray(jax.device_get(q), np.float64),
                            axis=1)
    numbers = compare.retrieval_numbers(
        served_idx, served_scores, scanned["at_served"],
        scanned["top_scores"], q_norm, int(index["rows"]))
    for name in ("route_margin", "commit_margin", "logit_err"):
        numbers[name] = float(np.max(got[name]))
    return numbers


def judged(cell, seed: int, tokens, served_idx, served_scores,
           replays: list) -> dict:
    """-> ``compared`` (name -> value and limit, without ``unanswered``)
    for answers put in the served place and the program's replays."""
    picked, replay_err = serve_lm.match_replay(cell, seed, replays,
                                               served_idx, served_scores)
    numbers = reference_numbers(cell, seed, tokens, served_idx,
                                served_scores, dict(zip(TRAJECTORY, picked)))
    numbers["replay_err"] = replay_err
    return {k: {"value": numbers[k], "limit": cell.limits[k]}
            for k in COMPARED}


@contextlib.contextmanager
def altered(what: str):
    """The program with one piece of its attention's visibility broken.
    ``causal_in_block``: a block's positions see only those up to their own
    (the block mask made causal).  ``cache_dropped``: no position of an
    earlier block is seen (the key/value cache of earlier blocks dropped).
    Controls only: the benchmark swaps the function the tower calls; the
    program has no such option."""
    import jax.numpy as jnp

    from milnce_tpu.models import text_dlm

    name, broken = {
        "causal_in_block": ("in_block_visible", lambda rows, span: (
            jnp.broadcast_to(jnp.tril(jnp.ones((span, span), bool)),
                             (rows, span, span)))),
        "cache_dropped": ("earlier_visible", lambda start, positions, span: (
            jnp.zeros((start.shape[0], span, positions), bool))),
    }[what]
    sound = getattr(text_dlm, name)
    setattr(text_dlm, name, broken)
    try:
        yield
    finally:
        setattr(text_dlm, name, sound)


def control(cell, seed: int, tokens, kind: str) -> dict:
    """A control put in the served place over ``tokens``, judged as a run
    is -> ``compared``.  ``float8``: the reference generating FREE with
    the products in float8_e4m3fn, its own trajectory, experts and
    answers (it replays as itself).  ``program``: the sound
    program's top-rung replay as what was served (no window: the readings
    behind the limits).  ``causal_in_block`` / ``cache_dropped``: the
    program with that piece broken (:func:`altered`), replayed by the
    sound one.  ``unrelated``: the sound replay's answers handed to the
    next query of the sample."""
    import jax.numpy as jnp

    cfg = cell.config
    k = int(cfg["serve"]["topk"])
    empty = np.zeros((len(tokens), k), np.int64)
    if kind == "float8":
        w = bench(cfg, "weights")
        low = bench(cfg, "reference").generate(
            lambda prefix: w.leaves_under(seed, cfg, prefix,
                                          as_float32=True),
            tokens, cfg, layers=cfg["num_hidden_layers"],
            first_expert=cfg["share"]["first_expert"],
            experts_held=cfg["share"]["experts_held"], precision="float8")
        served = np.asarray(low["emb"], np.float32)
        replays = [{"emb": served, "experts": [low[k] for k in TRAJECTORY]}]
    else:
        replays = program_trajectory(cell, seed, tokens)
        served = replays[-1]["emb"]
        if kind in ("causal_in_block", "cache_dropped"):
            with altered(kind):
                served = program_trajectory(cell, seed, tokens)[-1]["emb"]
        elif kind == "unrelated":
            served = np.roll(served, 1, axis=0)
        elif kind != "program":
            raise ValueError(f"control {kind!r}")
    top = reference.scan(jnp.asarray(served, jnp.float32),
                         serve_lm.corpus_blocks(seed, cfg["index"]), empty, k)
    return judged(cell, seed, tokens, top["top_idx"], top["top_scores"],
                  replays)


def generation_notes(flushes: list) -> dict:
    """What the window's flushes wrote, from their records' counters."""
    def total(name):
        return sum(e.get(name, 0) for e in flushes)

    passes = total("gen_passes_denoise") + total("gen_passes_commit")
    if not flushes or not passes:
        return {}
    return {"gen_passes_per_flush": round(passes / len(flushes), 2),
            "gen_tokens_per_row_pass": round(
                total("gen_tokens") / max(1, total("gen_row_passes")), 4),
            "gen_row_fill": round(
                total("gen_row_passes") / max(1, total("gen_row_slots")), 4),
            "gen_tokens": total("gen_tokens"),
            "moe_experts_touched_per_layer_pass": round(
                total("moe_experts_touched") / (passes + len(flushes)), 1)}


@contextlib.contextmanager
def _bound(module, **names):
    """``module``'s ``names`` bound to other values for a while."""
    kept = {name: getattr(module, name) for name in names}
    for name, value in names.items():
        setattr(module, name, value)
    try:
        yield
    finally:
        for name, value in kept.items():
            setattr(module, name, value)


def run(cell, *, seed: int, seconds: float, trace: bool, work: str,
        platform: str = "", t_start: float | None = None,
        fault=None) -> dict:
    """One run of a serving cell: ``serve_tower.run``, step for step (its
    docstring), with this module's replay and comparison in the places of
    its ``program_routing`` and ``judged`` (that file is the benchmark's
    and may not be edited; it finds both by their names in its own
    module), and what the window's flushes wrote added to the notes."""
    with _bound(serve_tower, program_routing=program_trajectory,
                judged=judged):
        out = serve_tower.run(cell, seed=seed, seconds=seconds, trace=trace,
                              work=work, platform=platform, t_start=t_start,
                              fault=fault)
    flushes = [e for e in out["record"].events
               if e.get("name") == "dispatch"
               and e.get("site") == "engine.text"]
    out["notes"].update(generation_notes(flushes))
    if flushes:
        out["notes"]["text_flush_rows_mean"] = (
            sum(e.get("rows", 0) for e in flushes) / len(flushes))
    return out
