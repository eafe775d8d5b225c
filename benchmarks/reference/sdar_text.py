"""The plain reference of the SDAR-30B-A3B-Chat sentence tower
(JetLM/SDAR-30B-A3B-Chat ``config.json``, ``model_type`` ``sdar_moe``: the
Qwen3-MoE block key for key, generating by diffusion over blocks): float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``, NO cache,
no kernels, no grouping of tokens: every pass is a full forward of the
whole row — the query, the blocks written so far, the current block with
its masks — under the block mask.  One layer's float32 weights are
resident at a time and the experts run under one ``lax.scan`` (each held
expert over EVERY token, times the weight the token gave it).  It takes the
benchmark's weights (``benchmarks/weights_sdar.py``, made again from the
seed) and nothing the program made.

**The architecture** (``config`` wins over prose):

- *Layer* (every one: ``decoder_sparse_step`` 1, ``mlp_only_layers`` [];
  ``intermediate_size`` is unused): ``x += Attn(RMSNorm(x)); x +=
  MoE(RMSNorm(x))``, eps ``rms_norm_eps``, no biases.
- *Attn*: q = x Wq -> ``num_attention_heads`` x ``head_dim``; k, v = x Wk,
  x Wv -> ``num_key_value_heads`` x ``head_dim``; a per-head RMSNorm of
  width ``head_dim`` on q and on k (ASSUMED: the family's modelling code
  does it unconditionally, no key states it); rotary position on the whole
  head, ``rope_theta``, no scaling, HALVES rotated (``rotate_half``: the
  pair of dimension i is dimension i + head_dim / 2); scores /
  sqrt(head_dim); each key/value head serves ``num_attention_heads /
  num_key_value_heads`` query heads; output Wo.  **Visibility is by
  blocks**: with block length L, position i sees position j iff ``j // L <=
  i // L``: whole earlier blocks, and its own block in BOTH directions.
- *MoE*: p = softmax over all ``num_experts`` router logits; the
  ``num_experts_per_tok`` largest; their weights divided by their sum
  (``norm_topk_prob``); y = sum of w_e W_down,e (silu(W_gate,e h) * W_up,e
  h), expert width ``moe_intermediate_size``; no shared expert.
- *Generation by diffusion over blocks*: a row holds its n query tokens and
  then mask tokens up to a whole number of blocks.  Block by block, from
  the one that holds the query's last ``n % L`` tokens: a **denoise pass**
  gives logits at the block's masked positions; each one's candidate = the
  argmax (ids 0 and the mask id left out: ASSUMED), its confidence = that
  candidate's softmax probability over the whole vocabulary; the **commit
  rule** ``low_confidence_dynamic``: every masked position whose
  confidence exceeds the threshold if there are at least ``L /
  denoising_steps`` of them, else the ``L / denoising_steps`` most
  confident (ties: the earlier position); until the block holds no mask.

Departures, each the configuration's (its file lists them under
``assumed`` / ``reduced``): random weights; L, ``denoising_steps``, the
threshold, greedy candidates and the mask id are the service's settings
(the catalog gives none); the EMBEDDING is the final RMSNorm at the last
written position of the finished row, times a bias-free projection
``proj`` (hidden -> 512); the chip's share ``(first_expert,
experts_held)`` as in the other towers' references (here the whole layer).

``precision='float8'`` is the control: the inputs (activations and
weights) of the attention's four projections, the router's product, the
experts' three products and the head's rounded to ``float8_e4m3fn``, one
step below the bfloat16 the configuration states.

Two ways to run:

- :func:`generate` — FREE: the reference's own argmax and its own rule,
  pass after pass (the CPU tests; the float8 control);
- :func:`teacher_forced` — on a GIVEN trajectory (which token each
  written position took at which pass) and, where given, the program's
  experts: the passes are then independent of each other and run layer by
  layer side by side.  The reference measures every choice of the program
  against its own numbers — ``route_margin`` (as
  ``benchmarks/reference/axk1_text.py``), ``commit_margin`` (below),
  ``logit_err`` — and goes on with the PROGRAM's choices.  Because the
  reference has no cache, agreement is also "prefill, then decoding
  through the cache, against the full forward pass".
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

PREFIX = "text_module"
SERVICE = "text_dlm"        # the file's group of generation settings


def _round(x, precision: str):
    if precision == "float32":
        return x
    if precision == "float8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    raise ValueError(f"precision {precision!r}")


def rms(x, weight, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rotary(x, theta: float):
    """x (N, T, heads, head_dim) at positions 0..T-1, halves rotated."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles)[None, :, None], jnp.sin(angles)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def block_visible(lengths, positions: int, span: int, causal: bool = False):
    """(N, T, T): position i sees j iff j's block is not later than i's
    and j is a position of the row (and always itself)."""
    at = jnp.arange(positions)
    if causal:
        blocks = at[None, :] <= at[:, None]
    else:
        blocks = at[None, :] // span <= at[:, None] // span
    there = at[None, :] < lengths[:, None]
    return ((blocks[None] & there[:, None, :])
            | jnp.eye(positions, dtype=bool)[None])


def attention(h, w: dict, lengths, lm: dict, span: int,
              precision: str = "float32"):
    """h (N, T, hidden) -> the attention's output."""
    r = lambda t: _round(t, precision)      # noqa: E731
    n, t, _ = h.shape
    heads, kv, hd = (lm["num_attention_heads"], lm["num_key_value_heads"],
                     lm["head_dim"])
    q = (r(h) @ r(w["wq"])).reshape(n, t, heads, hd)
    k = (r(h) @ r(w["wk"])).reshape(n, t, kv, hd)
    v = (r(h) @ r(w["wv"])).reshape(n, t, kv, hd)
    q = rotary(rms(q, w["q_norm"], lm["rms_norm_eps"]), lm["rope_theta"])
    k = rotary(rms(k, w["k_norm"], lm["rms_norm_eps"]), lm["rope_theta"])
    k, v = (jnp.repeat(x, heads // kv, axis=2) for x in (k, v))
    scores = jnp.einsum("nqhd,nkhd->nhqk", q, k) / np.sqrt(hd)
    probs = jax.nn.softmax(jnp.where(
        block_visible(lengths, t, span)[:, None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("nhqk,nkhd->nqhd", probs, v).reshape(n, t, heads * hd)
    return r(out) @ r(w["wo"])


def moe(h, w: dict, real, lm: dict, first_expert: int, experts_held: int,
        precision: str = "float32", follow=None):
    """h (T, hidden), real (T,), follow (T, k) int or None -> (this
    share's part of the routed sum, the (T, k) experts whose outputs were
    added, the (T,) route margin of ``follow``: zeros without it)."""
    r = lambda a: _round(a, precision)      # noqa: E731
    logits = r(h) @ r(w["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top, chosen = lax.top_k(probs, lm["num_experts_per_tok"])
    margin = jnp.zeros(h.shape[:1], jnp.float32)
    if follow is not None:
        chosen = follow
        taken = jnp.any(follow[:, :, None]
                        == jnp.arange(logits.shape[1])[None, None], axis=1)
        worst_in = jnp.min(jnp.where(taken, logits, jnp.inf), axis=1)
        best_out = jnp.max(jnp.where(taken, -jnp.inf, logits), axis=1)
        margin = jnp.where(real, jnp.maximum(best_out - worst_in, 0.0), 0.0)
        top = jnp.take_along_axis(probs, follow, axis=1)
    if lm["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)

    def add_expert(out, expert):
        j, gate, up, down = expert
        weight = jnp.sum(jnp.where(chosen == first_expert + j, top, 0.0),
                         axis=-1)
        weight = jnp.where(real, weight, 0.0)
        mid = jax.nn.silu(r(h) @ r(gate)) * (r(h) @ r(up))
        return out + weight[:, None] * (r(mid) @ r(down)), None

    out, _ = lax.scan(add_expert, jnp.zeros_like(h),
                      (jnp.arange(experts_held), w["w_gate"], w["w_up"],
                       w["w_down"]))
    return out, chosen, margin


def layer(x, w: dict, lengths, lm: dict, span: int, first_expert: int,
          experts_held: int, precision: str = "float32", follow=None):
    """One layer over whole rows -> (x, the (N, T, k) experts added, the
    (N, T) route margins)."""
    eps = lm["rms_norm_eps"]
    x = x + attention(rms(x, w["attn_norm"], eps), w, lengths, lm, span,
                      precision)
    n, t, hidden = x.shape
    real = (jnp.arange(t)[None, :] < lengths[:, None]).reshape(-1)
    routed, chosen, margin = moe(
        rms(x, w["mlp_norm"], eps).reshape(-1, hidden), w, real, lm,
        first_expert, experts_held, precision,
        None if follow is None else follow.reshape(n * t, -1))
    return (x + routed.reshape(x.shape), chosen.reshape(n, t, -1),
            margin.reshape(n, t))


_layer = jax.jit(layer, static_argnames=("span", "first_expert",
                                         "experts_held", "precision"),
                 static_argnums=(3,))


class _Frozen(dict):
    """The published keys as a hashable static argument."""

    def __hash__(self):
        return hash(repr(sorted(self.items(), key=lambda kv: kv[0])))


def _freeze(lm: dict) -> _Frozen:
    return _Frozen({k: (tuple(v) if isinstance(v, list) else v)
                    for k, v in lm.items() if not isinstance(v, dict)})


def forward(get_weights, tokens, lengths, lm: dict, *, layers: int,
            first_expert: int, experts_held: int,
            precision: str = "float32", follow=None, chunk_rows: int = 256,
            top=None):
    """Whole rows through all layers, no cache.  ``tokens`` (N, T) int,
    ``lengths`` (N,): a row's positions ``0..length-1``.  ``follow``: one
    (N, T, k) int array a layer — the experts to take.  ``get_weights
    (prefix)`` -> {name: float32 array}, called once a layer.  -> (x after
    the last layer (N, T, hidden) float32, {"experts": [(N, T, k) a
    layer], "margin": (N,) each row's largest route margin})."""
    tokens = np.asarray(tokens, np.int32)
    lengths = np.asarray(lengths, np.int32)
    n, t = tokens.shape
    span = lm[SERVICE]["block_length"]
    frozen = _freeze(lm)
    pad = -n % chunk_rows if n > chunk_rows else 0
    if pad:     # one shape for every chunk: one program
        tokens = np.concatenate([tokens, np.zeros((pad, t), np.int32)])
        lengths = np.concatenate([lengths, np.zeros((pad,), np.int32)])
    cuts = range(0, n + pad, chunk_rows)
    with jax.default_matmul_precision("highest"):
        top = get_weights(PREFIX + "/") if top is None else top
        table = jnp.asarray(top["embed"], jnp.float32)
        xs = [jnp.take(table, jnp.asarray(tokens[lo:lo + chunk_rows]), axis=0)
              for lo in cuts]
        del table
        experts, margin = [], np.zeros((n + pad,), np.float32)
        for i in range(layers):
            w_i = get_weights(f"{PREFIX}/layers_{i}/")
            chosen_all = np.zeros((n + pad, t, lm["num_experts_per_tok"]),
                                  np.int32)
            for at, lo in enumerate(cuts):
                rows = slice(lo, lo + chunk_rows)
                taken = None
                if follow is not None:
                    taken = np.zeros_like(chosen_all[rows])
                    given = np.asarray(follow[i][lo:lo + chunk_rows],
                                       np.int32)
                    taken[:len(given)] = given
                    taken = jnp.asarray(taken)
                xs[at], chosen, margins = _layer(
                    xs[at], w_i, jnp.asarray(lengths[rows]), frozen,
                    span=span, first_expert=first_expert,
                    experts_held=experts_held, precision=precision,
                    follow=taken)
                chosen_all[rows] = np.asarray(chosen)
                margin[rows] = np.maximum(margin[rows],
                                          np.asarray(margins).max(axis=1))
            del w_i
            experts.append(chosen_all[:n])
    x = jnp.concatenate(xs)[:n] if len(xs) > 1 else xs[0][:n]
    return x, {"experts": experts, "margin": margin[:n]}


@jax.jit
def _logits(x, norm, head, eps):
    return rms(x, norm, eps) @ head


def logits_of(x, top: dict, lm: dict, precision: str = "float32"):
    """x (M, hidden) -> (M, vocabulary) float32 logits."""
    with jax.default_matmul_precision("highest"):
        if precision == "float32":
            return _logits(x, top["norm"], top["head"], lm["rms_norm_eps"])
        return (_round(rms(x, top["norm"], lm["rms_norm_eps"]), precision)
                @ _round(top["head"], precision))


def embed_last(x_last, top: dict, lm: dict):
    """x_last (N, hidden) -> (N, 512): the final norm, then ``proj``."""
    with jax.default_matmul_precision("highest"):
        return rms(x_last, top["norm"], lm["rms_norm_eps"]) @ top["proj"]


def candidates(logits, mask_id: int):
    """-> (candidate ids: the argmax with ids 0 and ``mask_id`` left out;
    their log-confidences over the whole vocabulary)."""
    allowed = jnp.asarray(logits).at[:, 0].set(-jnp.inf) \
        .at[:, mask_id].set(-jnp.inf)
    return (np.asarray(jnp.argmax(allowed, axis=-1), np.int32),
            np.asarray(jnp.max(allowed, axis=-1)
                       - jax.nn.logsumexp(logits, axis=-1), np.float64))


def commit_choice(log_conf, masked, least: int, threshold: float):
    """One row's rule: log_conf, masked (L,) -> the positions to commit."""
    over = masked & (log_conf > np.log(threshold))
    if over.sum() >= least:
        return over
    order = sorted(np.flatnonzero(masked), key=lambda j: (-log_conf[j], j))
    out = np.zeros_like(masked)
    out[order[:least]] = True
    return out


def _layout(ids, lm: dict):
    ids = np.asarray(ids, np.int32)
    span, blocks = lm[SERVICE]["block_length"], lm[SERVICE]["expand_blocks"]
    n = (ids != 0).sum(axis=1)
    base = n // span * span
    return ids, span, blocks, n, base, ids.shape[1] + span * blocks


def generate(get_weights, token_rows, lm: dict, *, layers: int,
             first_expert: int, experts_held: int,
             precision: str = "float32"):
    """FREE generation over ``token_rows`` (S, W) int, 0 = pad -> {"emb"
    (S, 512); the trajectory "tokens", "step" (S, blocks, L) (``step``:
    the pass a position was committed at, -1 for a query token); and what
    a program's replay would hand over beside it (:func:`followed`,
    :func:`teacher_forced`): the experts "prefill" (S, layers, W, k),
    "denoise" (S, blocks, L passes, layers, L, k), "commit" (S, blocks,
    layers, L, k) — a finished block's are read off the next forward that
    holds it — and "logits" (S, blocks, L, V) of each block's first
    pass}."""
    ids, span, blocks, n, base, width = _layout(token_rows, lm)
    settings = lm[SERVICE]
    least = span // settings["denoising_steps"]
    mask_id = settings["mask_token_id"]
    rows, k = len(ids), lm["num_experts_per_tok"]
    seq = np.zeros((rows, width), np.int32)
    seq[:, :ids.shape[1]] = ids
    take = np.arange(rows)[:, None]
    out = {"tokens": np.zeros((rows, blocks, span), np.int32),
           "step": np.full((rows, blocks, span), -1, np.int32),
           "prefill": np.zeros((rows, layers, ids.shape[1], k), np.int32),
           "denoise": np.zeros((rows, blocks, span, layers, span, k),
                               np.int32),
           "commit": np.zeros((rows, blocks, layers, span, k), np.int32),
           "logits": None}
    top = get_weights(PREFIX + "/")
    run = dict(layers=layers, first_expert=first_expert,
               experts_held=experts_held, precision=precision, top=top)

    def block_at(b):
        return base[:, None] + b * span + np.arange(span)[None, :]  # (S, L)

    def routed_at(route, at):
        """(S, layers, L, k) of the forward's experts at positions ``at``."""
        return np.stack([e[take, at] for e in route["experts"]], axis=1)

    for b in range(blocks):
        at = block_at(b)
        masked = at >= n[:, None]
        seq[take, at] = np.where(masked, mask_id, seq[take, at])
        s = 0
        while masked.any():
            x, route = forward(get_weights, seq, base + (b + 1) * span, lm,
                               **run)
            if b == 0 and s == 0:
                out["prefill"] = np.stack(
                    [e[:, :ids.shape[1]] for e in route["experts"]], axis=1)
            if b and s == 0:
                out["commit"][:, b - 1] = routed_at(route, block_at(b - 1))
            logits = logits_of(
                jnp.asarray(x)[take, at].reshape(rows * span, -1), top, lm,
                precision)
            if s == 0:
                if out["logits"] is None:
                    out["logits"] = np.zeros(
                        (rows, blocks, span, logits.shape[-1]), np.float32)
                out["logits"][:, b] = np.asarray(logits).reshape(
                    rows, span, -1)
            cand, log_conf = (v.reshape(rows, span)
                              for v in candidates(logits, mask_id))
            at_work = masked.any(axis=1)
            out["denoise"][at_work, b, s] = routed_at(route, at)[at_work]
            for r in np.flatnonzero(at_work):
                commit = commit_choice(log_conf[r], masked[r], least,
                                       settings["confidence_threshold"])
                seq[r, at[r][commit]] = cand[r][commit]
                out["step"][r, b][commit] = s
                masked[r] &= ~commit
            s += 1
        out["tokens"][:, b] = seq[take, at]
    x, route = forward(get_weights, seq, base + blocks * span, lm, **run)
    out["commit"][:, blocks - 1] = routed_at(route, block_at(blocks - 1))
    last = jnp.asarray(x)[np.arange(rows), base + blocks * span - 1]
    return {"emb": embed_last(last, top, lm), **out}


def pass_items(ids, tokens, step, lm: dict):
    """The passes of a trajectory as independent whole rows.  -> {"seq"
    (M, T), "lengths" (M,), "query", "block", "pass" (M,) (pass = -1: the
    finished row), "masked" (M, L): the current block's masked positions}:
    for every query, every denoise pass in which it still had a mask, and
    the finished row once."""
    ids, span, blocks, n, base, width = _layout(ids, lm)
    mask_id = lm[SERVICE]["mask_token_id"]
    keys = ("seq", "lengths", "query", "block", "pass", "masked")
    items = []
    for r in range(len(ids)):
        seq = np.zeros((width,), np.int32)
        seq[:ids.shape[1]] = ids[r]
        for b in range(blocks):
            at = base[r] + b * span + np.arange(span)
            for s in range(int(step[r, b].max()) + 1):
                masked = step[r, b] >= s
                row = seq.copy()
                row[at] = np.where(masked, mask_id, tokens[r, b])
                row[at[-1] + 1:] = 0
                items.append((row, at[-1] + 1, r, b, s, masked))
            seq[at] = tokens[r, b]
        items.append((seq, base[r] + blocks * span, r, blocks - 1, -1,
                      np.zeros((span,), bool)))
    return {k: np.asarray(v) for k, v in zip(keys, zip(*items))}


def followed(items: dict, ids, experts: dict, lm: dict) -> list:
    """The program's experts laid on the items' whole rows, a layer: a
    position of the query's whole blocks takes the prefill's choice, one of
    an earlier written block that block's commit pass's, one of the
    current block this pass's.  ``experts``: "prefill" (S, layers, W, k),
    "denoise" (S, blocks, L passes, layers, L, k), "commit" (S, blocks,
    layers, L, k)."""
    ids, span, blocks, n, base, width = _layout(ids, lm)
    layers, k = experts["prefill"].shape[1], experts["prefill"].shape[-1]
    out = np.zeros((layers, len(items["seq"]), width, k), np.int32)
    for m, (r, b, s) in enumerate(zip(items["query"], items["block"],
                                      items["pass"])):
        out[:, m, :base[r]] = experts["prefill"][r][:, :base[r]]
        for earlier in range(b + (s < 0)):
            at = base[r] + earlier * span
            out[:, m, at:at + span] = experts["commit"][r, earlier]
        if s >= 0:
            at = base[r] + b * span
            out[:, m, at:at + span] = experts["denoise"][r, b, s]
    return list(out)


def commit_margin(log_conf, short, committed, masked, least: int,
                  threshold: float) -> float:
    """One pass of one row, in the reference's float32 numbers: how far
    the program's choices lie under the reference's own (0 = the
    reference's).  Token: ``short`` (L,), the best allowed logit less the
    committed token's, over the committed positions.  Position: the
    committed set against the rule — over the threshold (each committed
    position's log-confidence short of log threshold, each masked one left
    out over it, if at least ``least`` were committed), or the ``least``
    most confident (the best left out over the worst taken, if exactly
    ``least`` were committed and fewer lie over the threshold): the
    smaller of the two readings."""
    token = float(short[committed].max()) if committed.any() else 0.0
    left = masked & ~committed
    line = np.log(threshold)
    by_threshold = np.inf
    if committed.sum() >= least:
        by_threshold = max(
            [line - log_conf[j] for j in np.flatnonzero(committed)]
            + [log_conf[j] - line for j in np.flatnonzero(left)] + [0.0])
    by_rank = np.inf
    if committed.sum() == min(least, masked.sum()):
        over = np.sort(log_conf[masked])[::-1][least - 1:least]
        by_rank = max(
            [log_conf[left].max() - log_conf[committed].min()
             if left.any() and committed.any() else 0.0]
            + [float(v - line) for v in over] + [0.0])
    return float(max(token, min(by_threshold, by_rank)))


@jax.jit
def _read_logits(logits, taken, mask_id):
    """logits (M, V), taken (M,) ids -> (log-confidence of the best
    allowed id, the best allowed logit less the taken id's)."""
    ids = jnp.arange(logits.shape[-1])
    allowed = jnp.where((ids == 0) | (ids == mask_id), -jnp.inf, logits)
    best = jnp.max(allowed, axis=-1)
    return (best - jax.nn.logsumexp(logits, axis=-1),
            best - jnp.take_along_axis(allowed, taken[:, None], axis=1)[:, 0])


@jax.jit
def _logit_err(mine, theirs):
    """Each row's RMS distance over the vocabulary in units of the
    reference's spread there."""
    return (jnp.sqrt(jnp.mean((theirs - mine) ** 2, axis=-1))
            / jnp.std(mine, axis=-1))


def teacher_forced(get_weights, token_rows, tokens, step, lm: dict, *,
                   layers: int, first_expert: int, experts_held: int,
                   experts: dict | None = None, program_logits=None,
                   precision: str = "float32", head_rows: int = 512):
    """The reference on a GIVEN trajectory (``tokens``, ``step`` (S,
    blocks, L)) and, where given, the program's ``experts`` (:func:
    `followed`) and the ``program_logits`` of each block's first pass (S,
    blocks, L, V).  -> {"emb" (S, 512), "route_margin", "commit_margin",
    "logit_err" (S,): each query's largest}."""
    ids, span, blocks, n, base, width = _layout(token_rows, lm)
    settings = lm[SERVICE]
    least = span // settings["denoising_steps"]
    items = pass_items(ids, tokens, step, lm)
    top = get_weights(PREFIX + "/")
    x, route = forward(
        get_weights, items["seq"], items["lengths"], lm, layers=layers,
        first_expert=first_expert, experts_held=experts_held,
        precision=precision, top=top,
        follow=None if experts is None else followed(items, ids, experts, lm))
    queries = len(ids)
    margins = {k: np.zeros((queries,), np.float64)
               for k in ("route_margin", "commit_margin", "logit_err")}
    np.maximum.at(margins["route_margin"], items["query"], route["margin"])
    denoise = np.flatnonzero(items["pass"] >= 0)
    block_at = (items["lengths"][:, None] - span + np.arange(span)[None, :])
    for lo in range(0, len(denoise), head_rows // span):
        picks = denoise[lo:lo + head_rows // span]
        logits = logits_of(
            x[picks[:, None], block_at[picks]].reshape(len(picks) * span, -1),
            top, lm)
        taken = tokens[items["query"][picks], items["block"][picks]]
        log_conf, short = (
            np.asarray(v, np.float64).reshape(len(picks), span)
            for v in _read_logits(logits, jnp.asarray(taken.reshape(-1)),
                                  settings["mask_token_id"]))
        first = np.flatnonzero(items["pass"][picks] == 0)
        if program_logits is not None and len(first):
            err = np.asarray(_logit_err(
                logits.reshape(len(picks), span, -1)[first],
                jnp.asarray(program_logits[items["query"][picks[first]],
                                           items["block"][picks[first]]],
                            jnp.float32)))
            err = np.where(items["masked"][picks[first]], err, 0.0)
            np.maximum.at(margins["logit_err"],
                          items["query"][picks[first]], err.max(axis=1))
        for i, m in enumerate(picks):
            r, b, s = (items[k][m] for k in ("query", "block", "pass"))
            margins["commit_margin"][r] = max(
                margins["commit_margin"][r], commit_margin(
                    log_conf[i], short[i], step[r, b] == s,
                    items["masked"][m], least,
                    settings["confidence_threshold"]))
    done = np.flatnonzero(items["pass"] < 0)
    last = x[done, items["lengths"][done] - 1]
    emb = np.zeros((queries, top["proj"].shape[1]), np.float32)
    emb[items["query"][done]] = np.asarray(embed_last(last, top, lm))
    return {"emb": jnp.asarray(emb), **margins}
