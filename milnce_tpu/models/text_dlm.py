"""Sentence tower from a language model that generates by diffusion over
blocks: it WRITES an expansion of the query and embeds query and expansion
together.  Token table -> layers of rotary grouped-query attention (a
per-head RMSNorm on queries and keys) and softmax-routed experts, every
layer alike -> an output head for the positions still to be written ->
RMSNorm at the last written position -> projection into the joint space.
The keys of :class:`milnce_tpu.config.TextDLMConfig` carry their published
names (an ``sdar_moe`` ``config.json``).

A layer: ``x += Attn(RMS(x))``, ``x += Routed(RMS(x))``.  Attention: q of
``num_attention_heads`` heads, k and v of ``num_key_value_heads``, each of
``head_dim``; RMSNorm over ``head_dim`` on q and on k; rotary position on
the whole head, halves rotated (``[x1, x2] -> [x1 cos - x2 sin, x2 cos +
x1 sin]``); scores / sqrt(head_dim).  **Visibility is by blocks** of
``block_length`` positions: position i sees position j iff ``j // L <=
i // L`` — whole earlier blocks and its own block in both directions.  The
router soft-maxes over ALL ``num_experts`` logits (float32), takes the
``num_experts_per_tok`` largest and divides their weights by their sum
(``norm_topk_prob``).

One call of the tower is one flush of the service, and one jitted program:

1. **prefill**: the query's ``n // L`` whole blocks through all layers
   under the block mask; their keys and values go into the cache.
2. ``expand_blocks`` times, from the block that holds the query's last
   ``n % L`` tokens (the rest of it mask tokens): **denoise passes** — the
   block's L positions (committed tokens and masks) through all layers
   against the cache of earlier blocks, logits at its positions, each
   masked position's candidate = the argmax (ids 0 and ``mask_token_id``
   left out), its confidence = that candidate's softmax probability
   (float32, over the whole vocabulary); the commit rule
   ``low_confidence_dynamic``: every masked position whose confidence
   exceeds ``confidence_threshold`` where there are at least ``L /
   denoising_steps`` of them, else the ``L / denoising_steps`` most
   confident (ties: the earlier position) — until no real row of the flush
   has a mask left in the block (a ``lax.while_loop``: the number of passes
   is a value of the data).  A denoise pass writes nothing to the cache.
   Then one **commit pass**: the finished block once more through all
   layers, its keys and values written to the cache, no head.
3. the final RMSNorm at the last position of the last block in its commit
   pass (it sees every block), times ``proj``.

The cache is per row: (rows, ``max_words + expand_blocks x L`` positions,
kv heads, head_dim) keys and as many values a layer; a row's blocks start
at its own ``base = n // L x L``, so rows of one flush stand at different
positions.  **Within a rung a row's result depends on the row alone**: a
row without a mask left in a block rides the block's remaining passes
unchanged (and sends nothing to the experts in them); a pad row (no
token) never has a mask.

Ids, pads and the chip's share of an expert layer are ``text_lm.py``'s (0
is the pad, real ids first; ``(first_expert, experts_held)``), and so are
``rms_norm``, the products' types, ``held_expert_sum`` (whose turn takes
every pair at once where every expert is held), the counters' collection
and ``sum_counters``.  The passes are plain functions of the parameter
tree (they run inside ``lax`` loops, where a Flax module cannot be
called); the module declares the leaves.

Counters (``COUNTER_NAMES``): ``text_lm``'s four, summed over layers AND
passes, and ``gen_passes_denoise`` / ``gen_passes_commit`` (passes run),
``gen_tokens`` (positions committed by a denoise pass, real rows),
``gen_row_passes`` ((real row, pass) pairs that did the row's work: a
denoise pass in which the row still had a mask, and the commit pass of
each block), ``gen_row_slots`` (the rung's rows x the passes run),
``moe_experts_touched`` (held experts that got a pair, summed over layers
and passes, the prefill among them), ``kv_positions`` (positions of
earlier blocks read from the cache, summed over the rows at work and the
passes).

``trace=True`` (the benchmark's replay; the served program does not) makes
the trajectory an output: which token each written position took at which
pass, every pass's routing, and the logits of each block's first pass.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax

from milnce_tpu.config import TextDLMConfig
from milnce_tpu.models import text_lm
from milnce_tpu.models.text_lm import (COUNTERS, _dot, _fan_in_normal,
                                       held_expert_sum, rms_norm)

COUNTER_NAMES = text_lm.COUNTER_NAMES + (
    "gen_passes_denoise", "gen_passes_commit", "gen_tokens",
    "gen_row_passes", "gen_row_slots", "moe_experts_touched", "kv_positions")
REMASKING = ("low_confidence_dynamic",)

# ``TextDLMConfig`` frozen: hashable, a static argument
DLMDims = dataclasses.make_dataclass(
    "DLMDims", [(f.name, f.type) for f in dataclasses.fields(TextDLMConfig)],
    frozen=True)


def dlm_dims(cfg: TextDLMConfig):
    """``cfg`` validated and frozen: a value the tower does not implement
    is an error here, at build time."""
    d = DLMDims(**dataclasses.asdict(cfg))

    def refuse(what):
        raise ValueError(f"text_dlm: {what}")

    if d.remasking not in REMASKING:
        refuse(f"remasking={d.remasking!r}: the tower implements "
               f"{REMASKING} only")
    if d.decoder_sparse_step != 1:
        refuse(f"decoder_sparse_step={d.decoder_sparse_step}: the tower "
               "implements 1 only (routed experts in every layer)")
    if d.attention_bias:
        refuse("attention_bias=True: the tower's projections have no bias")
    if d.hidden_act != "silu":
        refuse(f"hidden_act={d.hidden_act!r}: the tower implements 'silu' "
               "only")
    if d.head_dim % 2 or d.num_attention_heads % d.num_key_value_heads:
        refuse("head_dim must be even, and num_key_value_heads divide "
               "num_attention_heads")
    if not (0 <= d.first_expert and d.experts_held >= 1
            and d.first_expert + d.experts_held <= d.num_experts):
        refuse(f"experts [{d.first_expert}, {d.first_expert} + "
               f"{d.experts_held}) lie outside the {d.num_experts} routed "
               "experts")
    if d.num_experts_per_tok > d.num_experts:
        refuse("num_experts_per_tok exceeds num_experts")
    if (d.block_length < 1 or d.expand_blocks < 1 or d.denoising_steps < 1
            or d.block_length % d.denoising_steps):
        refuse("block_length, expand_blocks and denoising_steps must be "
               "positive, and denoising_steps divide block_length")
    if not 0 < d.mask_token_id < d.vocab_size:
        refuse(f"mask_token_id={d.mask_token_id} lies outside the "
               f"vocabulary's ids 1..{d.vocab_size - 1}")
    return d


def layer_leaves(d) -> tuple:
    """((name, shape, 'ones' | 'matrix'), ...): one layer's leaves."""
    hidden, hd = d.hidden_size, d.head_dim
    q, kv = d.num_attention_heads * hd, d.num_key_value_heads * hd
    width, held = d.moe_intermediate_size, d.experts_held
    return (("attn_norm", (hidden,), "ones"), ("mlp_norm", (hidden,), "ones"),
            ("wq", (hidden, q), "matrix"), ("wk", (hidden, kv), "matrix"),
            ("wv", (hidden, kv), "matrix"), ("wo", (q, hidden), "matrix"),
            ("q_norm", (hd,), "ones"), ("k_norm", (hd,), "ones"),
            ("router", (hidden, d.num_experts), "matrix"),
            ("w_gate", (held, hidden, width), "matrix"),
            ("w_up", (held, hidden, width), "matrix"),
            ("w_down", (held, width, hidden), "matrix"))


_INITS = {"ones": nn.initializers.ones, "matrix": _fan_in_normal}


class Leaves(nn.Module):
    """Declares its leaves and hands them back as a dict."""
    leaves: tuple

    @nn.compact
    def __call__(self):
        return {name: self.param(name, _INITS[kind], shape)
                for name, shape, kind in self.leaves}


# ---- pieces --------------------------------------------------------------

def rope_tables(d, positions: int):
    """-> (cos, sin), each (positions, head_dim / 2) float32."""
    half = d.head_dim // 2
    inv_freq = 1.0 / d.rope_theta ** (
        jnp.arange(half, dtype=jnp.float32) * 2.0 / d.head_dim)
    angles = jnp.arange(positions, dtype=jnp.float32)[:, None] * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


def rotate_halves(x, cos, sin):
    """x (B, S, ..., head_dim) float32 by its position's angles (B, S,
    head_dim / 2): the two halves of a head are a pair's two parts."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    lift = (slice(None), slice(None)) + (None,) * (x.ndim - 3)
    c, s = cos[lift], sin[lift]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(w, h, angles, own_visible, cache, cache_visible, d, dt):
    """h (B, S, hidden) at positions whose rotary angles are ``angles``
    ((cos, sin), each (B, S, half)); ``own_visible`` (B, S, S): which of
    these S positions each sees; ``cache``: (keys, values), each (B, P,
    kv heads, head_dim), of earlier positions, ``cache_visible`` (B, S, P)
    — or None.  -> (out (B, S, hidden), this call's keys, values (B, S, kv
    heads, head_dim))."""
    heads, kv, hd = d.num_attention_heads, d.num_key_value_heads, d.head_dim
    b, s, _ = h.shape
    q = _dot(h, w["wq"], dt).reshape(b, s, kv, heads // kv, hd)
    k = _dot(h, w["wk"], dt).reshape(b, s, kv, hd)
    v = _dot(h, w["wv"], dt).reshape(b, s, kv, hd)
    q = rotate_halves(rms_norm(q, w["q_norm"], d.rms_norm_eps, jnp.float32),
                      *angles).astype(dt)
    k = rotate_halves(rms_norm(k, w["k_norm"], d.rms_norm_eps, jnp.float32),
                      *angles).astype(dt)
    scores = jnp.where(
        own_visible[:, None, None],
        jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                   preferred_element_type=jnp.float32) * hd ** -0.5,
        -jnp.inf)
    values = v
    if cache is not None:
        earlier = jnp.where(
            cache_visible[:, None, None],
            jnp.einsum("bqkgd,bpkd->bkgqp", q, cache[0],
                       preferred_element_type=jnp.float32) * hd ** -0.5,
            -jnp.inf)
        scores = jnp.concatenate([earlier, scores], axis=-1)
        values = jnp.concatenate([cache[1], v], axis=1)
    probs = jax.nn.softmax(scores, axis=-1)                     # float32
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(dt), values,
                     preferred_element_type=jnp.float32).astype(dt)
    return _dot(out.reshape(b, s, heads * hd), w["wo"], dt), k, v


def route(h, w_router, d):
    """Every token's choice over ALL routed experts.  h (T, hidden) ->
    (experts (T, k) int32, weights (T, k) float32): a softmax over every
    logit in float32, the k largest, divided by their sum
    (``norm_topk_prob``)."""
    probs = jax.nn.softmax(jnp.dot(h, w_router.astype(h.dtype),
                                   preferred_element_type=jnp.float32),
                           axis=-1)
    top, experts = lax.top_k(probs, d.num_experts_per_tok)
    if d.norm_topk_prob:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return experts.astype(jnp.int32), top


def routed_experts(w, h, real, d, dt):
    """h (B, S, hidden), real (B, S) -> (this chip's part of the routed
    sum, the counters of the visit (name -> int32), the experts chosen
    (B, S, k))."""
    flat, flat_real = h.reshape(-1, h.shape[-1]), real.reshape(-1)
    experts, weights = route(flat, w["router"], d)
    routed, n_held, most, tile_rows = held_expert_sum(
        flat, experts, weights, flat_real, w["w_gate"], w["w_up"],
        w["w_down"], first_expert=d.first_expert, dtype=dt,
        num_experts=d.num_experts)
    local = jnp.where(flat_real[:, None], experts - d.first_expert, -1)
    touched = jnp.sum(jnp.any(
        local.reshape(-1)[None, :] == jnp.arange(d.experts_held)[:, None],
        axis=1), dtype=jnp.int32)
    counts = {"moe_pairs_held": n_held, "moe_expert_max": most,
              "moe_pairs_total": (jnp.sum(flat_real, dtype=jnp.int32)
                                  * d.num_experts_per_tok),
              "moe_tile_rows": tile_rows, "moe_experts_touched": touched}
    return (routed.reshape(h.shape).astype(dt), counts,
            experts.reshape(h.shape[:2] + experts.shape[-1:]))


def zero_counts() -> dict:
    return {name: jnp.int32(0) for name in COUNTER_NAMES}


def add_counts(total: dict, more: dict) -> dict:
    """``total`` with ``more`` added (a ``*_max`` taken)."""
    out = dict(total)
    for name, value in more.items():
        value = jnp.asarray(value, jnp.int32)
        out[name] = (jnp.maximum(total[name], value)
                     if name.endswith("_max") else total[name] + value)
    return out


def layers_pass(layers, x, angles, own_visible, real, caches, cache_visible,
                d, dt):
    """One pass of ``x`` (B, S, hidden) through all layers.  ``caches``:
    a (keys, values) pair a layer, or None (the prefill).  -> (x, [(keys,
    values) of THIS pass a layer], counters, [experts (B, S, k) a
    layer])."""
    made, chosen, counts = [], [], zero_counts()
    for i, w in enumerate(layers):
        with jax.named_scope("text_dlm/attn"):
            out, k, v = attention(
                w, rms_norm(x, w["attn_norm"], d.rms_norm_eps, dt), angles,
                own_visible, None if caches is None else caches[i],
                cache_visible, d, dt)
            x = x + out
        with jax.named_scope("text_dlm/moe"):
            routed, visit, experts = routed_experts(
                w, rms_norm(x, w["mlp_norm"], d.rms_norm_eps, dt), real, d,
                dt)
            x = x + routed
        made.append((k, v))
        chosen.append(experts)
        counts = add_counts(counts, visit)
    return x, made, counts, chosen


def candidates(logits, d):
    """logits (T, V) float32 -> (each position's candidate: the argmax
    with ids 0 and ``mask_token_id`` left out; its confidence: that id's
    softmax probability over the whole vocabulary)."""
    ids = jnp.arange(logits.shape[-1])
    allowed = jnp.where((ids == 0) | (ids == d.mask_token_id), -jnp.inf,
                        logits)
    best = jnp.max(allowed, axis=-1)
    return (jnp.argmax(allowed, axis=-1).astype(jnp.int32),
            jnp.exp(best - jax.nn.logsumexp(logits, axis=-1)))


def commit_rule(conf, masked, d):
    """conf, masked (B, L) -> the masked positions a pass commits
    (``low_confidence_dynamic``): those over the threshold where they are
    at least ``L / denoising_steps``, else that many of the most confident
    (ties: the earlier position; fewer where fewer are masked)."""
    least = d.block_length // d.denoising_steps
    at = jnp.arange(conf.shape[1])
    c = jnp.where(masked, conf, -1.0)
    over = masked & (c > d.confidence_threshold)
    ahead = ((c[:, None, :] > c[:, :, None])
             | ((c[:, None, :] == c[:, :, None])
                & (at[None, None, :] < at[None, :, None])))
    rank = jnp.sum(ahead & masked[:, None, :], axis=2)
    return jnp.where(jnp.sum(over, axis=1, keepdims=True) >= least, over,
                     masked & (rank < least))


def prefill_visible(base, width: int, span: int):
    """(B, W, W) for the prefill: position i sees position j iff j's block
    is not later than i's and j lies in the row's whole blocks (``j <
    base``); and always itself (a row's softmax stays finite)."""
    at = jnp.arange(width)
    blocks = at[None, :] // span <= at[:, None] // span
    whole = at[None, :] < base[:, None]
    return ((blocks[None] & whole[:, None, :])
            | jnp.eye(width, dtype=bool)[None])


def in_block_visible(rows: int, span: int):
    """(B, L, L): a block's positions see each other, both ways."""
    return jnp.ones((rows, span, span), dtype=bool)


def earlier_visible(start, positions: int, span: int):
    """(B, L, P): the cache's positions before the block's first."""
    return jnp.broadcast_to(
        jnp.arange(positions)[None, None, :] < start[:, None, None],
        (start.shape[0], span, positions))


def expand_and_embed(params, ids, d, dt, trace: bool = False):
    """The flush: ids (B, W) int, 0 = pad -> (the final hidden state at
    each row's last written position (B, hidden), the counters, and with
    ``trace`` the trajectory; the module's docstring)."""
    rows, width = ids.shape
    span, blocks = d.block_length, d.expand_blocks
    if width % span:
        raise ValueError(f"text_dlm: a row's {width} slots are not a whole "
                         f"number of blocks of {span}")
    positions = width + blocks * span
    layers = [params[f"layers_{i}"] for i in range(d.num_hidden_layers)]
    n = jnp.sum(ids != 0, axis=1).astype(jnp.int32)
    row_real = n > 0
    base = n // span * span
    cos, sin = rope_tables(d, positions)
    table = params["embed"]

    # ---- prefill: the query's whole blocks, no cache to read -------------
    whole = jnp.arange(width)[None, :] < base[:, None]          # (B, W)
    visible = prefill_visible(base, width, span)
    with jax.named_scope("text_dlm/prefill"):
        x = jnp.take(table, ids, axis=0).astype(dt)
        angles = tuple(jnp.broadcast_to(t[:width], (rows,) + t[:width].shape)
                       for t in (cos, sin))
        _, made, counts, prefill_experts = layers_pass(
            layers, x, angles, visible, whole, None, None, d, dt)
        room = ((0, 0), (0, positions - width), (0, 0), (0, 0))
        caches = tuple((jnp.pad(k, room), jnp.pad(v, room)) for k, v in made)

    within = jnp.arange(span)
    every = jnp.arange(positions)
    own_visible = in_block_visible(rows, span)
    k_experts = d.num_experts_per_tok

    def block(b, state):
        caches, counts, _, out = state
        start = base + b * span                                 # (B,)
        p = start[:, None] + within[None, :]                    # (B, L)
        angles = (jnp.take(cos, p, axis=0), jnp.take(sin, p, axis=0))
        given = p < n[:, None]
        tokens = jnp.where(
            given, jnp.take_along_axis(ids, jnp.minimum(p, width - 1), axis=1),
            d.mask_token_id).astype(jnp.int32)
        masked = ~given & row_real[:, None]
        cache_visible = earlier_visible(start, positions, span)
        cache_read = jnp.where(row_real, start, 0)

        def run(tokens, real):
            x = jnp.take(table, tokens, axis=0).astype(dt)
            return layers_pass(layers, x, angles, own_visible, real, caches,
                               cache_visible, d, dt)

        def denoise(loop):
            tokens, masked, step_of, s, counts, seen = loop
            active = jnp.any(masked, axis=1)            # real rows at work
            with jax.named_scope("text_dlm/denoise"):
                x, _, visit, experts = run(
                    tokens, jnp.broadcast_to(active[:, None], masked.shape))
                with jax.named_scope("text_dlm/head"):
                    logits = jnp.dot(
                        rms_norm(x, params["norm"], d.rms_norm_eps, dt)
                        .reshape(rows * span, -1), params["head"].astype(dt),
                        preferred_element_type=jnp.float32)
                    cand, conf = candidates(logits, d)
                commit = commit_rule(conf.reshape(rows, span), masked, d)
            counts = add_counts(add_counts(counts, visit), {
                "gen_passes_denoise": 1, "gen_row_slots": rows,
                "gen_tokens": jnp.sum(commit),
                "gen_row_passes": jnp.sum(active),
                "kv_positions": jnp.sum(jnp.where(active, cache_read, 0))})
            if trace:
                seen = dict(
                    seen, experts=lax.dynamic_update_slice(
                        seen["experts"], jnp.stack(experts)[None],
                        (s, 0, 0, 0, 0)),
                    logits=jnp.where(s == 0, logits.reshape(rows, span, -1),
                                     seen["logits"]))
            return (jnp.where(commit, cand.reshape(rows, span), tokens),
                    masked & ~commit, jnp.where(commit, s, step_of), s + 1,
                    counts, seen)

        seen = ({"experts": jnp.zeros((span, d.num_hidden_layers, rows, span,
                                       k_experts), jnp.int32),
                 "logits": jnp.zeros((rows, span, d.vocab_size), jnp.float32)}
                if trace else {})
        tokens, _, step_of, passes, counts, seen = lax.while_loop(
            lambda loop: jnp.any(loop[1]), denoise,
            (tokens, masked, jnp.full(masked.shape, -1, jnp.int32),
             jnp.int32(0), counts, seen))

        with jax.named_scope("text_dlm/commit"):
            x, made, visit, experts = run(
                tokens, jnp.broadcast_to(row_real[:, None], masked.shape))
            written = every[None, :, None] == p[:, None, :]     # (B, P, L)
            here = jnp.any(written, axis=2)[:, :, None, None]
            put = written.astype(dt)
            # a 0/1 product lays the block's rows on the cache's positions:
            # exact, and no scatter (a serial loop on the TPU)
            caches = tuple(
                tuple(jnp.where(here, jnp.einsum(
                    "bpl,blkd->bpkd", put, new,
                    preferred_element_type=jnp.float32).astype(dt), old)
                    for old, new in zip(cache, pair))
                for cache, pair in zip(caches, made))
        counts = add_counts(add_counts(counts, visit), {
            "gen_passes_commit": 1, "gen_row_slots": rows,
            "gen_row_passes": jnp.sum(row_real),
            "kv_positions": jnp.sum(cache_read)})
        if trace:
            def put_block(name, value):
                return lax.dynamic_update_slice(
                    out[name], value[None],
                    (b,) + (0,) * value.ndim)

            out = {"tokens": put_block("tokens", tokens),
                   "step": put_block("step", step_of),
                   "passes": put_block("passes", passes),
                   "denoise_experts": put_block("denoise_experts",
                                                seen["experts"]),
                   "commit_experts": put_block("commit_experts",
                                               jnp.stack(experts)),
                   "logits": put_block("logits", seen["logits"])}
        return caches, counts, x[:, span - 1], out

    shape_l = (d.num_hidden_layers, rows, span, k_experts)
    out = ({"tokens": jnp.zeros((blocks, rows, span), jnp.int32),
            "step": jnp.zeros((blocks, rows, span), jnp.int32),
            "passes": jnp.zeros((blocks,), jnp.int32),
            "denoise_experts": jnp.zeros((blocks, span) + shape_l, jnp.int32),
            "commit_experts": jnp.zeros((blocks,) + shape_l, jnp.int32),
            "logits": jnp.zeros((blocks, rows, span, d.vocab_size),
                                jnp.float32)} if trace else {})
    _, counts, last, out = lax.fori_loop(
        0, blocks, block,
        (caches, counts, jnp.zeros((rows, d.hidden_size), dtype=dt), out))
    if trace:
        out["prefill_experts"] = jnp.stack(prefill_experts)
    return last, counts, out


class TextDLM(nn.Module):
    """tokens (B, S) int -> (B, embd_dim): the embedding of the query and
    the expansion the tower wrote for it; with ``trace`` also the
    trajectory (``expand_and_embed``)."""
    dims: Any
    embd_dim: int = 512
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, tokens, trace: bool = False):
        d, dt = self.dims, self.dtype
        params = {
            "embed": self.param("embed", nn.initializers.normal(1.0),
                                (d.vocab_size, d.hidden_size)),
            "head": self.param("head", _fan_in_normal,
                               (d.hidden_size, d.vocab_size)),
            "norm": self.param("norm", nn.initializers.ones,
                               (d.hidden_size,)),
            **{f"layers_{i}": Leaves(layer_leaves(d), name=f"layers_{i}")()
               for i in range(d.num_hidden_layers)}}
        proj = self.param("proj", _fan_in_normal,
                          (d.hidden_size, self.embd_dim))
        last, counts, out = expand_and_embed(params, tokens, d, dt, trace)
        self.sow(COUNTERS, "tower",
                 jnp.stack([counts[name] for name in COUNTER_NAMES]))
        emb = _dot(rms_norm(last, params["norm"], d.rms_norm_eps, dt), proj,
                   dt)
        return (emb, out) if trace else emb
