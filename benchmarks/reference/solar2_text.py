"""The plain reference of the Solar-Open2-250B sentence tower
(upstage/Solar-Open2-250B ``config.json``, ``model_type`` ``solar_open2``:
Kimi Delta Attention in three of every four layers beside a gated NoPE
grouped-query attention, 320 sigmoid-routed experts and a shared one in
every layer; KDA's equations are Kimi Linear's, arXiv:2510.26692): float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``, no kernels,
no grouping of tokens, one layer's float32 weights resident at a time.  It
takes the benchmark's weights (``benchmarks/weights_solar2.py``, made again
from the seed) and nothing the program made.  It reads the configuration's
PUBLISHED names (``linear_attn_config``, ``head_dim``, ...), not the
program's group.

A layer: ``x += Mixer(RMS(x))``; ``h = RMS(x)``; ``x += Shared(h) +
Routed(h)``.  The KDA layer is computed AS THE RECURRENCE ITSELF: a
``lax.scan`` over the positions with one state (128 x 128) a head,

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t / sqrt(128)

— no chunks, no triangular solve, nothing of ``milnce_tpu/ops/kda.py``.
q, k, v: each its projection, a causal depthwise conv of 4 taps, SiLU; q and
k of unit length.  ``alpha_t = exp(-exp(A_log) softplus(h W_fa W_fb +
dt_bias))``, ``beta_t = 2 sigmoid(h W_b)``; out ``W_o [RMS_head(o) x
sigmoid(h W_ga W_gb)]``.  The attention layer: causal softmax at
1/sqrt(head_dim) over every position, no position term, ``W_o [attn x
sigmoid(h W_g)]``.  The router: sigmoid of the 320 logits, the top 8,
normalised over those 8 (``norm_topk_prob``) times
``routed_scaling_factor``.

Departures from the published model, each the configuration's (its file
lists them under ``assumed`` / ``reduced``):

- **no output head**: an embedding tower has none.  Instead: the final
  RMSNorm at each row's LAST REAL token, times a bias-free projection
  ``proj`` (hidden -> 512) into the joint space.
- **sizes the config does not give** (``assumed``): the gates element by
  element, the low-rank pairs of rank 128, no selection bias in the
  router, l2 norms with eps 1e-6.
- **the chip's share**: given ``(first_expert, experts_held)``, only the
  held experts' part of the routed sum is added (the router still scores
  all 320 and takes its 8).  What the absent experts would add is left out,
  and that partial result goes on to the next layer — in the program alike.
- **pads**: id 0; real ids come first.  A pad is never a key for a real
  position and never routed; conv and recurrence are causal, so no real
  position reads a pad.  Rows are computed in blocks, each cut to its
  longest row (rounded up).

``precision='float8'`` is the control: the inputs (activations and
weights) of the mixers' projections, the router's product and the routed
experts' three products rounded to ``float8_e4m3fn``, one step below the
bfloat16 the configuration states.

**Following a program's routing** (``follow``), as the other towers'
references do: given the experts the program chose for each token, the
reference measures the choice against its own router (``route_margin``:
how far, in its own float32 logits, the best expert left out lies above the
worst taken) and goes on with the PROGRAM's experts, weighted by its own
normalised sigmoid scores of them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.reference.granite4h_text import (PREFIX, _Frozen, _round,
                                                 rms, row_blocks, swiglu)


def causal_conv(x, taps_w):
    """Depthwise causal conv, no bias: x (B, S, C), taps_w (C, K)."""
    taps, s = taps_w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, j:j + s] * taps_w[:, j] for j in range(taps))


def l2(x, eps=1e-6):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def recurrence(q, k, v, g, beta, scale):
    """The gated delta rule as written: q, k (B, S, H, K), v (B, S, H, V),
    g (B, S, H, K) the log of the decay, beta (B, S, H) -> o (B, S, H,
    V)."""

    def step(state, at_t):
        q_t, k_t, v_t, g_t, b_t = at_t
        state = jnp.exp(g_t)[..., None] * state             # Diag(alpha) S
        state = state + b_t[..., None, None] * k_t[..., None] * (
            v_t - jnp.einsum("bhk,bhkv->bhv", k_t, state))[..., None, :]
        return state, scale * jnp.einsum("bhkv,bhk->bhv", state, q_t)

    rows, _, heads, dk = q.shape
    zero = jnp.zeros((rows, heads, dk, v.shape[-1]), jnp.float32)
    _, o = lax.scan(step, zero, tuple(jnp.moveaxis(t, 1, 0)
                                      for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def kda(u, w: dict, lm: dict, precision: str = "float32"):
    """u (B, S, hidden) float32 -> the KDA mixer's output."""
    r = lambda t: _round(t, precision)      # noqa: E731
    heads, width = lm["kda_heads"], lm["kda_width"]
    b, s, _ = u.shape

    def branch(name):
        x = causal_conv(r(u) @ r(w[f"kda/w{name}"]), w[f"kda/conv_{name}"])
        return jax.nn.silu(x).reshape(b, s, heads, width)

    q, k, v = l2(branch("q")), l2(branch("k")), branch("v")
    low = r(r(u) @ r(w["kda/f_a"])) @ r(w["kda/f_b"])
    g = (-jnp.exp(w["kda/A_log"])[:, None]
         * jax.nn.softplus(low + w["kda/dt_bias"]).reshape(b, s, heads,
                                                           width))
    beta = jax.nn.sigmoid(r(u) @ r(w["kda/b"]))
    if lm["kda_allow_neg_eigval"]:
        beta = 2.0 * beta
    o = recurrence(q, k, v, g, beta, width ** -0.5)
    o = rms(o, w["kda/norm/weight"], lm["rms_norm_eps"])
    gate = jax.nn.sigmoid(r(r(u) @ r(w["kda/g_a"])) @ r(w["kda/g_b"]))
    return r(o.reshape(b, s, heads * width) * gate) @ r(w["kda/wo"])


def attention(h, w: dict, lengths, lm: dict, precision: str = "float32"):
    """Gated grouped-query attention, no position term.  h (B, S, hidden)."""
    r = lambda t: _round(t, precision)      # noqa: E731
    b, s, _ = h.shape
    heads, kv, width = lm["num_attention_heads"], lm["num_key_value_heads"], \
        lm["head_dim"]
    q = (r(h) @ r(w["attn/wq"])).reshape(b, s, heads, width)
    k = (r(h) @ r(w["attn/wk"])).reshape(b, s, kv, width)
    v = (r(h) @ r(w["attn/wv"])).reshape(b, s, kv, width)
    k, v = (jnp.repeat(t, heads // kv, axis=2) for t in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(width)
    at = jnp.arange(s)
    key_real = at[None, :] < lengths[:, None]
    visible = ((at[None, None, :] <= at[None, :, None])
               & key_real[:, None, :]) | (at[:, None] == at[None, :])[None]
    probs = jax.nn.softmax(jnp.where(visible[:, None], scores, -jnp.inf),
                           axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, heads * width)
    if lm["use_gqa_gate"]:
        out = out * jax.nn.sigmoid(r(h) @ r(w["attn/wg"]))
    return r(out) @ r(w["attn/wo"])


def moe(h, w: dict, real, lm: dict, first_expert: int, experts_held: int,
        precision: str = "float32", follow=None):
    """h (T, hidden), real (T,), follow (T, k) int or None -> (this
    share's part of the routed sum — WITHOUT the shared expert —, the
    (T, k) experts whose outputs were added, the (T,) route margin of
    ``follow``: zeros without it)."""
    r = lambda a: _round(a, precision)      # noqa: E731
    logits = r(h) @ r(w["moe/router"])                          # (T, 320)
    _top, chosen = lax.top_k(logits, lm["num_experts_per_tok"])
    margin = jnp.zeros(h.shape[:1], jnp.float32)
    if follow is not None:
        chosen = follow
        taken = jnp.any(follow[:, :, None]
                        == jnp.arange(logits.shape[1])[None, None], axis=1)
        worst_in = jnp.min(jnp.where(taken, logits, jnp.inf), axis=1)
        best_out = jnp.max(jnp.where(taken, -jnp.inf, logits), axis=1)
        margin = jnp.where(real, jnp.maximum(best_out - worst_in, 0.0), 0.0)
    top = jax.nn.sigmoid(jnp.take_along_axis(logits, chosen, axis=1))
    if lm["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    top = top * lm["routed_scaling_factor"]

    def add_expert(out, expert):
        """One held expert over EVERY token, times the weight each token
        gave it (0 where it did not choose it)."""
        j, gate, up, down = expert
        weight = jnp.sum(jnp.where(chosen == first_expert + j, top, 0.0),
                         axis=-1)                               # (T,)
        weight = jnp.where(real, weight, 0.0)
        return out + weight[:, None] * swiglu(h, gate, up, down,
                                              precision), None

    out, _ = lax.scan(add_expert, jnp.zeros_like(h),
                      (jnp.arange(experts_held), w["moe/w_gate"],
                       w["moe/w_up"], w["moe/w_down"]))
    return out, chosen, margin


def layer(x, w: dict, lengths, lm: dict, kind: str, first_expert: int,
          experts_held: int, precision: str = "float32", follow=None):
    """One layer -> (x, the (B, S, k) experts added, the (B, S) route
    margins).  ``w``: the layer's float32 weights by their names under
    ``text_module/layers_<i>/``; ``follow`` (B, S, k): the experts to take
    (the module's docstring)."""
    eps = lm["rms_norm_eps"]
    h = rms(x, w["mixer_norm/weight"], eps)
    if kind == "kda":
        x = x + kda(h, w, lm, precision)
    elif kind == "attention":
        x = x + attention(h, w, lengths, lm, precision)
    else:
        raise ValueError(f"layer kind {kind!r}")
    h = rms(x, w["mlp_norm/weight"], eps)
    b, s, hidden = x.shape
    real = (jnp.arange(s)[None, :] < lengths[:, None]).reshape(-1)
    flat = h.reshape(-1, hidden)
    routed, chosen, margin = moe(
        flat, w, real, lm, first_expert, experts_held, precision,
        None if follow is None else follow.reshape(b * s, -1))
    shared = swiglu(flat, w["shared/w_gate"], w["shared/w_up"],
                    w["shared/w_down"])
    return (x + (shared + routed).reshape(x.shape),
            chosen.reshape(b, s, -1), margin.reshape(b, s))


_layer = jax.jit(layer, static_argnames=("kind", "first_expert",
                                         "experts_held", "precision"),
                 static_argnums=(3,))


def published(cfg: dict) -> _Frozen:
    """The keys the layers read, from the configuration's published names
    (``linear_attn_config`` flattened), as a hashable static argument."""
    lin = cfg["linear_attn_config"]
    return _Frozen(
        kda_heads=lin["num_heads"], kda_width=lin["head_dim"],
        kda_allow_neg_eigval=cfg["kda_allow_neg_eigval"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], use_gqa_gate=cfg["use_gqa_gate"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        rms_norm_eps=cfg["rms_norm_eps"])


def query_embeddings(get_weights, token_rows, lm: dict, *, layers: int,
                     first_expert: int, experts_held: int,
                     precision: str = "float32", per_layer: bool = False,
                     follow=None, routing: bool = False,
                     block_rows: int = 8):
    """``granite4h_text.query_embeddings``'s signature and returns, for
    the configuration ``lm`` (published names, ``layer_types`` the list of
    kinds): ``get_weights(prefix)`` -> {name under the prefix: float32
    array} for ``text_module/`` and each ``text_module/layers_<i>/``;
    ``token_rows`` (S, W) int, 0 = pad; ``follow``: one (S, W, k) int array
    a layer.  -> (S, 512) float32 embeddings; with ``per_layer`` also the
    list of x (S, W, hidden) after each layer (zeros where a block was
    cut); with ``routing`` also {"experts": the (S, W, k) experts added, a
    layer; "margin": (S,) each query's largest route margin}."""
    ids = np.asarray(token_rows, np.int32)
    n, width = ids.shape
    lengths = (ids != 0).sum(axis=1)
    frozen = published(lm)
    kinds = list(lm["layer_types"])[:layers]
    blocks = row_blocks(lengths, width, block_rows)
    k = lm["num_experts_per_tok"]
    after, experts = [], []
    margin = np.zeros((n,), np.float32)
    follow = iter(follow) if follow is not None else None
    with jax.default_matmul_precision("highest"):
        top = get_weights(PREFIX + "/")
        table = jnp.asarray(top["embed"], jnp.float32)
        xs = [jnp.take(table, jnp.asarray(ids[rows, :w]), axis=0)
              for rows, w in blocks]
        del table
        for i, kind in enumerate(kinds):
            w_i = get_weights(f"{PREFIX}/layers_{i}/")
            taken = (None if follow is None
                     else np.asarray(next(follow), np.int32))
            chosen_all = np.zeros((n, width, k), np.int32)
            for at, (rows, w) in enumerate(blocks):
                xs[at], chosen, margins = _layer(
                    xs[at], w_i, jnp.asarray(lengths[rows]), frozen,
                    kind=kind, first_expert=first_expert,
                    experts_held=experts_held, precision=precision,
                    follow=(None if taken is None
                            else jnp.asarray(taken[rows, :w])))
                chosen_all[rows, :w] = np.asarray(chosen)
                margin[rows] = np.maximum(margin[rows],
                                          np.asarray(margins).max(axis=1))
            del w_i
            experts.append(chosen_all)
            if per_layer:
                full = np.zeros((n, width, xs[0].shape[-1]), np.float32)
                for (rows, w), x in zip(blocks, xs):
                    full[rows, :w] = np.asarray(x)
                after.append(full)
        pooled = np.zeros((n, xs[0].shape[-1]), np.float32)
        for (rows, _w), x in zip(blocks, xs):
            last = jnp.maximum(jnp.asarray(lengths[rows]) - 1, 0)
            pooled[rows] = np.asarray(jnp.take_along_axis(
                x, last[:, None, None], axis=1)[:, 0])
        x = rms(jnp.asarray(pooled), jnp.asarray(top["norm/weight"],
                                                 jnp.float32),
                lm["rms_norm_eps"])
        emb = x @ jnp.asarray(top["proj"], jnp.float32)
    out = (emb,) + ((after,) if per_layer else ()) + (
        ({"experts": experts, "margin": jnp.asarray(margin)},)
        if routing else ())
    return out if len(out) > 1 else emb
