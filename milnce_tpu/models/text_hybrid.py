"""Sentence tower from a hybrid causal language model: token table ->
layers by a list of kinds (``layer_types``) — a Mamba-2 mixer, Kimi Delta
Attention (the gated delta rule, ``kda``) or a grouped-query attention
without any position encoding, then routed experts beside a shared MLP, in
EVERY layer — -> RMSNorm at each row's last real token -> projection into
the joint space.  The keys of
:class:`milnce_tpu.config.TextHybridConfig` carry their published names (a
``granitemoehybrid`` ``config.json``), the family's three multipliers among
them: the table's rows times ``embedding_multiplier``, every sublayer's
output times ``residual_multiplier`` into the residual stream, the
attention's scores times ``attention_multiplier``.

A layer: ``x += r Mixer(RMS(x))``, ``h = RMS(x)``, ``x += r (Shared(h) +
Routed(h))``.  The router (``scoring_func``) takes the
``num_experts_per_tok`` largest LOGITS and soft-maxes over those alone
(``softmax``, the granite family's), or is ``text_lm.route`` (``sigmoid``:
sigmoid scores, the top k normalised over themselves and scaled).

Ids, pads and the chip's share of an expert layer are ``text_lm.py``'s
(0 is the pad, real ids first; ``(first_expert, experts_held)``), and so
are ``RMSNorm``, the products' types, ``held_expert_sum``, the attention
mask and the two Flax collections.  Right padding is harmless to the
causal conv and the scan: no real position reads a later one, and a row
never reads another.

Counters (``COUNTER_NAMES``): ``text_lm``'s four and, over the Mamba
layers, ``ssm_chunks_run`` (row x chunk blocks the scan computed) and
``ssm_chunks_real`` (those that held a real token); a tower with ``kda``
layers adds the same pair over them, ``kda_chunks_run`` /
``kda_chunks_real`` (:func:`counter_names`).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax

from milnce_tpu.config import TextHybridConfig
from milnce_tpu.models import text_lm
from milnce_tpu.models.text_lm import (COUNTERS, ROUTING, DenseMLP, RMSNorm,
                                       _dot, _fan_in_normal,
                                       attention_mask, held_expert_sum)
from milnce_tpu.ops.kda import SUB as KDA_SUB
from milnce_tpu.ops.kda import kda_chunked
from milnce_tpu.ops.ssd import ssd_scan

COUNTER_NAMES = text_lm.COUNTER_NAMES + ("ssm_chunks_run", "ssm_chunks_real")
KDA_COUNTER_NAMES = ("kda_chunks_run", "kda_chunks_real")
LAYER_KINDS = ("mamba", "attention", "kda")
ROUTERS = ("softmax", "sigmoid")

# ``TextHybridConfig`` frozen, ``layer_types`` a tuple of the layers built
HybridDims = dataclasses.make_dataclass(
    "HybridDims",
    [(f.name, f.type) for f in dataclasses.fields(TextHybridConfig)],
    frozen=True)


def hybrid_dims(cfg: TextHybridConfig):
    """``cfg`` validated and frozen: a value the tower does not implement
    is an error here, at build time."""
    fields = dataclasses.asdict(cfg)
    kinds = tuple(k.strip() for k in str(cfg.layer_types).split(",")
                  if k.strip())
    fields["layer_types"] = kinds[:cfg.num_hidden_layers]
    d = HybridDims(**fields)

    def refuse(what):
        raise ValueError(f"text_hybrid: {what}")

    if len(kinds) < d.num_hidden_layers or d.num_hidden_layers < 1:
        refuse(f"layer_types names {len(kinds)} layers, num_hidden_layers "
               f"is {d.num_hidden_layers}")
    unknown = sorted(set(kinds) - set(LAYER_KINDS))
    if unknown:
        refuse(f"layer_types entries {unknown}: the tower implements "
               f"{LAYER_KINDS} only")
    if d.position_embedding_type != "nope":
        refuse(f"position_embedding_type={d.position_embedding_type!r}: the "
               "tower implements 'nope' only (no position term)")
    if d.hidden_act != "silu":
        refuse(f"hidden_act={d.hidden_act!r}: the tower implements 'silu' "
               "only")
    if d.scoring_func not in ROUTERS:
        refuse(f"scoring_func={d.scoring_func!r}: the tower implements "
               f"{ROUTERS} only")
    if "mamba" in kinds[:d.num_hidden_layers]:
        if d.mamba_n_groups != 1:
            refuse(f"mamba_n_groups={d.mamba_n_groups}: the tower implements "
                   "1 only (B and C shared by all heads)")
        if d.mamba_proj_bias:
            refuse("mamba_proj_bias=True: the tower's projections have no "
                   "bias")
        if d.mamba_n_heads * d.mamba_d_head != d.mamba_expand * d.hidden_size:
            refuse(f"mamba_n_heads x mamba_d_head = "
                   f"{d.mamba_n_heads * d.mamba_d_head} is not mamba_expand x "
                   f"hidden_size = {d.mamba_expand * d.hidden_size}")
    if ((not d.head_dim and d.hidden_size % d.num_attention_heads)
            or d.num_attention_heads % d.num_key_value_heads):
        refuse("num_attention_heads must divide hidden_size (or head_dim be "
               "given), and num_key_value_heads num_attention_heads")
    if "kda" in kinds[:d.num_hidden_layers]:
        if d.kda_use_full_proj:
            refuse("kda_use_full_proj=True: the tower implements the "
                   "low-rank decay and gate projections only")
        if not d.kda_allow_neg_eigval:
            refuse("kda_allow_neg_eigval=False: the tower implements beta "
                   "in (0, 2) only")
        if d.kda_num_kv_heads:
            refuse(f"kda_num_kv_heads={d.kda_num_kv_heads}: the tower "
                   "implements null only (a key and a value a head)")
        if d.kda_chunk_size % min(KDA_SUB, d.kda_chunk_size):
            refuse(f"kda_chunk_size={d.kda_chunk_size} is no multiple of "
                   f"the scan's sub-block of {KDA_SUB}")
    if not (0 <= d.first_expert and d.experts_held >= 1
            and d.first_expert + d.experts_held <= d.num_local_experts):
        refuse(f"experts [{d.first_expert}, {d.first_expert} + "
               f"{d.experts_held}) lie outside the {d.num_local_experts} "
               "routed experts")
    if d.num_experts_per_tok > d.num_local_experts:
        refuse("num_experts_per_tok exceeds num_local_experts")
    return d


def counter_names(dims) -> tuple:
    """The names of the counters a tower of ``dims`` sows, in order:
    :data:`COUNTER_NAMES`, and the ``kda`` pair where it has such a
    layer."""
    return COUNTER_NAMES + (KDA_COUNTER_NAMES if "kda" in dims.layer_types
                            else ())


# ---- pieces --------------------------------------------------------------

def causal_conv(x, weight, bias):
    """Depthwise causal convolution along the positions, left-padded with
    zeros: x (B, S, C), weight (C, K), bias (C,) or None -> (B, S, C)
    float32; ``out_t = bias + sum_j weight[:, j] x_{t - (K - 1) + j}``."""
    taps, s = weight.shape[1], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    w = weight.astype(jnp.float32)
    out = sum(padded[:, j:j + s] * w[:, j] for j in range(taps))
    return out if bias is None else out + bias.astype(jnp.float32)


class Mamba2Mixer(nn.Module):
    """Mamba-2: fused input projection -> [gate | conv channels | time
    step], causal conv and SiLU over the conv channels -> [x | B | C], the
    state-space scan (``ops/ssd.py``), the gate, one RMSNorm over all inner
    channels, output projection."""
    dims: Any
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u):
        """u (B, S, hidden) -> (B, S, hidden)."""
        d, dt = self.dims, self.dtype
        heads, p, n = d.mamba_n_heads, d.mamba_d_head, d.mamba_d_state
        inner = heads * p
        channels = inner + 2 * n
        init = _fan_in_normal
        w_in = self.param("w_in", init,
                          (d.hidden_size, inner + channels + heads))
        conv_w = self.param(
            "conv_w", nn.initializers.normal(d.mamba_d_conv ** -0.5),
            (channels, d.mamba_d_conv))
        conv_b = (self.param("conv_b", nn.initializers.zeros, (channels,))
                  if d.mamba_conv_bias else None)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (heads,))
        a_log = self.param("A_log", nn.initializers.zeros, (heads,))
        skip = self.param("D", nn.initializers.ones, (heads,))
        w_out = self.param("w_out", init, (inner, d.hidden_size))
        b, s, _ = u.shape
        fused = _dot(u, w_in, dt)
        z, xbc, step = (fused[..., :inner], fused[..., inner:inner + channels],
                        fused[..., inner + channels:])
        xbc = nn.silu(causal_conv(xbc, conv_w, conv_b)).astype(dt)
        step = jax.nn.softplus(step.astype(jnp.float32)
                               + dt_bias.astype(jnp.float32))
        y = ssd_scan(xbc[..., :inner].reshape(b, s, heads, p), step,
                     -jnp.exp(a_log.astype(jnp.float32)),
                     xbc[..., inner:inner + n], xbc[..., inner + n:], skip,
                     chunk=d.mamba_chunk_size)
        gated = (y.reshape(b, s, inner).astype(jnp.float32)
                 * nn.silu(z.astype(jnp.float32)))
        return _dot(RMSNorm(d.rms_norm_eps, dt, name="norm")(gated), w_out,
                    dt)


class GQA(nn.Module):
    """Grouped-query attention over one row's positions with no position
    term: ``num_key_value_heads`` key/value heads of ``head_dim`` (0:
    hidden / heads), each serving ``num_attention_heads /
    num_key_value_heads`` query heads; scores times
    ``attention_multiplier``; with ``use_gqa_gate`` the heads' outputs
    times ``sigmoid(h W_g)``, element by element, before ``wo``; no
    cache."""
    dims: Any
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h, visible):
        """h (B, S, hidden), visible (B, S, S) bool -> (B, S, hidden)."""
        d, dt = self.dims, self.dtype
        heads, kv = d.num_attention_heads, d.num_key_value_heads
        width = d.head_dim or d.hidden_size // heads
        init = _fan_in_normal
        wq = self.param("wq", init, (d.hidden_size, heads * width))
        wk = self.param("wk", init, (d.hidden_size, kv * width))
        wv = self.param("wv", init, (d.hidden_size, kv * width))
        wo = self.param("wo", init, (heads * width, d.hidden_size))
        b, s, _ = h.shape
        q = _dot(h, wq, dt).reshape(b, s, kv, heads // kv, width)
        k = _dot(h, wk, dt).reshape(b, s, kv, width)
        v = _dot(h, wv, dt).reshape(b, s, kv, width)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                            preferred_element_type=jnp.float32)
        scores = jnp.where(visible[:, None, None],
                           scores * d.attention_multiplier, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)                 # float32
        out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(dt), v,
                         preferred_element_type=jnp.float32).astype(dt)
        out = out.reshape(b, s, heads * width)
        if d.use_gqa_gate:
            wg = self.param("wg", init, (d.hidden_size, heads * width))
            out = (out.astype(jnp.float32) * jax.nn.sigmoid(
                _dot(h, wg, dt).astype(jnp.float32))).astype(dt)
        return _dot(out, wo, dt)


def l2_normalize(x, eps: float = 1e-6):
    """x / sqrt(|x|^2 + eps) over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


class KDAMixer(nn.Module):
    """Kimi Delta Attention (arXiv:2510.26692), ``kda_num_heads`` heads of
    ``kda_head_dim``: q, k, v by their own projections, each through a
    causal depthwise conv of ``kda_short_conv_kernel_size`` taps (no bias)
    and SiLU, q and k then of unit length; a decay per channel
    ``g = -exp(A_log) softplus(h W_fa W_fb + dt_bias)`` (``A_log`` a head,
    ``dt_bias`` a channel; a low-rank pair of ``kda_proj_rank``); ``beta =
    2 sigmoid(h W_b)`` a head (``kda_allow_neg_eigval``); the rule
    (``ops/kda.py``) at scale ``kda_head_dim^-1/2``; an RMSNorm over each
    head's ``kda_head_dim`` outputs (one weight shared by the heads) times
    ``sigmoid(h W_ga W_gb)``, element by element; the output projection."""
    dims: Any
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u):
        """u (B, S, hidden) -> (B, S, hidden)."""
        d, dt = self.dims, self.dtype
        heads, width, rank = d.kda_num_heads, d.kda_head_dim, d.kda_proj_rank
        inner, taps = heads * width, d.kda_short_conv_kernel_size
        init = _fan_in_normal
        conv_init = nn.initializers.normal(taps ** -0.5)
        b, s, _ = u.shape

        def branch(name):
            """The projection ``w<name>``, its conv and SiLU -> (B, S,
            heads, width) float32."""
            w = self.param(f"w{name}", init, (d.hidden_size, inner))
            conv = self.param(f"conv_{name}", conv_init, (inner, taps))
            out = nn.silu(causal_conv(_dot(u, w, dt), conv, None))
            return out.reshape(b, s, heads, width)

        q, k, v = (branch(name) for name in ("q", "k", "v"))
        q, k = l2_normalize(q), l2_normalize(k)
        low = _dot(_dot(u, self.param("f_a", init, (d.hidden_size, rank)), dt),
                   self.param("f_b", init, (rank, inner)), dt)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (inner,))
        a_log = self.param("A_log", nn.initializers.zeros, (heads,))
        g = (-jnp.exp(a_log.astype(jnp.float32))[:, None]
             * jax.nn.softplus(low.astype(jnp.float32)
                               + dt_bias.astype(jnp.float32)
                               ).reshape(b, s, heads, width))
        beta = 2.0 * jax.nn.sigmoid(_dot(u, self.param(
            "b", init, (d.hidden_size, heads)), dt).astype(jnp.float32))
        o = kda_chunked(q.astype(dt), k.astype(dt), v.astype(dt), g, beta,
                        chunk=d.kda_chunk_size, scale=width ** -0.5)
        o = RMSNorm(d.rms_norm_eps, jnp.float32, name="norm")(o)
        gate = _dot(_dot(u, self.param("g_a", init, (d.hidden_size, rank)),
                         dt), self.param("g_b", init, (rank, inner)), dt)
        o = o.reshape(b, s, inner) * jax.nn.sigmoid(gate.astype(jnp.float32))
        return _dot(o, self.param("wo", init, (inner, d.hidden_size)), dt)


def route(h, w_router, d):
    """Every token's choice over ALL routed experts.  h (T, hidden) ->
    (experts (T, k) int32, weights (T, k) float32).  ``softmax``: logits
    in float32, the k largest of them, a softmax over those k alone;
    ``sigmoid``: ``text_lm.route``."""
    if d.scoring_func == "sigmoid":
        return text_lm.route(h, w_router, d)
    logits = jnp.dot(h, w_router.astype(h.dtype),
                     preferred_element_type=jnp.float32)
    top, experts = lax.top_k(logits, d.num_experts_per_tok)
    return experts.astype(jnp.int32), jax.nn.softmax(top, axis=-1)


class RoutedExperts(nn.Module):
    """This chip's part of the routed sum (``text_lm.held_expert_sum``).
    The published fused input matrix of an expert (hidden x 2 width) is
    held as its gate and up halves, stacked over the held experts."""
    dims: Any
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h, real):
        """h (B, S, hidden), real (B, S) bool -> ((B, S, hidden),
        ``text_lm.COUNTER_NAMES``' block (4,) int32)."""
        d, dt = self.dims, self.dtype
        init = _fan_in_normal
        hidden, width, held = (d.hidden_size, d.intermediate_size,
                               d.experts_held)
        w_router = self.param("router", init, (hidden, d.num_local_experts))
        w_gate = self.param("w_gate", init, (held, hidden, width))
        w_up = self.param("w_up", init, (held, hidden, width))
        w_down = self.param("w_down", init, (held, width, hidden))
        flat, flat_real = h.reshape(-1, hidden), real.reshape(-1)
        experts, weights = route(flat, w_router, d)
        routed, n_held, most, tile_rows = held_expert_sum(
            flat, experts, weights, flat_real, w_gate, w_up, w_down,
            first_expert=d.first_expert, dtype=dt,
            num_experts=d.num_local_experts)
        total = jnp.sum(flat_real) * d.num_experts_per_tok
        self.sow(ROUTING, "experts",
                 experts.reshape(h.shape[:2] + experts.shape[-1:]))
        return (routed.reshape(h.shape).astype(dt),
                jnp.stack([n_held, most, total.astype(jnp.int32),
                           tile_rows]))


def chunk_counts(real, chunk: int):
    """(row x chunk blocks a scan computes over ``real`` (B, S), those of
    them that hold a real token) int32."""
    per_row = -(-real.shape[1] // chunk)
    return jnp.stack([
        jnp.int32(real.shape[0] * per_row),
        jnp.sum(-(-jnp.sum(real, axis=1) // chunk)).astype(jnp.int32)])


class Layer(nn.Module):
    dims: Any
    kind: str
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, visible, real):
        d, dt = self.dims, self.dtype
        r = d.residual_multiplier
        h = RMSNorm(d.rms_norm_eps, dt, name="mixer_norm")(x)
        chunks = kda_chunks = jnp.zeros((2,), jnp.int32)
        if self.kind == "mamba":
            with jax.named_scope("text_hybrid/mamba"):
                x = x + r * Mamba2Mixer(d, dt, name="mamba")(h)
            chunks = chunk_counts(real, d.mamba_chunk_size)
        elif self.kind == "kda":
            with jax.named_scope("text_hybrid/kda"):
                x = x + r * KDAMixer(d, dt, name="kda")(h)
            kda_chunks = chunk_counts(real, d.kda_chunk_size)
        else:
            with jax.named_scope("text_hybrid/attn"):
                x = x + r * GQA(d, dt, name="attn")(h, visible)
        h = RMSNorm(d.rms_norm_eps, dt, name="mlp_norm")(x)
        with jax.named_scope("text_hybrid/shared"):
            shared = DenseMLP(d.shared_intermediate_size, dt,
                              name="shared")(h)
        with jax.named_scope("text_hybrid/moe"):
            routed, pairs = RoutedExperts(d, dt, name="moe")(h, real)
        blocks = [pairs, chunks] + ([kda_chunks] if "kda" in d.layer_types
                                    else [])
        self.sow(COUNTERS, "layer", jnp.concatenate(blocks))
        return x + r * (shared + routed)


class TextHybrid(nn.Module):
    """tokens (B, S) int -> (B, embd_dim): the query's embedding."""
    dims: Any
    embd_dim: int = 512
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, tokens):
        d, dt = self.dims, self.dtype
        real, visible = attention_mask(tokens)
        table = self.param("embed", nn.initializers.normal(1.0),
                           (d.vocab_size, d.hidden_size))
        x = (jnp.take(table, tokens, axis=0).astype(dt)
             * d.embedding_multiplier)
        for i, kind in enumerate(d.layer_types):
            x = Layer(d, kind=kind, dtype=dt, name=f"layers_{i}")(
                x, visible, real)
        last = jnp.maximum(jnp.sum(real, axis=1) - 1, 0)
        x = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        x = RMSNorm(d.rms_norm_eps, dt, name="norm")(x)
        proj = self.param("proj", _fan_in_normal, (d.hidden_size,
                                                   self.embd_dim))
        return _dot(x, proj, dt)
