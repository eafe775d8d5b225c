"""Sentence tower from a causal language model: token table -> layers of
latent attention (MLA, decoupled RoPE under YaRN) and SwiGLU — the first
``first_k_dense_replace`` dense, the rest routed experts beside a shared
expert — -> RMSNorm at each row's last real token -> projection into the
joint space.  The keys of :class:`milnce_tpu.config.TextLMConfig` carry
their published names (a DeepSeek-V3-shaped ``config.json``).

Ids: 0 is the pad, real ids come first in a row; ``len`` = real tokens of
a row, positions ``0..len-1``.  A pad never reaches an expert and no real
position attends to one.

The expert layer is told which experts it holds (``first_expert``,
``experts_held`` of ``n_routed_experts``): it routes over all of them,
drops no token, and adds its own experts' part of the result — what
expert parallelism asks of one chip.  With ``(0, n_routed_experts)`` it is
the whole layer.  What the absent experts would add is left out; nothing
stands in for the chips that hold them or for the all-reduce that would
join the parts.

Activations are ``dtype`` (bfloat16 when served so); norm statistics,
router scores and softmax are float32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax

from milnce_tpu.config import TextLMConfig
from milnce_tpu.ops import grouped_matmul

COUNTERS = "moe_counters"       # the collection the expert layers sow into
COUNTER_NAMES = ("moe_pairs_held", "moe_expert_max", "moe_pairs_total",
                 "moe_tile_rows")
ROUTING = "moe_routing"         # each token's chosen experts, (B, S, k) an
#                                 expert layer: sown for whoever applies the
#                                 tower with this collection mutable (a
#                                 diagnostic; the served program does not)


# ``TextLMConfig`` frozen: hashable, so the module that holds it stays
# usable as a static jit argument
LMDims = dataclasses.make_dataclass(
    "LMDims", [(f.name, f.type) for f in dataclasses.fields(TextLMConfig)],
    frozen=True)


def lm_dims(cfg: TextLMConfig):
    """``cfg`` validated and frozen: a value the tower does not implement
    is an error here, at build time."""
    d = LMDims(**dataclasses.asdict(cfg))
    if d.scoring_func != "sigmoid":
        raise ValueError(f"text_lm.scoring_func={d.scoring_func!r}: the "
                         "tower implements 'sigmoid' only")
    if d.topk_method != "none":
        raise ValueError(f"text_lm.topk_method={d.topk_method!r}: the "
                         "tower implements 'none' only (top-k over all "
                         "experts, no group limit, no correction bias)")
    if d.rope_scaling_type != "yarn":
        raise ValueError(f"text_lm.rope_scaling_type="
                         f"{d.rope_scaling_type!r}: the tower implements "
                         "'yarn' only")
    if not (0 <= d.first_expert and d.experts_held >= 1
            and d.first_expert + d.experts_held <= d.n_routed_experts):
        raise ValueError(
            f"text_lm: experts [{d.first_expert}, {d.first_expert} + "
            f"{d.experts_held}) lie outside the {d.n_routed_experts} "
            "routed experts")
    if d.num_experts_per_tok > d.n_routed_experts:
        raise ValueError("text_lm.num_experts_per_tok exceeds "
                         "n_routed_experts")
    return d


# ---- YaRN (the published rope_scaling; DeepSeek-V3's arithmetic) ---------

def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rope_tables(d, positions: int):
    """-> (cos, sin), each (positions, qk_rope_head_dim / 2) float32, and
    the factor YaRN puts on the softmax scale."""
    half = d.qk_rope_head_dim // 2
    exponent = jnp.arange(half, dtype=jnp.float32) * 2.0 / d.qk_rope_head_dim
    inv_freq = 1.0 / d.rope_theta ** exponent
    dim, base = d.qk_rope_head_dim, d.rope_theta
    orig = d.rope_scaling_original_max_position_embeddings

    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(d.rope_scaling_beta_fast)), 0)
    high = min(math.ceil(correction_dim(d.rope_scaling_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                   # 1: the frequency stays as it was
    inv_freq = (inv_freq / d.rope_scaling_factor) * (1.0 - keep) \
        + inv_freq * keep
    cos_scale = (yarn_get_mscale(d.rope_scaling_factor,
                                 d.rope_scaling_mscale)
                 / yarn_get_mscale(d.rope_scaling_factor,
                                   d.rope_scaling_mscale_all_dim))
    softmax_factor = yarn_get_mscale(d.rope_scaling_factor,
                                     d.rope_scaling_mscale_all_dim) ** 2
    angles = jnp.arange(positions, dtype=jnp.float32)[:, None] * inv_freq
    return jnp.cos(angles) * cos_scale, jnp.sin(angles) * cos_scale, \
        softmax_factor


def rotate_pairs(x, cos, sin):
    """Rotate the pairs (2i, 2i+1) of ``x`` (..., S, heads, rope_dim) by
    the position's angles; float32 in, float32 out."""
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.stack([a * c - b * s, a * s + b * c], axis=-1).reshape(x.shape)


# ---- pieces --------------------------------------------------------------

def _fan_in_normal(key, shape, dtype=jnp.float32):
    """N(0, 1 / fan-in), for a matrix or a stack of them."""
    return (jax.random.normal(key, shape, jnp.float32)
            / math.sqrt(shape[-2])).astype(dtype)


def rms_norm(x, weight, eps: float, dtype):
    """x over its last axis, statistics in float32, out in ``dtype``."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps)
            * weight.astype(jnp.float32)).astype(dtype)


class RMSNorm(nn.Module):
    eps: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        w = self.param("weight", nn.initializers.ones, (x.shape[-1],))
        return rms_norm(x, w, self.eps, self.dtype)


def _dot(x, w, dtype):
    """x (..., in) @ w (in, out) in the activations' type, accumulated in
    float32 by the MXU."""
    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   preferred_element_type=jnp.float32).astype(dtype)


def _swiglu(h, w_gate, w_up, w_down, dtype):
    return _dot(nn.silu(_dot(h, w_gate, dtype)) * _dot(h, w_up, dtype),
                w_down, dtype)


class MLA(nn.Module):
    """Multi-head latent attention over one row's positions; no cache (a
    query is one forward pass)."""
    dims: LMDims
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h, visible):
        """h (B, S, hidden), visible (B, S, S) bool -> (B, S, hidden)."""
        d, dt = self.dims, self.dtype
        heads, nope, rope, vdim = (d.num_attention_heads, d.qk_nope_head_dim,
                                   d.qk_rope_head_dim, d.v_head_dim)
        init = _fan_in_normal
        wq_a = self.param("wq_a", init, (d.hidden_size, d.q_lora_rank))
        wq_b = self.param("wq_b", init, (d.q_lora_rank,
                                         heads * (nope + rope)))
        wkv_a = self.param("wkv_a", init, (d.hidden_size,
                                           d.kv_lora_rank + rope))
        wkv_b = self.param("wkv_b", init, (d.kv_lora_rank,
                                           heads * (nope + vdim)))
        wo = self.param("wo", init, (heads * vdim, d.hidden_size))
        b, s, _ = h.shape
        cq = RMSNorm(d.rms_norm_eps, dt, name="q_norm")(_dot(h, wq_a, dt))
        q = _dot(cq, wq_b, dt).reshape(b, s, heads, nope + rope)
        kv_a = _dot(h, wkv_a, dt)
        ckv = RMSNorm(d.rms_norm_eps, dt, name="kv_norm")(
            kv_a[..., :d.kv_lora_rank])
        kv = _dot(ckv, wkv_b, dt).reshape(b, s, heads, nope + vdim)
        cos, sin, yarn = rope_tables(d, s)
        q_rope = rotate_pairs(q[..., nope:].astype(jnp.float32), cos, sin)
        k_rope = rotate_pairs(kv_a[..., None, d.kv_lora_rank:]
                              .astype(jnp.float32), cos, sin)  # one, shared
        scores = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :nope],
                             kv[..., :nope],
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bqhd,bkd->bhqk", q_rope.astype(dt),
                               k_rope[:, :, 0].astype(dt),
                               preferred_element_type=jnp.float32))
        scores = scores * ((nope + rope) ** -0.5 * yarn)
        scores = jnp.where(visible[:, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)                 # float32
        out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(dt),
                         kv[..., nope:],
                         preferred_element_type=jnp.float32).astype(dt)
        return _dot(out.reshape(b, s, heads * vdim), wo, dt)


class DenseMLP(nn.Module):
    width: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h):
        init = _fan_in_normal
        hidden = h.shape[-1]
        return _swiglu(h,
                       self.param("w_gate", init, (hidden, self.width)),
                       self.param("w_up", init, (hidden, self.width)),
                       self.param("w_down", init, (self.width, hidden)),
                       self.dtype)


def route(h, w_router, d):
    """Every token's choice over ALL routed experts.  h (T, hidden) ->
    (experts (T, k) int32, weights (T, k) float32): sigmoid scores in
    float32, the k largest, their weights normalised over the chosen k and
    scaled (``norm_topk_prob``, ``routed_scaling_factor``)."""
    scores = jax.nn.sigmoid(jnp.dot(h, w_router.astype(h.dtype),
                                    preferred_element_type=jnp.float32))
    top, experts = lax.top_k(scores, d.num_experts_per_tok)
    if d.norm_topk_prob:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return experts.astype(jnp.int32), top * d.routed_scaling_factor


def rank_block(chunk: int) -> int:
    """Ranks (real tokens, counted from 0) a step of the put-back takes,
    from the shapes alone: a turn's pairs, at most 512 (a step's 0/1
    matrix is at most 512 x ``chunk``: the MXU runs it at ~9/10 of its
    peak from 256 ranks on, and a flush wastes under a block of its real
    tokens; PERF.md section 6, PR 33)."""
    return min(chunk, 512)


def turn_pairs(tokens: int, k: int, held: int,
               num_experts: int | None = None) -> int:
    """Pairs a turn of :func:`held_expert_sum` takes, from the share of
    the experts held.  Every expert held (``held == num_experts``): every
    pair of every real token meets a held expert, their number is known to
    be at most ``tokens x k``, and one turn takes them all (a tower that
    makes tens of passes a flush, each a few tokens a row, then pays one
    turn's three kernels a layer a pass and not up to ``4 x k`` of them).
    A part of them held (or ``num_experts`` not given): a quarter of the
    slots, which is what a rung served with 7 slots in 10 pads sends to a
    chip's share (~0.15 pairs a slot to 12 of 192 experts at 8 a token);
    what a turn costs beside the products (the gather, the put-back) grows
    with its size, a further turn costs the groups it touches: so the
    typical flush, not the fullest, sizes it."""
    if num_experts is not None and held == num_experts:
        return tokens * k
    return max(1, tokens // 4)


def held_expert_sum(h, experts, weights, real, w_gate, w_up, w_down, *,
                    first_expert: int, dtype,
                    num_experts: int | None = None):
    """sum over the chosen experts held here of weight x SwiGLU_e(h), for
    every real token.  w_* are (held, ...) stacks.  -> (out (T, hidden)
    float32, pairs held, most pairs of one expert, rows the grouped
    products were asked to multiply).

    The (token, expert) pairs that meet a held expert are sorted by expert
    and multiplied group by group (``ops/grouped_matmul.py``: a kernel
    that walks the row tiles in which a group has a row, so that an expert
    fed 18 rows costs its matrices' bytes and one tile of rows),
    :func:`turn_pairs` pairs at a time (every pair at once where
    ``num_experts`` says that every expert is held, else ``T / 4``) for as
    long as pairs remain: no capacity, so no token is ever dropped; a
    flush whose tokens all pick the same experts takes more turns (at most
    ``4 x num_experts_per_tok``), not a larger buffer.

    A turn's weighted rows go back to their tokens (scope ``putback``) at
    the cost of what the flush holds, not of its slots: every real token
    has a rank (its place among the real ones), a pair carries its
    token's rank through the sort, and the turn's rows are added into a
    float32 buffer of ranks by a 0/1 product, ``rank_block`` ranks a step,
    for as many steps as the real tokens fill (a value of the program).
    After the loop one gather of rows lays the ranks back on the slots; a
    pad reads a row that no rank wrote."""
    tokens, k = experts.shape
    held, hidden = w_gate.shape[0], h.shape[-1]
    local = experts - first_expert
    mine = (local >= 0) & (local < held) & real[:, None]
    group = jnp.where(mine, local, held).reshape(-1)       # held = "not here"
    rank = jnp.cumsum(real.astype(jnp.int32)) - 1      # of a real token
    n_real = rank[-1] + 1
    # a pair goes through the sort with its token, its weight and its
    # token's rank: a gather of tokens x k scalars by the sorted order
    # takes 0.6-0.7 ms a layer on the chip for each of the three, and a
    # scatter-add of as many ones as long, so the groups are counted by
    # comparison (PERF.md section 6, PR 33)
    _, token_of, weight_of, rank_of = lax.sort(
        (group, jnp.repeat(jnp.arange(tokens, dtype=jnp.int32), k),
         weights.reshape(-1), jnp.repeat(rank, k)),
        num_keys=1, is_stable=True)
    counts = jnp.sum(group[None, :] == jnp.arange(held)[:, None], axis=1,
                     dtype=jnp.int32)
    ends = jnp.cumsum(counts)
    n_held = ends[-1]
    # pairs a turn, in whole tiles of the grouped product
    pairs = turn_pairs(tokens, k, held, num_experts)
    tile = grouped_matmul.tiling(pairs, *w_gate.shape[1:], dtype)[0]
    chunk = -(-pairs // tile) * tile
    spare = -(tokens * k) % chunk       # the last turn's slice stays inside
    token_of, weight_of, rank_of = (jnp.pad(of, (0, spare)) for of in
                                    (token_of, weight_of, rank_of))
    block = rank_block(chunk)
    blocks = (n_real + block - 1) // block              # that hold a rank
    room = -(-tokens // block) * block

    def turn(state):
        c, by_rank, tile_rows = state
        lo = c * chunk
        sizes = (jnp.clip(ends, lo, lo + chunk)
                 - jnp.clip(ends - counts, lo, lo + chunk))

        def grouped(rows, stack, out_dtype):
            return grouped_matmul.grouped_matmul(
                rows, stack.astype(dtype), sizes, out_dtype=out_dtype)

        tok = lax.dynamic_slice(token_of, (lo,), (chunk,))
        wgt = lax.dynamic_slice(weight_of, (lo,), (chunk,))
        live = lo + jnp.arange(chunk) < n_held
        x = jnp.take(h, tok, axis=0)
        gate = grouped(x, w_gate, dtype)
        up = grouped(x, w_up, dtype)
        y = grouped(nn.silu(gate) * up, w_down, jnp.float32)
        # a row beyond the groups comes back unspecified (NaN, interpreted):
        # masked here, ahead of every sum over rows
        y = jnp.where(live[:, None], y * wgt[:, None], 0.0).astype(dtype)
        visits = grouped_matmul.tile_visits(sizes, chunk, tile)[3]
        with jax.named_scope("putback"):
            # back to the tokens' ranks: a 0/1 matrix of (a block of ranks)
            # x (the turn's pairs) times y on the MXU, exact, over the
            # blocks that hold a real token (a scatter-add of rows is a
            # serial loop on the TPU; a product over every slot of the
            # flush pays for its pads)
            ranks = jnp.where(
                live, lax.dynamic_slice(rank_of, (lo,), (chunk,)), -1)

            def add(b, by_rank):
                at = b * block
                place = ranks[None, :] == (at + jnp.arange(block))[:, None]
                rows = lax.dynamic_slice(by_rank, (at, 0), (block, hidden))
                return lax.dynamic_update_slice(
                    by_rank,
                    rows + jnp.dot(place.astype(dtype), y,
                                   preferred_element_type=jnp.float32),
                    (at, 0))

            by_rank = lax.fori_loop(0, blocks, add, by_rank)
        return c + 1, by_rank, tile_rows + visits * tile

    def more(state):
        return state[0] * chunk < n_held

    _, by_rank, tile_rows = lax.while_loop(
        more, turn, (jnp.int32(0), jnp.zeros((room, hidden), jnp.float32),
                     jnp.int32(0)))
    with jax.named_scope("putback"):
        # where there is a pad, row ``n_real`` is the first that no rank
        # wrote: still zero
        out = jnp.take(by_rank, jnp.where(real, rank, n_real), axis=0,
                       mode="clip")
    return out, n_held, jnp.max(counts), tile_rows


class MoE(nn.Module):
    """Routed experts beside the shared expert."""
    dims: LMDims
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h, real):
        """h (B, S, hidden), real (B, S) bool -> (B, S, hidden)."""
        d, dt = self.dims, self.dtype
        init = _fan_in_normal
        hidden, width, held = (d.hidden_size, d.moe_intermediate_size,
                               d.experts_held)
        w_router = self.param("router", init, (hidden, d.n_routed_experts))
        w_gate = self.param("w_gate", init, (held, hidden, width))
        w_up = self.param("w_up", init, (held, hidden, width))
        w_down = self.param("w_down", init, (held, width, hidden))
        shared = DenseMLP(width * d.n_shared_experts, dt, name="shared")(h)
        flat, flat_real = h.reshape(-1, hidden), real.reshape(-1)
        experts, weights = route(flat, w_router, d)
        routed, n_held, most, tile_rows = held_expert_sum(
            flat, experts, weights, flat_real, w_gate, w_up, w_down,
            first_expert=d.first_expert, dtype=dt,
            num_experts=d.n_routed_experts)
        total = jnp.sum(flat_real) * d.num_experts_per_tok
        self.sow(COUNTERS, "layer", jnp.stack(
            [n_held, most, total.astype(jnp.int32), tile_rows]))
        self.sow(ROUTING, "experts",
                 experts.reshape(h.shape[:2] + experts.shape[-1:]))
        return shared + routed.reshape(h.shape).astype(dt)


class Layer(nn.Module):
    dims: LMDims
    dense: bool
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, visible, real):
        d, dt = self.dims, self.dtype
        with jax.named_scope("text_lm/mla"):
            x = x + MLA(d, dt, name="attn")(
                RMSNorm(d.rms_norm_eps, dt, name="attn_norm")(x), visible)
        h = RMSNorm(d.rms_norm_eps, dt, name="mlp_norm")(x)
        if self.dense:
            with jax.named_scope("text_lm/dense"):
                return x + DenseMLP(d.intermediate_size, dt, name="mlp")(h)
        with jax.named_scope("text_lm/moe"):
            return x + MoE(d, dt, name="moe")(h, real)


def attention_mask(ids):
    """(B, S) ids -> (real (B, S), visible (B, S, S)): a position sees the
    real positions up to itself, and always itself (a pad row's softmax
    stays finite; no real position sees a pad)."""
    s = ids.shape[1]
    real = jnp.arange(s)[None, :] < jnp.sum(ids != 0, axis=1)[:, None]
    at = jnp.arange(s)
    causal = at[None, :] <= at[:, None]
    visible = (causal[None] & real[:, None, :]) | jnp.eye(s, dtype=bool)[None]
    return real, visible


class TextLM(nn.Module):
    """tokens (B, S) int -> (B, embd_dim): the query's embedding."""
    dims: LMDims
    embd_dim: int = 512
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, tokens):
        d, dt = self.dims, self.dtype
        real, visible = attention_mask(tokens)
        table = self.param("embed", nn.initializers.normal(1.0),
                           (d.vocab_size, d.hidden_size))
        x = jnp.take(table, tokens, axis=0).astype(dt)
        for i in range(d.num_hidden_layers):
            x = Layer(d, dense=i < d.first_k_dense_replace, dtype=dt,
                      name=f"layers_{i}")(x, visible, real)
        last = jnp.maximum(jnp.sum(real, axis=1) - 1, 0)
        x = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        x = RMSNorm(d.rms_norm_eps, dt, name="norm")(x)
        proj = self.param("proj", _fan_in_normal, (d.hidden_size,
                                                   self.embd_dim))
        return _dot(x, proj, dt)


def sum_counters(collection, names=COUNTER_NAMES) -> dict:
    """The sown per-layer blocks (one entry a name, in ``names``' order)
    -> name -> int32 scalar: summed over the layers, a ``*_max`` taken
    over them."""
    blocks = jax.tree_util.tree_leaves(collection)
    stacked = (jnp.stack(blocks) if blocks
               else jnp.zeros((1, len(names)), jnp.int32))
    return {name: (jnp.max if name.endswith("_max") else jnp.sum)(column)
            for name, column in zip(names, stacked.T)}
