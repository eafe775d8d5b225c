"""The plain reference of the A.X-K1 sentence tower (skt/A.X-K1
``config.json``; the layer equations of DeepSeek-V3's published modelling
code, which ``model_type: axk1`` follows): float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, no kernels, no grouping of
tokens, one layer at a time so that one layer's float32 weights are what is
resident.  It takes the benchmark's weights (``benchmarks/weights_axk1.py``,
made again from the seed) and nothing the program made.

Departures from the published model, each the configuration's (its file
lists them under ``assumed`` / ``reduced``):

- **no output head**: an embedding tower has none.  Instead: the final
  RMSNorm at each row's LAST REAL token, times a bias-free projection
  ``proj`` (hidden -> 512) into the joint space.  Last-token pooling and
  the projection are this repo's choice for a causal model used as an
  encoder.
- **the router is read as written**: ``topk_method: "none"`` beside
  ``n_group`` 8 / ``topk_group`` 4 -> no group limit and no correction
  bias: the 8 largest of all 192 sigmoid scores, normalised over the
  chosen 8 (``norm_topk_prob``), times ``routed_scaling_factor``.
- **the chip's share**: given ``(first_expert, experts_held)``, only the
  held experts' part of the routed sum is added (the router still scores
  all experts and a token's weights are normalised over all 8 it chose).
  What the absent experts would add is left out, and that partial result
  goes on to the next layer — in the program alike.
- **pads**: id 0; real ids come first.  A pad is never a key for a real
  position and never routed.  Every position sees itself, so a pad row's
  softmax is finite (its output is never read).
- RoPE: the pairs (2i, 2i+1) of the rope part are rotated in place; the
  published code de-interleaves them first, a fixed permutation of q and
  k alike that no score can see.

``precision='float8'`` is the control: the inputs of the router's product
and of the routed experts' three products (activations and weights)
rounded to ``float8_e4m3fn``, one step below the bfloat16 the
configuration states.

**Following a program's routing** (``follow``).  A top-k router is
discontinuous: the 8th and 9th of 192 scores lie ~0.06 apart in the
logit, and two sound computations that differ by rounding alone pick
another expert for about one token in ten, whose state then moves by a
whole expert's output.  A comparison of outputs cannot tell that from a
fault.  So the comparison is split in two.  Given the experts the
program under test chose for each token, the reference (a) measures the
choice against its own router: the ``route_margin`` of a token is how far,
in the reference's own float32 logits, the best expert the program left
out lies above the worst it took — 0 where the program's eight are the
reference's eight, rounding-sized at a near-tie, large where the router is
wrong; and (b) goes on with the PROGRAM's eight, weighted by its own
scores of them.  What comes out then differs from the program's output by
arithmetic alone, and the margin is held to a limit of its own.  Nothing
else of the program is followed.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

PREFIX = "text_module"


def _round(x, precision: str):
    if precision == "float32":
        return x
    if precision == "float8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    raise ValueError(f"precision {precision!r}")


def rms(x, weight, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def yarn_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rope_angles(lm: dict, positions: int):
    """-> (angles (positions, rope/2), factor on cos/sin, factor on the
    softmax scale).  ``lm``: the published keys; ``rope_scaling`` is the
    published group (None: plain RoPE)."""
    dim, base = lm["qk_rope_head_dim"], float(lm["rope_theta"])
    i = np.arange(0, dim, 2, dtype=np.float64)
    inv_freq = 1.0 / base ** (i / dim)
    on_table, on_softmax = 1.0, 1.0
    rs = lm.get("rope_scaling")
    if rs:
        assert rs["type"] == "yarn", rs
        orig = rs["original_max_position_embeddings"]

        def correction_dim(rotations):
            return (dim * math.log(orig / (rotations * 2 * math.pi))
                    / (2 * math.log(base)))

        low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
        high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
        extrapolated = 1.0 - ramp
        inv_freq = (inv_freq / rs["factor"]) * (1 - extrapolated) \
            + inv_freq * extrapolated
        on_table = (yarn_mscale(rs["factor"], rs["mscale"])
                    / yarn_mscale(rs["factor"], rs["mscale_all_dim"]))
        on_softmax = yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    angles = np.arange(positions, dtype=np.float64)[:, None] * inv_freq[None]
    return jnp.asarray(angles, jnp.float32), on_table, on_softmax


def rotate(x, angles, on_table):
    """x (B, S, H, rope): pairs (2i, 2i+1) by the position's angle i."""
    cos = (jnp.cos(angles) * on_table)[None, :, None, :]
    sin = (jnp.sin(angles) * on_table)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
    return out.reshape(x.shape)


def mla(h, w: dict, lengths, lm: dict):
    """h (B, S, hidden) float32 -> the attention's addition to x."""
    b, s, _ = h.shape
    heads = lm["num_attention_heads"]
    nope, rope, vdim = (lm["qk_nope_head_dim"], lm["qk_rope_head_dim"],
                        lm["v_head_dim"])
    rank, eps = lm["kv_lora_rank"], lm["rms_norm_eps"]
    cq = rms(h @ w["attn/wq_a"], w["attn/q_norm/weight"], eps)
    q = (cq @ w["attn/wq_b"]).reshape(b, s, heads, nope + rope)
    kv_a = h @ w["attn/wkv_a"]
    ckv = rms(kv_a[..., :rank], w["attn/kv_norm/weight"], eps)
    kv = (ckv @ w["attn/wkv_b"]).reshape(b, s, heads, nope + vdim)
    angles, on_table, on_softmax = rope_angles(lm, s)
    q_rope = rotate(q[..., nope:], angles, on_table)
    k_rope = rotate(kv_a[:, :, None, rank:], angles, on_table)
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :nope], kv[..., :nope])
              + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope[:, :, 0]))
    scores = scores * (nope + rope) ** -0.5 * on_softmax
    at = jnp.arange(s)
    key_real = at[None, :] < lengths[:, None]                   # (B, S)
    visible = ((at[None, None, :] <= at[None, :, None])
               & key_real[:, None, :]) | (at[:, None] == at[None, :])[None]
    scores = jnp.where(visible[:, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, kv[..., nope:])
    return out.reshape(b, s, heads * vdim) @ w["attn/wo"]


def swiglu(h, gate, up, down, precision="float32"):
    r = lambda a: _round(a, precision)      # noqa: E731
    mid = jax.nn.silu(r(h) @ r(gate)) * (r(h) @ r(up))
    return r(mid) @ r(down)


def moe(h, w: dict, real, lm: dict, first_expert: int, experts_held: int,
        precision: str = "float32", follow=None):
    """h (T, hidden), real (T,), follow (T, k) int or None -> (shared
    expert + this share's part of the routed sum, the (T, k) experts whose
    outputs were added, the (T,) route margin of ``follow`` — zeros
    without it)."""
    r = lambda a: _round(a, precision)      # noqa: E731
    logits = r(h) @ r(w["moe/router"])                          # (T, 192)
    scores = jax.nn.sigmoid(logits)
    top, chosen = lax.top_k(scores, lm["num_experts_per_tok"])
    margin = jnp.zeros(h.shape[:1], jnp.float32)
    if follow is not None:
        chosen = follow
        taken = jnp.any(follow[:, :, None]
                        == jnp.arange(logits.shape[1])[None, None], axis=1)
        worst_in = jnp.min(jnp.where(taken, logits, jnp.inf), axis=1)
        best_out = jnp.max(jnp.where(taken, -jnp.inf, logits), axis=1)
        margin = jnp.where(real, jnp.maximum(best_out - worst_in, 0.0), 0.0)
        top = jnp.take_along_axis(scores, follow, axis=1)
    if lm["norm_topk_prob"]:
        top = top / top.sum(axis=-1, keepdims=True)
    top = top * lm["routed_scaling_factor"]
    out = swiglu(h, w["moe/shared/w_gate"], w["moe/shared/w_up"],
                 w["moe/shared/w_down"])
    for j in range(experts_held):
        e = first_expert + j
        weight = jnp.sum(jnp.where(chosen == e, top, 0.0), axis=-1)  # (T,)
        weight = jnp.where(real, weight, 0.0)
        out = out + weight[:, None] * swiglu(
            h, w["moe/w_gate"][j], w["moe/w_up"][j], w["moe/w_down"][j],
            precision)
    return out, chosen, margin


def layer(x, w: dict, lengths, lm: dict, dense: bool, first_expert: int,
          experts_held: int, precision: str = "float32", follow=None):
    """One layer -> (x, the (B, S, k) experts added, the (B, S) route
    margins); the last two None for a dense layer.  ``w``: the layer's
    float32 weights by their names under ``text_module/layers_<i>/``;
    ``follow`` (B, S, k): the experts to take (the module's docstring)."""
    eps = lm["rms_norm_eps"]
    x = x + mla(rms(x, w["attn_norm/weight"], eps), w, lengths, lm)
    h = rms(x, w["mlp_norm/weight"], eps)
    if dense:
        return x + swiglu(h, w["mlp/w_gate"], w["mlp/w_up"],
                          w["mlp/w_down"]), None, None
    b, s, hidden = x.shape
    real = (jnp.arange(s)[None, :] < lengths[:, None]).reshape(-1)
    add, chosen, margin = moe(
        h.reshape(-1, hidden), w, real, lm, first_expert, experts_held,
        precision,
        None if follow is None else follow.reshape(b * s, -1))
    return (x + add.reshape(x.shape), chosen.reshape(b, s, -1),
            margin.reshape(b, s))


_layer = jax.jit(layer, static_argnames=("dense", "first_expert",
                                         "experts_held", "precision"),
                 static_argnums=(3,))


class _Frozen(dict):
    """The published keys as a hashable static argument."""

    def __hash__(self):
        return hash(repr(sorted(self.items(), key=lambda kv: kv[0])))


def query_embeddings(get_weights, token_rows, lm: dict, *, layers: int,
                     first_expert: int, experts_held: int,
                     precision: str = "float32", per_layer: bool = False,
                     follow=None, routing: bool = False):
    """``get_weights(prefix)`` -> {name under the prefix: float32 array}
    for ``text_module/`` (``embed``, ``norm/weight``, ``proj``) and for
    each ``text_module/layers_<i>/``; called once a layer, so a caller can
    make a layer's weights as they are asked for.  ``token_rows`` (S, W)
    int, 0 = pad.  ``follow``: one (S, W, k) int array an expert layer, in
    order — the experts a program chose (the module's docstring).
    -> (S, 512) float32 embeddings; with ``per_layer`` also the list of x
    after each layer; with ``routing`` also {"experts": the (S, W, k)
    experts added, an expert layer; "margin": (S,) each query's largest
    route margin over its real tokens and the layers}."""
    ids = jnp.asarray(token_rows, jnp.int32)
    lengths = jnp.sum(ids != 0, axis=1)
    frozen = _Frozen({k: (_Frozen(v) if isinstance(v, dict) else v)
                      for k, v in lm.items()})
    after, experts = [], []
    margin = jnp.zeros(ids.shape[:1], jnp.float32)
    follow = iter(follow) if follow is not None else None
    with jax.default_matmul_precision("highest"):
        top = get_weights(PREFIX + "/")
        x = jnp.take(jnp.asarray(top["embed"], jnp.float32), ids, axis=0)
        for i in range(layers):
            dense = i < lm["first_k_dense_replace"]
            w = get_weights(f"{PREFIX}/layers_{i}/")
            x, chosen, margins = _layer(
                x, w, lengths, frozen, dense=dense,
                first_expert=first_expert, experts_held=experts_held,
                precision=precision,
                follow=(None if dense or follow is None
                        else jnp.asarray(next(follow), jnp.int32)))
            del w
            if not dense:
                experts.append(chosen)
                margin = jnp.maximum(margin, margins.max(axis=1))
            if per_layer:
                after.append(x)
        last = jnp.maximum(lengths - 1, 0)
        x = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        x = rms(x, jnp.asarray(top["norm/weight"], jnp.float32),
                lm["rms_norm_eps"])
        emb = x @ jnp.asarray(top["proj"], jnp.float32)
    out = (emb,) + ((after,) if per_layer else ()) + (
        ({"experts": experts, "margin": margin},) if routing else ())
    return out if len(out) > 1 else emb
