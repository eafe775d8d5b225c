"""The plain reference of the granite-4.0-h-small sentence tower
(ibm-granite/granite-4.0-h-small ``config.json``, ``model_type``
``granitemoehybrid``; the layer equations of the family's published
modelling code and of Mamba-2's paper): float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, no kernels, no grouping of
tokens, one layer's float32 weights resident at a time.  It takes the
benchmark's weights (``benchmarks/weights_granite4h.py``, made again from
the seed) and nothing the program made.

The Mamba-2 layer is computed AS THE RECURRENCE ITSELF: a ``lax.scan`` over
the positions with one state (d_head x d_state) a head, ``S_t = a_t S_{t-1}
+ dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t`` — no chunks, no quadratic
form, nothing of ``milnce_tpu/ops/ssd.py``.

Departures from the published model, each the configuration's (its file
lists them under ``assumed`` / ``reduced``):

- **no output head**: an embedding tower has none (the tied head and
  ``logits_scaling`` are not used).  Instead: the final RMSNorm at each
  row's LAST REAL token, times a bias-free projection ``proj`` (hidden ->
  512) into the joint space, as for the A.X-K1 tower.
- **the fused matrices are held split**: the published experts' and shared
  MLP's input matrices (hidden x 2 width, gate and up side by side) are
  two matrices here, ``w_gate`` and ``w_up``: the same product, column
  block by column block.
- **the chip's share**: given ``(first_expert, experts_held)``, only the
  held experts' part of the routed sum is added (the router still scores
  all experts and soft-maxes over the ten it chose).  What the absent
  experts would add is left out, and that partial result goes on to the
  next layer — in the program alike.
- **pads**: id 0; real ids come first.  A pad is never a key for a real
  position and never routed; conv and recurrence are causal, so no real
  position reads a pad.  Rows are computed in blocks, each cut to its
  longest row (rounded up): the positions cut away are pads no real
  position reads.

``precision='float8'`` is the control: the inputs (activations and
weights) of the mixers' projections, the router's product and the routed
experts' three products rounded to ``float8_e4m3fn``, one step below the
bfloat16 the configuration states.

**Following a program's routing** (``follow``), as
``benchmarks/reference/axk1_text.py`` does and for its reason: given the
experts the program chose for each token, the reference measures the
choice against its own router (``route_margin``: how far, in its own
float32 logits, the best expert left out lies above the worst taken) and
goes on with the PROGRAM's experts, weighted by its own soft-max over its
own logits of them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

PREFIX = "text_module"
WIDTH_STEP = 256        # a block of rows is cut to its longest, rounded up
#                         (few widths: each is a program to compile)


def _round(x, precision: str):
    if precision == "float32":
        return x
    if precision == "float8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    raise ValueError(f"precision {precision!r}")


def rms(x, weight, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def recurrence(x, dt, a, b, c, d):
    """The state-space layer as written: x (B, S, H, P), dt (B, S, H)
    positive, a (H,) negative, b, c (B, S, N), d (H,) -> y (B, S, H, P)."""

    def step(state, at_t):
        x_t, dt_t, b_t, c_t = at_t
        decay = jnp.exp(dt_t * a)                               # (B, H)
        state = (decay[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None]
                 * b_t[:, None, None, :])
        return state, jnp.einsum("bhpn,bn->bhp", state, c_t) + d[:, None] * x_t

    rows, _, heads, p = x.shape
    zero = jnp.zeros((rows, heads, p, b.shape[-1]), jnp.float32)
    _, y = lax.scan(step, zero, tuple(jnp.moveaxis(t, 1, 0)
                                      for t in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)


def mamba(u, w: dict, lm: dict, precision: str = "float32"):
    """u (B, S, hidden) float32 -> the mixer's output."""
    r = lambda t: _round(t, precision)      # noqa: E731
    heads, p, n = lm["mamba_n_heads"], lm["mamba_d_head"], lm["mamba_d_state"]
    inner, taps = heads * p, lm["mamba_d_conv"]
    b, s, _ = u.shape
    fused = r(u) @ r(w["mamba/w_in"])
    z, xbc, dt = (fused[..., :inner], fused[..., inner:2 * inner + 2 * n],
                  fused[..., 2 * inner + 2 * n:])
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + s] * w["mamba/conv_w"][:, j]
               for j in range(taps))
    if "mamba/conv_b" in w:
        conv = conv + w["mamba/conv_b"]
    xbc = jax.nn.silu(conv)
    dt = jax.nn.softplus(dt + w["mamba/dt_bias"])
    y = recurrence(xbc[..., :inner].reshape(b, s, heads, p), dt,
                   -jnp.exp(w["mamba/A_log"]), xbc[..., inner:inner + n],
                   xbc[..., inner + n:], w["mamba/D"])
    gated = y.reshape(b, s, inner) * jax.nn.silu(z)
    normed = rms(gated, w["mamba/norm/weight"], lm["rms_norm_eps"])
    return r(normed) @ r(w["mamba/w_out"])


def attention(h, w: dict, lengths, lm: dict, precision: str = "float32"):
    """Grouped-query attention, no position term.  h (B, S, hidden)."""
    r = lambda t: _round(t, precision)      # noqa: E731
    b, s, hidden = h.shape
    heads, kv = lm["num_attention_heads"], lm["num_key_value_heads"]
    width = hidden // heads
    q = (r(h) @ r(w["attn/wq"])).reshape(b, s, heads, width)
    k = (r(h) @ r(w["attn/wk"])).reshape(b, s, kv, width)
    v = (r(h) @ r(w["attn/wv"])).reshape(b, s, kv, width)
    k, v = (jnp.repeat(t, heads // kv, axis=2) for t in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * lm["attention_multiplier"]
    at = jnp.arange(s)
    key_real = at[None, :] < lengths[:, None]
    visible = ((at[None, None, :] <= at[None, :, None])
               & key_real[:, None, :]) | (at[:, None] == at[None, :])[None]
    probs = jax.nn.softmax(jnp.where(visible[:, None], scores, -jnp.inf),
                           axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, heads * width)
    return r(out) @ r(w["attn/wo"])


def swiglu(h, gate, up, down, precision="float32"):
    r = lambda a: _round(a, precision)      # noqa: E731
    mid = jax.nn.silu(r(h) @ r(gate)) * (r(h) @ r(up))
    return r(mid) @ r(down)


def moe(h, w: dict, real, lm: dict, first_expert: int, experts_held: int,
        precision: str = "float32", follow=None):
    """h (T, hidden), real (T,), follow (T, k) int or None -> (this
    share's part of the routed sum — WITHOUT the shared MLP —, the (T, k)
    experts whose outputs were added, the (T,) route margin of ``follow``:
    zeros without it)."""
    r = lambda a: _round(a, precision)      # noqa: E731
    logits = r(h) @ r(w["moe/router"])                          # (T, 72)
    top, chosen = lax.top_k(logits, lm["num_experts_per_tok"])
    margin = jnp.zeros(h.shape[:1], jnp.float32)
    if follow is not None:
        chosen = follow
        taken = jnp.any(follow[:, :, None]
                        == jnp.arange(logits.shape[1])[None, None], axis=1)
        worst_in = jnp.min(jnp.where(taken, logits, jnp.inf), axis=1)
        best_out = jnp.max(jnp.where(taken, -jnp.inf, logits), axis=1)
        margin = jnp.where(real, jnp.maximum(best_out - worst_in, 0.0), 0.0)
        top = jnp.take_along_axis(logits, follow, axis=1)
    top = jax.nn.softmax(top, axis=-1)

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
    eps, res = lm["rms_norm_eps"], lm["residual_multiplier"]
    h = rms(x, w["mixer_norm/weight"], eps)
    if kind == "mamba":
        x = x + res * mamba(h, w, lm, precision)
    elif kind == "attention":
        x = x + res * attention(h, w, lengths, lm, precision)
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
    return (x + res * (shared + routed).reshape(x.shape),
            chosen.reshape(b, s, -1), margin.reshape(b, s))


_layer = jax.jit(layer, static_argnames=("kind", "first_expert",
                                         "experts_held", "precision"),
                 static_argnums=(3,))


class _Frozen(dict):
    """The published keys as a hashable static argument."""

    def __hash__(self):
        return hash(repr(sorted(self.items(), key=lambda kv: kv[0])))


def _freeze(value):
    if isinstance(value, dict):
        return _Frozen({k: _freeze(v) for k, v in value.items()})
    return tuple(value) if isinstance(value, list) else value


def row_blocks(lengths: np.ndarray, width: int, block_rows: int) -> list:
    """[(rows of the block, its width), ...]: the rows by falling length,
    ``block_rows`` at a time, each block as wide as its longest row
    rounded up to ``WIDTH_STEP`` (at most ``width``)."""
    order = np.argsort(-lengths, kind="stable")
    out = []
    for lo in range(0, len(order), block_rows):
        rows = order[lo:lo + block_rows]
        longest = max(int(lengths[rows].max()), 1)
        out.append((rows, min(width, -(-longest // WIDTH_STEP) * WIDTH_STEP)))
    return out


def query_embeddings(get_weights, token_rows, lm: dict, *, layers: int,
                     first_expert: int, experts_held: int,
                     precision: str = "float32", per_layer: bool = False,
                     follow=None, routing: bool = False,
                     block_rows: int = 8):
    """``get_weights(prefix)`` -> {name under the prefix: float32 array}
    for ``text_module/`` (``embed``, ``norm/weight``, ``proj``) and for
    each ``text_module/layers_<i>/``; called once a layer, so a caller can
    make a layer's weights as they are asked for.  ``token_rows`` (S, W)
    int, 0 = pad.  ``follow``: one (S, W, k) int array a layer, in order —
    the experts a program chose (the module's docstring).
    -> (S, 512) float32 embeddings; with ``per_layer`` also the list of x
    (S, W, hidden) after each layer (zeros where a block was cut); with
    ``routing`` also {"experts": the (S, W, k) experts added, a layer;
    "margin": (S,) each query's largest route margin over its real tokens
    and the layers}."""
    ids = np.asarray(token_rows, np.int32)
    n, width = ids.shape
    lengths = (ids != 0).sum(axis=1)
    frozen = _freeze({k: v for k, v in lm.items()
                      if not isinstance(v, dict)})
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
              * lm["embedding_multiplier"] for rows, w in blocks]
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
