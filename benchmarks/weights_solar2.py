"""Weights of the Solar-Open2-250B sentence tower from the seed: the
benchmark's own, handed to the program (inside the export it serves) and to
the plain reference alike.  Nothing here comes from ``milnce_tpu``: names
are the leaves of the tower's parameter tree (``models/text_hybrid.py``'s
``kda`` and gated ``attention`` layers), shapes follow from the
configuration's published keys.

Leaf by leaf, in the type the configuration serves in (bfloat16), one small
jitted program a leaf shape (``weights_granite4h``'s maker): no float32 copy
of the 3.9 B parameters ever exists.  A leaf's values depend on the seed
and its name alone.

Scales (random weights have no training to set them; the configuration's
file repeats them under ``assumed``).  The published description has no
multipliers, so every sublayer adds O(1) to a residual stream that starts
at unit scale, and four layers leave it at ~3 (every sublayer reads it
through an RMSNorm):

- the token table N(0, 1);
- a matrix N(0, 1 / fan-in); norm weights 1 +- 0.1; the conv's taps N(0,
  1 / taps);
- ``A_log`` = log(U(1, 16)) a head and ``dt_bias`` = the inverse softplus
  of exp(U(log 0.001, log 0.1)) a channel, as Mamba initialises them, and
  the decay's low-rank ``f_b`` N(0, 0.25^2 / rank): the low-rank term moves
  softplus's argument by ~0.25, so a channel's decay a step is ~dt x A,
  from e^-0.001 (a memory of many chunks) to e^-1.6 (of a position);
- the attention's ``wq`` and ``wk`` each times (1 / (attention_multiplier
  x sqrt(head_dim)))^(1/2) (1 at 1/sqrt(128)): the scores' spread is ~1;
- a routed expert's down-projection times sqrt(num_experts_per_tok): a
  token's 8 weights sum to 1, so the chip's ~1 pair a token adds ~0.35 of
  what the shared expert adds.
"""

from __future__ import annotations

import math
import zlib

from benchmarks.weights_granite4h import _maker

PREFIX = "text_module"


def layer_kinds(cfg: dict) -> list:
    return list(cfg["layer_types"])[:cfg["num_hidden_layers"]]


def weight_shapes(cfg: dict) -> dict:
    """name -> shape for every leaf of the tower that ``cfg`` (a file
    under ``benchmarks/configs`` with the published keys at its top
    level) describes: the first ``num_hidden_layers`` of ``layer_types``,
    ``n_routed_experts`` experts HELD, ``vocab_size`` rows of the table."""
    hidden = cfg["hidden_size"]
    heads, kv, width = (cfg["num_attention_heads"],
                        cfg["num_key_value_heads"], cfg["head_dim"])
    lin = cfg["linear_attn_config"]
    k_heads, k_width = lin["num_heads"], lin["head_dim"]
    inner, rank = k_heads * k_width, cfg["kda_proj_rank"]
    taps = lin["short_conv_kernel_size"]
    held, expert = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    shared = expert * cfg["n_shared_experts"]
    shapes = {f"{PREFIX}/embed": (cfg["vocab_size"], hidden),
              f"{PREFIX}/norm/weight": (hidden,),
              f"{PREFIX}/proj": (hidden, cfg["model"]["embedding_dim"])}
    for i, kind in enumerate(layer_kinds(cfg)):
        p = f"{PREFIX}/layers_{i}"
        if kind == "kda":
            for branch in ("q", "k", "v"):
                shapes[f"{p}/kda/w{branch}"] = (hidden, inner)
                shapes[f"{p}/kda/conv_{branch}"] = (inner, taps)
            shapes.update({
                f"{p}/kda/f_a": (hidden, rank),
                f"{p}/kda/f_b": (rank, inner),
                f"{p}/kda/dt_bias": (inner,),
                f"{p}/kda/A_log": (k_heads,),
                f"{p}/kda/b": (hidden, k_heads),
                f"{p}/kda/norm/weight": (k_width,),
                f"{p}/kda/g_a": (hidden, rank),
                f"{p}/kda/g_b": (rank, inner),
                f"{p}/kda/wo": (inner, hidden)})
        elif kind == "attention":
            shapes.update({
                f"{p}/attn/wq": (hidden, heads * width),
                f"{p}/attn/wk": (hidden, kv * width),
                f"{p}/attn/wv": (hidden, kv * width),
                f"{p}/attn/wg": (hidden, heads * width),
                f"{p}/attn/wo": (heads * width, hidden)})
        else:
            raise ValueError(f"layer kind {kind!r}")
        shapes.update({
            f"{p}/mixer_norm/weight": (hidden,),
            f"{p}/mlp_norm/weight": (hidden,),
            f"{p}/shared/w_gate": (hidden, shared),
            f"{p}/shared/w_up": (hidden, shared),
            f"{p}/shared/w_down": (shared, hidden),
            f"{p}/moe/router": (hidden, cfg["published"]["n_routed_experts"]),
            f"{p}/moe/w_gate": (held, hidden, expert),
            f"{p}/moe/w_up": (held, hidden, expert),
            f"{p}/moe/w_down": (held, expert, hidden)})
    return shapes


def leaf_rule(name: str, shape, cfg: dict) -> tuple:
    """-> (kind, p, q): 'normal' with mean p and deviation q; 'log_uniform'
    = log(U(p, q)); 'dt_bias' = the inverse softplus of exp(U(log p,
    log q))."""
    if name.endswith("/A_log"):
        return "log_uniform", 1.0, 16.0
    if name.endswith("/dt_bias"):
        return "dt_bias", 1e-3, 1e-1
    if name.endswith("/weight"):
        return "normal", 1.0, 0.1
    if name.endswith("/embed"):
        return "normal", 0.0, 1.0 / cfg["embedding_multiplier"]
    if "/conv_" in name:
        return "normal", 0.0, shape[-1] ** -0.5
    std = (1.0 / shape[-2]) ** 0.5
    if name.endswith("/kda/f_b"):
        std *= 0.25
    if name.endswith(("/attn/wq", "/attn/wk")):
        std *= (1.0 / (cfg["attention_multiplier"]
                       * math.sqrt(cfg["head_dim"]))) ** 0.5
    if name.endswith("/moe/w_down"):
        std *= math.sqrt(cfg["num_experts_per_tok"])
    return "normal", 0.0, std


def make_leaf(seed: int, name: str, shape, cfg: dict,
              dtype: str = "bfloat16"):
    """The leaf ``name`` on the device, in ``dtype``."""
    import jax

    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)),
                             zlib.crc32(name.encode()) & 0x7FFFFFFF)
    kind, p, q = leaf_rule(name, shape, cfg)
    return _maker(tuple(shape), dtype, kind)(key, p, q)


def leaves_under(seed: int, cfg: dict, prefix: str, as_float32=False):
    """{name under ``prefix``: device array} for the leaves DIRECTLY
    under ``prefix`` ('text_module/': the table, the last norm, the
    projection; 'text_module/layers_3/': that layer) — how the reference
    asks for one layer at a time."""
    import jax.numpy as jnp

    out = {}
    for name, shape in weight_shapes(cfg).items():
        if not name.startswith(prefix):
            continue
        rest = name[len(prefix):]
        if prefix == PREFIX + "/" and rest.startswith("layers_"):
            continue
        leaf = make_leaf(seed, name, shape, cfg)
        out[rest] = leaf.astype(jnp.float32) if as_float32 else leaf
    return out
