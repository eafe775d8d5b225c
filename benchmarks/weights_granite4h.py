"""Weights of the granite-4.0-h-small sentence tower from the seed: the
benchmark's own, handed to the program (inside the export it serves) and to
the plain reference alike.  Nothing here comes from ``milnce_tpu``: names
are the leaves of the tower's parameter tree, shapes follow from the
configuration's published keys.

Leaf by leaf, each in the type the configuration serves in (bfloat16): one
small jitted program a leaf shape, so that no float32 copy of 4.8 B
parameters ever exists.  A leaf's values depend on the seed and its name
alone.

Scales (random weights have no training to set them; the configuration's
file repeats them under ``assumed``).  The residual stream starts at unit
scale and every sublayer adds ``residual_multiplier`` x O(1) to it, so ten
layers leave it at ~1.5:

- the token table N(0, 1 / embedding_multiplier^2): the multiplier brings
  a row to unit scale;
- a matrix N(0, 1 / fan-in); norm weights and the scan's skip ``D`` 1 +-
  0.1; the conv's taps N(0, 1 / d_conv), its bias N(0, 0.1^2);
- the attention's ``wq`` and ``wk`` each times (1 / (attention_multiplier x
  sqrt(head size)))^(1/2), so that the scores' spread is ~1 and the softmax
  is neither flat nor one-hot;
- ``A_log`` = log(U(1, 16)) and ``dt_bias`` = the inverse softplus of
  exp(U(log 0.001, log 0.1)), as the family initialises them (heads whose
  memory is one position long beside heads whose memory spans chunks);
- a routed expert's down-projection times sqrt(num_experts_per_tok): a
  token's ten weights sum to 1, so one weighted pair adds ~0.3 of what
  the shared MLP adds and a chip's share (~5 pairs a token) ~0.7 of it:
  the routed product weighs in the answer, and the comparison sees it.
"""

from __future__ import annotations

import functools
import math
import zlib

PREFIX = "text_module"


def layer_kinds(cfg: dict) -> list:
    return list(cfg["layer_types"])[:cfg["num_hidden_layers"]]


def weight_shapes(cfg: dict) -> dict:
    """name -> shape for every leaf of the tower that ``cfg`` (a file
    under ``benchmarks/configs`` with the published keys at its top
    level) describes: the first ``num_hidden_layers`` of ``layer_types``,
    ``num_local_experts`` experts HELD, ``vocab_size`` rows of the table."""
    hidden = cfg["hidden_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    width = hidden // heads
    m_heads, inner = cfg["mamba_n_heads"], (cfg["mamba_n_heads"]
                                            * cfg["mamba_d_head"])
    channels = inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    held, expert = cfg["num_local_experts"], cfg["intermediate_size"]
    shared = cfg["shared_intermediate_size"]
    shapes = {f"{PREFIX}/embed": (cfg["vocab_size"], hidden),
              f"{PREFIX}/norm/weight": (hidden,),
              f"{PREFIX}/proj": (hidden, cfg["model"]["embedding_dim"])}
    for i, kind in enumerate(layer_kinds(cfg)):
        p = f"{PREFIX}/layers_{i}"
        if kind == "mamba":
            shapes.update({
                f"{p}/mamba/w_in": (hidden, inner + channels + m_heads),
                f"{p}/mamba/conv_w": (channels, cfg["mamba_d_conv"]),
                f"{p}/mamba/dt_bias": (m_heads,),
                f"{p}/mamba/A_log": (m_heads,),
                f"{p}/mamba/D": (m_heads,),
                f"{p}/mamba/norm/weight": (inner,),
                f"{p}/mamba/w_out": (inner, hidden)})
            if cfg["mamba_conv_bias"]:
                shapes[f"{p}/mamba/conv_b"] = (channels,)
        else:
            shapes.update({
                f"{p}/attn/wq": (hidden, heads * width),
                f"{p}/attn/wk": (hidden, kv * width),
                f"{p}/attn/wv": (hidden, kv * width),
                f"{p}/attn/wo": (heads * width, hidden)})
        shapes.update({
            f"{p}/mixer_norm/weight": (hidden,),
            f"{p}/mlp_norm/weight": (hidden,),
            f"{p}/shared/w_gate": (hidden, shared),
            f"{p}/shared/w_up": (hidden, shared),
            f"{p}/shared/w_down": (shared, hidden),
            f"{p}/moe/router": (hidden, cfg["published"]["num_local_experts"]),
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
    if name.endswith(("/weight", "/D")):
        return "normal", 1.0, 0.1
    if name.endswith("/embed"):
        return "normal", 0.0, 1.0 / cfg["embedding_multiplier"]
    if name.endswith("/conv_b"):
        return "normal", 0.0, 0.1
    if name.endswith("/conv_w"):
        return "normal", 0.0, shape[-1] ** -0.5
    std = (1.0 / shape[-2]) ** 0.5
    if name.endswith(("/attn/wq", "/attn/wk")):
        head = cfg["hidden_size"] // cfg["num_attention_heads"]
        std *= (1.0 / (cfg["attention_multiplier"] * math.sqrt(head))) ** 0.5
    if name.endswith("/moe/w_down"):
        std *= math.sqrt(cfg["num_experts_per_tok"])
    return "normal", 0.0, std


@functools.lru_cache(maxsize=None)
def _maker(shape: tuple, dtype: str, kind: str):
    import jax
    import jax.numpy as jnp

    def make(key, p, q):
        if kind == "normal":
            out = p + q * jax.random.normal(key, shape, jnp.float32)
        else:
            u = jax.random.uniform(key, shape, jnp.float32)
            if kind == "log_uniform":
                out = jnp.log(p + (q - p) * u)
            else:
                dt = jnp.exp(jnp.log(p) + (jnp.log(q) - jnp.log(p)) * u)
                out = dt + jnp.log(-jnp.expm1(-dt))
        return out.astype(jnp.dtype(dtype))

    return jax.jit(make)


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
