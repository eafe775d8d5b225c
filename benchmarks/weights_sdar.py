"""Weights of the SDAR-30B-A3B-Chat sentence tower from the seed: the
benchmark's own, handed to the program (inside the export it serves) and to
the plain reference alike.  Nothing here comes from ``milnce_tpu``: names
are the leaves of the tower's parameter tree, shapes follow from the
configuration's published keys.

Leaf by leaf, each in the type the configuration serves in (bfloat16): one
small jitted program a leaf shape, so that no float32 copy of 4.4 B
parameters ever exists.  A leaf's values depend on the seed and its name
alone.

Scales (random weights have no training to set them; the configuration's
file repeats them under ``assumed``): the token table N(0, 1) and a matrix
N(0, 1 / fan-in), so that every product's input and output are unit scale
(a head of q and of k leaves its RMSNorm with 128 unit entries, so the
scores / sqrt(128) spread by ~1: the softmax is neither flat nor one-hot;
the head's logits are ~N(0, 1) over the vocabulary, the largest of 151,936
a confidence of ~4e-4); norm weights 1 +- 0.1; a routed expert's
down-projection times sqrt(num_experts_per_tok): a token's eight weights
sum to 1, so the routed sum adds ~0.5 to the residual stream, about what
the attention adds: it weighs in the answer, and the comparison sees it.
"""

from __future__ import annotations

import functools
import math
import zlib

PREFIX = "text_module"


def weight_shapes(cfg: dict) -> dict:
    """name -> shape for every leaf of the tower that ``cfg`` (a file
    under ``benchmarks/configs`` with the published keys at its top
    level) describes: ``num_hidden_layers`` layers, ``share.experts_held``
    experts HELD of ``num_experts`` routed over, the whole vocabulary in
    the table and in the untied head."""
    hidden, hd = cfg["hidden_size"], cfg["head_dim"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    held, width = cfg["share"]["experts_held"], cfg["moe_intermediate_size"]
    shapes = {f"{PREFIX}/embed": (cfg["vocab_size"], hidden),
              f"{PREFIX}/head": (hidden, cfg["vocab_size"]),
              f"{PREFIX}/norm": (hidden,),
              f"{PREFIX}/proj": (hidden, cfg["model"]["embedding_dim"])}
    for i in range(cfg["num_hidden_layers"]):
        p = f"{PREFIX}/layers_{i}"
        shapes.update({
            f"{p}/attn_norm": (hidden,), f"{p}/mlp_norm": (hidden,),
            f"{p}/wq": (hidden, q), f"{p}/wk": (hidden, kv),
            f"{p}/wv": (hidden, kv), f"{p}/wo": (q, hidden),
            f"{p}/q_norm": (hd,), f"{p}/k_norm": (hd,),
            f"{p}/router": (hidden, cfg["num_experts"]),
            f"{p}/w_gate": (held, hidden, width),
            f"{p}/w_up": (held, hidden, width),
            f"{p}/w_down": (held, width, hidden)})
    return shapes


def leaf_rule(name: str, shape, cfg: dict) -> tuple:
    """-> (mean, deviation) of the normal a leaf is drawn from."""
    if len(shape) == 1:
        return 1.0, 0.1
    if name.endswith("/embed"):
        return 0.0, 1.0
    std = (1.0 / shape[-2]) ** 0.5
    if name.endswith("/w_down"):
        std *= math.sqrt(cfg["num_experts_per_tok"])
    return 0.0, std


@functools.lru_cache(maxsize=None)
def _maker(shape: tuple, dtype: str):
    import jax
    import jax.numpy as jnp

    def make(key, mean, std):
        return (mean + std * jax.random.normal(key, shape, jnp.float32)
                ).astype(jnp.dtype(dtype))

    return jax.jit(make)


def make_leaf(seed: int, name: str, shape, cfg: dict,
              dtype: str = "bfloat16"):
    """The leaf ``name`` on the device, in ``dtype``."""
    import jax

    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)),
                             zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return _maker(tuple(shape), dtype)(key, *leaf_rule(name, shape, cfg))


def leaves_under(seed: int, cfg: dict, prefix: str, as_float32=False):
    """{name under ``prefix``: device array} for the leaves DIRECTLY
    under ``prefix`` ('text_module/': the table, the head, the last norm,
    the projection; 'text_module/layers_3/': that layer) — how the
    reference asks for one layer at a time."""
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
