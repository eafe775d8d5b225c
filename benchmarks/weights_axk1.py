"""Weights of the A.X-K1 sentence tower from the seed: the benchmark's own,
handed to the program (inside the export it serves) and to the plain
reference alike.  Nothing here comes from ``milnce_tpu``: names are the
leaves of the tower's parameter tree, shapes follow from the
configuration's published keys.

Leaf by leaf, each in the type the configuration serves in (bfloat16): one
small jitted program a leaf shape, so that no float32 copy of 5.4 B
parameters ever exists.  A leaf's values depend on the seed and its name
alone.

Scales (random weights have no training to set them): the token table and
every matrix's input are unit scale, a matrix is N(0, 1 / fan-in), so
every sublayer adds O(1) to the residual stream and 8 layers leave it at
~3; norm weights 1 +- 0.1; a routed expert's down-projection is scaled by
``num_experts_per_tok / routed_scaling_factor`` so that ONE weighted pair
adds about what the shared expert adds — a chip's share holds a sixteenth
of the pairs, and the routed product must weigh in the answer for the
comparison to see it.
"""

from __future__ import annotations

import functools
import zlib

PREFIX = "text_module"


def weight_shapes(cfg: dict) -> dict:
    """name -> shape for every leaf of the tower that ``cfg`` (a file
    under ``benchmarks/configs`` with the published keys at its top
    level) describes: ``num_hidden_layers`` layers, ``n_routed_experts``
    experts HELD, ``vocab_size`` rows of the table."""
    hidden, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vdim = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                        cfg["v_head_dim"])
    held, width = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    shapes = {f"{PREFIX}/embed": (cfg["vocab_size"], hidden),
              f"{PREFIX}/norm/weight": (hidden,),
              f"{PREFIX}/proj": (hidden, cfg["model"]["embedding_dim"])}
    for i in range(cfg["num_hidden_layers"]):
        p = f"{PREFIX}/layers_{i}"
        shapes.update({
            f"{p}/attn_norm/weight": (hidden,),
            f"{p}/mlp_norm/weight": (hidden,),
            f"{p}/attn/wq_a": (hidden, cfg["q_lora_rank"]),
            f"{p}/attn/q_norm/weight": (cfg["q_lora_rank"],),
            f"{p}/attn/wq_b": (cfg["q_lora_rank"], heads * (nope + rope)),
            f"{p}/attn/wkv_a": (hidden, cfg["kv_lora_rank"] + rope),
            f"{p}/attn/kv_norm/weight": (cfg["kv_lora_rank"],),
            f"{p}/attn/wkv_b": (cfg["kv_lora_rank"], heads * (nope + vdim)),
            f"{p}/attn/wo": (heads * vdim, hidden)})
        if i < cfg["first_k_dense_replace"]:
            dense = cfg["intermediate_size"]
            shapes.update({f"{p}/mlp/w_gate": (hidden, dense),
                           f"{p}/mlp/w_up": (hidden, dense),
                           f"{p}/mlp/w_down": (dense, hidden)})
            continue
        shared = width * cfg["n_shared_experts"]
        shapes.update({
            f"{p}/moe/router": (hidden, cfg["published"]["n_routed_experts"]),
            f"{p}/moe/shared/w_gate": (hidden, shared),
            f"{p}/moe/shared/w_up": (hidden, shared),
            f"{p}/moe/shared/w_down": (shared, hidden),
            f"{p}/moe/w_gate": (held, hidden, width),
            f"{p}/moe/w_up": (held, hidden, width),
            f"{p}/moe/w_down": (held, width, hidden)})
    return shapes


def leaf_scale(name: str, shape, cfg: dict):
    """-> (mean, standard deviation) of the leaf's normal values."""
    if name.endswith("/weight"):
        return 1.0, 0.1
    if name.endswith("/embed"):
        return 0.0, 1.0
    std = (1.0 / shape[-2]) ** 0.5
    if name.endswith("/moe/w_down"):
        std *= cfg["num_experts_per_tok"] / cfg["routed_scaling_factor"]
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
    mean, std = leaf_scale(name, shape, cfg)
    return _maker(tuple(shape), dtype)(key, mean, std)


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
