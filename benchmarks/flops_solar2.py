"""Operations and bytes of the Solar-Open2-250B sentence tower, from shapes:
what the algorithm needs, whatever implements it.  ``cfg`` is the
configuration's file (published keys at the top level; the first
``num_hidden_layers`` of ``layer_types`` here, ``n_routed_experts`` experts
held, ``vocab_size`` rows of the table).

Matrix products, the conv's taps and the delta rule are counted; norms,
softmax, the gates' and decays' elementwise work and top-k are not.  The
routed experts cost what the (token, expert) pairs that meet a held expert
cost; nothing is counted for a pad.  Rows are taken as equally long.

The delta rule (``kda``'s, one chunk of ``C`` positions a head, keys K and
values V wide) is counted in its chunked form, which is what a chunk of real
tokens asks of any implementation that takes chunks: the decay-weighted
K x K products of the pairs inside the chunk (``A``: C(C-1)/2 pairs, ``P``:
C(C+1)/2), the triangular solve against the keys and the values, the
entering state's part (U = U~ - W S, the read-out's (Q o G) S), ``P U``, and
the state's update (K^T U): 2 FLOPs a multiply-add.  Its bytes: q, k, v
(bfloat16) and the decay g and beta (float32) in, o out (bfloat16) at every
position of the chunk, and one float32 state a chunk boundary.
"""

from __future__ import annotations


def layer_counts(cfg: dict) -> tuple:
    """-> (KDA layers, attention layers) of the cut."""
    kinds = list(cfg["layer_types"])[:cfg["num_hidden_layers"]]
    return kinds.count("kda"), kinds.count("attention")


def kda_sizes(cfg: dict) -> tuple:
    """-> (heads, key width, value width, chunk)."""
    lin = cfg["linear_attn_config"]
    return (lin["num_heads"], lin["head_dim"], lin["head_dim"],
            cfg["kda_chunk_size"])


def kda_params(cfg: dict) -> int:
    """The KDA mixer's projections: q, k, v and o, beta, and the decay's
    and the gate's low-rank pairs."""
    heads, dk, _dv, _c = kda_sizes(cfg)
    hidden, inner, rank = cfg["hidden_size"], heads * dk, cfg["kda_proj_rank"]
    return (4 * hidden * inner + hidden * heads
            + 2 * (hidden * rank + rank * inner))


def attention_params(cfg: dict) -> int:
    """q, the output gate and o at ``num_attention_heads`` heads of
    ``head_dim``; k and v at ``num_key_value_heads``."""
    hidden, width = cfg["hidden_size"], cfg["head_dim"]
    return (3 * hidden * cfg["num_attention_heads"] * width
            + 2 * hidden * cfg["num_key_value_heads"] * width)


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg: dict) -> int:
    return expert_params(cfg) * cfg["n_shared_experts"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["published"]["n_routed_experts"]


def expected_pairs_per_token(cfg: dict) -> float:
    """(token, expert) pairs that meet a held expert, a token a layer,
    under uniform routing: k x held / routed."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["published"]["n_routed_experts"])


def chunk_flops(cfg: dict) -> float:
    """The delta rule over one chunk of one head (the module docstring)."""
    _h, dk, dv, c = kda_sizes(cfg)
    lower, upper = c * (c - 1) / 2.0, c * (c + 1) / 2.0
    return 2.0 * (lower * dk            # A
                  + upper * dk          # P
                  + lower * (dk + dv)   # the solve for W and U~
                  + c * dk * dv         # U = U~ - W S
                  + c * dk * dv         # (Q o G) S
                  + upper * dv          # P U
                  + c * dk * dv)        # the state's update


def chunk_bytes(cfg: dict, bytes_per_value: int = 2) -> float:
    """What one chunk of one head must read and write (the docstring)."""
    _h, dk, dv, c = kda_sizes(cfg)
    per_position = bytes_per_value * (2 * dk + 2 * dv) + 4 * dk + 4
    return float(c * per_position + 4 * dk * dv)


def scan_work(cfg: dict, real_chunks: float) -> dict:
    """The delta rule over ``real_chunks`` (row x chunk blocks holding a
    real token, summed over the KDA layers: ``kda_chunks_real``) — the
    chunks of pads left out, whatever computed them."""
    heads = kda_sizes(cfg)[0]
    return {"flops": real_chunks * heads * chunk_flops(cfg),
            "bytes": real_chunks * heads * chunk_bytes(cfg)}


def tower_flops(cfg: dict, tokens: float, rows: float,
                pairs_held: float | None = None) -> float:
    """One execution over ``tokens`` real tokens in ``rows`` rows.
    ``pairs_held``: pairs that met a held expert, summed over the layers
    (None: the uniform expectation).  A token sees the positions up to its
    own; the delta rule is counted a real token (its chunks' share)."""
    kda, attn = layer_counts(cfg)
    layers, hidden = kda + attn, cfg["hidden_size"]
    heads, dk, dv, c = kda_sizes(cfg)
    if pairs_held is None:
        pairs_held = tokens * layers * expected_pairs_per_token(cfg)
    attended = tokens * ((tokens / rows if rows else 0.0) + 1.0) / 2.0
    conv = cfg["linear_attn_config"]["short_conv_kernel_size"] * (
        heads * (2 * dk + dv))
    per_token = (kda * (kda_params(cfg) + conv)
                 + attn * attention_params(cfg)
                 + layers * (shared_params(cfg) + router_params(cfg)))
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    return (2.0 * tokens * per_token
            + kda * tokens / c * heads * chunk_flops(cfg)
            + attn * 4.0 * attended * width
            + 2.0 * pairs_held * expert_params(cfg)
            + 2.0 * rows * hidden * cfg["model"]["embedding_dim"])
