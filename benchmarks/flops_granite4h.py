"""Operations and bytes of the granite-4.0-h-small sentence tower, from
shapes: what the algorithm needs, whatever implements it.  ``cfg`` is the
configuration's file (published keys at the top level; the first
``num_hidden_layers`` of ``layer_types`` here, ``num_local_experts`` experts
held, ``vocab_size`` rows of the table).

Matrix products, the conv's taps and the state-space scan are counted;
norms, softmax, softplus, gates and top-k are not.  The routed experts cost
what the (token, expert) pairs that meet a held expert cost; nothing is
counted for a pad.  Rows are taken as equally long.
"""

from __future__ import annotations


def layer_counts(cfg: dict) -> tuple:
    """-> (Mamba layers, attention layers) of the cut."""
    kinds = list(cfg["layer_types"])[:cfg["num_hidden_layers"]]
    return kinds.count("mamba"), kinds.count("attention")


def inner_size(cfg: dict) -> int:
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"]


def mamba_params(cfg: dict) -> int:
    """The Mamba mixer's two projections."""
    inner = inner_size(cfg)
    fused = (2 * inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
             + cfg["mamba_n_heads"])
    return cfg["hidden_size"] * fused + inner * cfg["hidden_size"]


def attention_params(cfg: dict) -> int:
    hidden = cfg["hidden_size"]
    kv = (hidden // cfg["num_attention_heads"]) * cfg["num_key_value_heads"]
    return 2 * hidden * hidden + 2 * hidden * kv


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def shared_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["shared_intermediate_size"]


def expected_pairs_per_token(cfg: dict) -> float:
    """(token, expert) pairs that meet a held expert, a token a layer,
    under uniform routing: k x held / routed."""
    return (cfg["num_experts_per_tok"] * cfg["num_local_experts"]
            / cfg["published"]["num_local_experts"])


def pairs_in_chunks(length: float, chunk: int) -> float:
    """(t, s) pairs with s <= t inside one chunk, over a row of ``length``
    positions: what the intra-chunk quadratic form multiplies."""
    whole, rest = divmod(length, chunk)
    return whole * chunk * (chunk + 1) / 2.0 + rest * (rest + 1) / 2.0


def scan_flops(cfg: dict, tokens: float, rows: float) -> float:
    """One Mamba layer's scan over ``tokens`` real tokens in ``rows`` rows:
    the state's update and read-out, 3 x 2 x (d_state x d_inner) a token
    (decay, outer product, read-out), and the intra-chunk form at the real
    length, (2 d_state + 2 d_inner) a causal pair inside a chunk."""
    inner, n = inner_size(cfg), cfg["mamba_d_state"]
    pairs = rows * pairs_in_chunks(tokens / rows if rows else 0.0,
                                   cfg["mamba_chunk_size"])
    return 6.0 * n * inner * tokens + (2.0 * n + 2.0 * inner) * pairs


def scan_bytes(cfg: dict, slots: float, bytes_per_value: int = 2) -> float:
    """One Mamba layer's scan must read x, B, C, dt and write y at every
    slot of the rung (bfloat16)."""
    per_slot = (2 * inner_size(cfg) + 2 * cfg["mamba_d_state"]
                + cfg["mamba_n_heads"])
    return float(bytes_per_value * per_slot * slots)


def scan_work(cfg: dict, tokens: float, rows: float, slots: float) -> dict:
    """All the Mamba layers' scans of one execution."""
    layers, _ = layer_counts(cfg)
    return {"flops": layers * scan_flops(cfg, tokens, rows),
            "bytes": layers * scan_bytes(cfg, slots)}


def tower_flops(cfg: dict, tokens: float, rows: float,
                pairs_held: float | None = None) -> float:
    """One execution over ``tokens`` real tokens in ``rows`` rows.
    ``pairs_held``: pairs that met a held expert, summed over the layers
    (None: the uniform expectation).  A token sees the positions up to its
    own."""
    mamba, attn = layer_counts(cfg)
    layers, hidden = mamba + attn, cfg["hidden_size"]
    if pairs_held is None:
        pairs_held = tokens * layers * expected_pairs_per_token(cfg)
    attended = tokens * ((tokens / rows if rows else 0.0) + 1.0) / 2.0
    conv = cfg["mamba_d_conv"] * (inner_size(cfg) + 2 * cfg["mamba_n_groups"]
                                  * cfg["mamba_d_state"])
    per_token = (mamba * (mamba_params(cfg) + conv)
                 + attn * attention_params(cfg)
                 + layers * (shared_params(cfg) + hidden
                             * cfg["published"]["num_local_experts"]))
    return (2.0 * tokens * per_token
            + mamba * scan_flops(cfg, tokens, rows)
            + attn * 4.0 * attended * hidden
            + 2.0 * pairs_held * expert_params(cfg)
            + 2.0 * rows * hidden * cfg["model"]["embedding_dim"])


def tower_params(cfg: dict) -> int:
    """Every held matrix (norms, conv and the scan's vectors left out)."""
    mamba, attn = layer_counts(cfg)
    hidden = cfg["hidden_size"]
    return (mamba * mamba_params(cfg) + attn * attention_params(cfg)
            + (mamba + attn) * (shared_params(cfg)
                                + hidden * cfg["published"]["num_local_experts"]
                                + cfg["num_local_experts"] * expert_params(cfg))
            + hidden * cfg["model"]["embedding_dim"])


def tower_bytes(cfg: dict, tokens: float, bytes_per_param: int = 2) -> float:
    """One execution reads every held layer weight once and the table's
    rows of its real tokens; activations are not counted."""
    return bytes_per_param * (tower_params(cfg) + tokens * cfg["hidden_size"])


def tower_work(cfg: dict, tokens: float, rows: float,
               pairs_held: float | None = None) -> dict:
    return {"flops": tower_flops(cfg, tokens, rows, pairs_held),
            "bytes": tower_bytes(cfg, tokens)}
