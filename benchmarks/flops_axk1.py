"""Operations and bytes of the A.X-K1 sentence tower, from shapes: what the
algorithm needs, whatever implements it.  ``cfg`` is the configuration's
file (published keys at the top level; ``num_hidden_layers`` layers here,
``n_routed_experts`` experts held, ``vocab_size`` rows of the table).

Matrix products only (2 x inputs x outputs a token, and the attention's
two products over the positions a token sees); norms, RoPE, softmax,
sigmoid and top-k are not counted.  The routed experts cost what the
(token, expert) pairs that meet a held expert cost; nothing is counted
for a pad.
"""

from __future__ import annotations


def mla_params(cfg: dict) -> int:
    heads = cfg["num_attention_heads"]
    nope, rope, vdim = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                        cfg["v_head_dim"])
    hidden = cfg["hidden_size"]
    return (hidden * cfg["q_lora_rank"]
            + cfg["q_lora_rank"] * heads * (nope + rope)
            + hidden * (cfg["kv_lora_rank"] + rope)
            + cfg["kv_lora_rank"] * heads * (nope + vdim)
            + heads * vdim * hidden)


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_counts(cfg: dict) -> tuple:
    """-> (dense layers, expert layers) of the cut."""
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return dense, cfg["num_hidden_layers"] - dense


def expected_pairs_per_token(cfg: dict) -> float:
    """(token, expert) pairs that meet a held expert, a token a layer,
    under uniform routing: k x held / routed."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["published"]["n_routed_experts"])


def tower_flops(cfg: dict, tokens: float, rows: float,
                pairs_held: float | None = None) -> float:
    """One execution over ``tokens`` real tokens in ``rows`` rows.
    ``pairs_held``: pairs that met a held expert, summed over the expert
    layers (None: the uniform expectation).  A token sees the positions
    up to its own; rows are taken as equally long."""
    dense, moe = layer_counts(cfg)
    hidden = cfg["hidden_size"]
    if pairs_held is None:
        pairs_held = tokens * moe * expected_pairs_per_token(cfg)
    attended = tokens * ((tokens / rows if rows else 0.0) + 1.0) / 2.0
    heads = cfg["num_attention_heads"]
    per_pair = heads * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                        + cfg["v_head_dim"])
    shared = cfg["n_shared_experts"] * expert_params(cfg)
    per_token = ((dense + moe) * mla_params(cfg)
                 + dense * 3 * hidden * cfg["intermediate_size"]
                 + moe * (hidden * cfg["published"]["n_routed_experts"]
                          + shared))
    return (2.0 * tokens * per_token
            + 2.0 * attended * per_pair * (dense + moe)
            + 2.0 * pairs_held * expert_params(cfg)
            + 2.0 * rows * hidden * cfg["model"]["embedding_dim"])


def tower_bytes(cfg: dict, tokens: float, bytes_per_param: int = 2) -> float:
    """One execution reads every held layer weight once and the table's
    rows of its real tokens; activations are not counted."""
    dense, moe = layer_counts(cfg)
    hidden = cfg["hidden_size"]
    params = ((dense + moe) * mla_params(cfg)
              + dense * 3 * hidden * cfg["intermediate_size"]
              + moe * (hidden * cfg["published"]["n_routed_experts"]
                       + (cfg["n_shared_experts"] + cfg["n_routed_experts"])
                       * expert_params(cfg))
              + hidden * cfg["model"]["embedding_dim"])
    return bytes_per_param * (params + tokens * hidden)


def tower_work(cfg: dict, tokens: float, rows: float,
               pairs_held: float | None = None) -> dict:
    return {"flops": tower_flops(cfg, tokens, rows, pairs_held),
            "bytes": tower_bytes(cfg, tokens)}
