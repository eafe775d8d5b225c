"""Operations and bytes of the SDAR-30B-A3B-Chat sentence tower, from
shapes and from what a flush's record counts: what the algorithm needs,
whatever implements it.  ``cfg`` is the configuration's file (published
keys at the top level; ``num_hidden_layers`` layers here, ``share
.experts_held`` of ``num_experts`` experts held, the whole vocabulary in
the table and the head; the generation's settings in its ``text_dlm``
group).

A flush is one prefill pass over the queries' whole blocks, then for each
written block its denoise passes and one commit pass, each over the
block's L positions of every row at work (``milnce_tpu/models/text_dlm
.py``).  Matrix products are counted; norms, softmax, the rotary position,
gates, top-k and the argmax are not.  The routed experts cost what the
(token, expert) pairs that meet a held expert cost; nothing is counted for
a pad, for a row without a mask left, or for a position of the cache that
no row at work reads.

BYTES are of what a pass MUST read: the matrices of the experts that got a
pair (not of those held), the attention's and the router's matrices once a
pass, the head once a pass that makes logits, the table's rows of the
tokens it embeds, the cache positions of earlier blocks.  Nothing twice.
"""

from __future__ import annotations

BYTES = 2       # bfloat16: parameters and cache


def settings(cfg: dict) -> dict:
    return cfg["text_dlm"]


def attention_params(cfg: dict) -> int:
    hidden, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return hidden * (q + 2 * kv) + q * hidden


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["num_experts"]


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def cache_bytes_per_position(cfg: dict) -> int:
    """Keys and values of one position, over the layers."""
    return (cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"]
            * cfg["head_dim"] * BYTES)


def expected_pairs_per_token(cfg: dict) -> float:
    """Pairs that meet a held expert, a token a layer, under uniform
    routing."""
    return (cfg["num_experts_per_tok"] * cfg["share"]["experts_held"]
            / cfg["num_experts"])


def work(cfg: dict, *, tokens: float, pairs_held: float, attended: float,
         logit_positions: float, passes: float, head_passes: float,
         experts_touched: float, cache_positions: float,
         rows: float) -> dict:
    """FLOPs and bytes of some passes, from their sums: ``tokens`` real
    positions put through the layers, ``pairs_held`` pairs (over the
    layers), ``attended`` (query position, key position) pairs a layer,
    ``logit_positions`` positions the head is asked for, ``passes`` passes
    through the layers, ``head_passes`` of them with logits,
    ``experts_touched`` experts that got a pair (over layers and passes),
    ``cache_positions`` positions of earlier blocks read (over rows and
    passes), ``rows`` embeddings made."""
    layers, hidden = cfg["num_hidden_layers"], cfg["hidden_size"]
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    per_pass = layers * (attention_params(cfg) + router_params(cfg))
    flops = (2.0 * tokens * per_pass + 2.0 * pairs_held * expert_params(cfg)
             + 4.0 * attended * width * layers
             + 2.0 * logit_positions * head_params(cfg)
             + 2.0 * rows * hidden * cfg["model"]["embedding_dim"])
    read = (BYTES * (experts_touched * expert_params(cfg)
                     + passes * per_pass + head_passes * head_params(cfg)
                     + tokens * hidden)
            + cache_positions * cache_bytes_per_position(cfg))
    return {"flops": flops, "bytes": float(read)}


def flush_work(cfg: dict, record: dict) -> dict:
    """One flush from its ``dispatch`` record of site ``engine.text``
    (``rows``, ``moe_pairs_total``, ``moe_pairs_held``,
    ``moe_experts_touched``, ``kv_positions`` and the ``gen_*`` counters
    of ``models/text_dlm.py``).  The prefill's attention is reckoned at
    rows equally long; the head at the L positions of every row at work in
    a denoise pass."""
    span = settings(cfg)["block_length"]
    layers, k = cfg["num_hidden_layers"], cfg["num_experts_per_tok"]
    rows = max(1, record["rows"])
    denoise, commit = (record["gen_passes_denoise"],
                       record["gen_passes_commit"])
    tokens = record["moe_pairs_total"] / (k * layers)
    loop_tokens = span * record["gen_row_passes"]
    prefill = max(0.0, tokens - loop_tokens)
    denoise_rows = max(0, record["gen_row_passes"] - rows * commit)
    attended = (prefill * (prefill / rows + span) / 2.0
                + span * record["kv_positions"] + span * loop_tokens)
    return work(cfg, tokens=tokens, pairs_held=record["moe_pairs_held"],
                attended=attended, logit_positions=span * denoise_rows,
                passes=1 + denoise + commit, head_passes=denoise,
                experts_touched=record["moe_experts_touched"],
                cache_positions=record["kv_positions"], rows=rows)


def query_passes(cfg: dict, tokens: float) -> dict:
    """One query of ``tokens`` tokens (a mean: the tokens its last block
    carries into the first written block, n mod L, are taken as uniform
    over 0..L-1) under the rule's fall-back, ``L / denoising_steps``
    positions a pass: what its row adds to a flush."""
    s = settings(cfg)
    span, blocks = s["block_length"], s["expand_blocks"]
    least = span // s["denoising_steps"]

    def block(masks: int) -> tuple:
        """-> (denoise passes, masked positions summed over them)."""
        left = range(masks, 0, -least)
        return len(left), sum(left)

    whole, whole_masked = block(span)
    firsts = [block(span - carried) for carried in range(span)]
    first = sum(p for p, _ in firsts) / span
    prefill = max(0.0, tokens - (span - 1) / 2.0)
    # positions of earlier blocks a pass reads: the prefix, then a block
    # more for each block written
    cache = sum((prefill + b * span) * ((first if b == 0 else whole) + 1)
                for b in range(blocks))
    denoise = first + (blocks - 1) * whole
    return {"prefill": prefill, "denoise": denoise,
            "row_passes": denoise + blocks, "cache": cache,
            "masked": (sum(m for _, m in firsts) / span
                       + (blocks - 1) * whole_masked)}


def tower_flops(cfg: dict, tokens: float, rows: float,
                pairs_held: float | None = None) -> float:
    """``rows`` queries of ``tokens`` real tokens in all, through every
    pass of their expansion (:func:`query_passes`): the work of a query
    that the window's rate is multiplied by (``query_mfu``)."""
    span = settings(cfg)["block_length"]
    one = query_passes(cfg, tokens / rows if rows else 0.0)
    through = rows * (one["prefill"] + span * one["row_passes"])
    if pairs_held is None:
        pairs_held = (through * cfg["num_hidden_layers"]
                      * expected_pairs_per_token(cfg))
    attended = rows * (one["prefill"] * (one["prefill"] + span) / 2.0
                       + span * one["cache"]
                       + span * span * one["row_passes"])
    return work(cfg, tokens=through, pairs_held=pairs_held,
                attended=attended, logit_positions=rows * one["masked"],
                passes=0, head_passes=0, experts_touched=0,
                cache_positions=0, rows=rows)["flops"]


def tower_params(cfg: dict) -> int:
    """Every held matrix (norms left out)."""
    return (cfg["num_hidden_layers"]
            * (attention_params(cfg) + router_params(cfg)
               + cfg["share"]["experts_held"] * expert_params(cfg))
            + 2 * head_params(cfg)
            + cfg["hidden_size"] * cfg["model"]["embedding_dim"])
