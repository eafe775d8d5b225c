"""hybrid_expert_tokens_mean.serve: what each held expert's product is
fed, a flush: ``moe_pairs_held`` of the window's ``dispatch`` records of
site ``engine.text`` over (experts held x layers: every layer of the
hybrid tower has experts), their mean."""

LAYER = "model"
UNIT = "tokens"
SOURCE = "program_span"
MOVES = "queries_per_s"
SITE = "engine.text"


def read(run):
    pairs = [e["moe_pairs_held"] for e in run.events
             if e.get("name") == "dispatch" and e.get("site") == SITE
             and "moe_pairs_held" in e]
    cfg = run.cell.config
    if not pairs or "num_local_experts" not in cfg:
        return None
    return sum(pairs) / len(pairs) / (cfg["num_local_experts"]
                                      * cfg["num_hidden_layers"])
