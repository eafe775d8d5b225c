"""expert_tile_fill.serve: of the rows the grouped expert products were
asked to multiply, the share that were real (token, expert) pairs: the
window's sum of ``moe_pairs_held`` over its sum of ``moe_tile_rows`` (tile
visits x the tile's rows, counted by the tower's program) on the
``dispatch`` records of site ``engine.text``.  ``None`` where the records
lack the counter (a program whose product walks no tiles of its own)."""

LAYER = "kernels"
UNIT = "%"
SOURCE = "program_span"
MOVES = "queries_per_s"
SITE = "engine.text"


def read(run):
    flushes = [e for e in run.events
               if e.get("name") == "dispatch" and e.get("site") == SITE
               and "moe_tile_rows" in e and "moe_pairs_held" in e]
    rows = sum(e["moe_tile_rows"] for e in flushes)
    if not rows:
        return None
    return 100.0 * sum(e["moe_pairs_held"] for e in flushes) / rows
