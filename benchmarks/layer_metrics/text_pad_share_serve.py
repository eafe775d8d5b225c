"""text_pad_share.serve: the share of the text flushes' token slots that
held no real token: ``pad_tokens / (tokens + pad_tokens)`` summed over the
window's ``dispatch`` records of site ``engine.text`` (a slot: one
position of one row of the bucket the flush was padded to) — what a
token-length axis in the ladder, or packed rows, could save."""

LAYER = "serving"
UNIT = "%"
SOURCE = "program_span"
MOVES = "queries_per_s"
SITE = "engine.text"


def read(run):
    flushes = [e for e in run.events
               if e.get("name") == "dispatch" and e.get("site") == SITE
               and "tokens" in e and "pad_tokens" in e]
    slots = sum(e["tokens"] + e["pad_tokens"] for e in flushes)
    if not slots:
        return None
    return 100.0 * sum(e["pad_tokens"] for e in flushes) / slots
