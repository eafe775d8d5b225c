"""denoise_tokens_per_pass.serve: tokens written a forward pass of a row:
the window's sum of ``gen_tokens`` (positions committed) over its sum of
``gen_row_passes`` ((real row, pass) pairs that did the row's work: its
denoise passes while it had a mask, and the commit pass of each block) on
the ``dispatch`` records of site ``engine.text`` (counted by the tower's
program).  1.0 is one token a forward (an autoregressive decoder with a
cache); a rule that commits one position a pass and then spends a commit
pass a block of 4 reads 0.8 and less (the query's last block is already
partly written).  ``None`` where the records lack the counters."""

LAYER = "model"
UNIT = "tokens"
SOURCE = "program_span"
MOVES = "queries_per_s"
SITE = "engine.text"


def read(run):
    flushes = [e for e in run.events
               if e.get("name") == "dispatch" and e.get("site") == SITE
               and "gen_tokens" in e and "gen_row_passes" in e]
    passes = sum(e["gen_row_passes"] for e in flushes)
    if not passes:
        return None
    return sum(e["gen_tokens"] for e in flushes) / passes
