"""denoise_row_fill.serve: of the (row of the rung, pass) slots the block
loop ran, the share in which the row had work to do: the window's sum of
``gen_row_passes`` over its sum of ``gen_row_slots`` (the rung's rows x
the denoise and commit passes run) on the ``dispatch`` records of site
``engine.text``.  What is left are the rung's pad rows and the rows that
had no mask left while the block waited for its last row.  ``None`` where
the records lack the counters."""

LAYER = "serving"
UNIT = "%"
SOURCE = "program_span"
MOVES = "queries_per_s"
SITE = "engine.text"


def read(run):
    flushes = [e for e in run.events
               if e.get("name") == "dispatch" and e.get("site") == SITE
               and "gen_row_slots" in e and "gen_row_passes" in e]
    slots = sum(e["gen_row_slots"] for e in flushes)
    if not slots:
        return None
    return 100.0 * sum(e["gen_row_passes"] for e in flushes) / slots
