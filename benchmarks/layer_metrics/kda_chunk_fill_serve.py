"""kda_chunk_fill.serve: of the (row, chunk) blocks the KDA layers' delta
rule computed, the share that held a real token: the window's sum of
``kda_chunks_real`` over its sum of ``kda_chunks_run`` on the ``dispatch``
records of site ``engine.text`` (both counted by the tower's program from
each row's length).  What a rule that skips all-pad chunks would save is
100 less this.  ``None`` where the records lack the counters."""

LAYER = "model"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "queries_per_s"
SITE = "engine.text"


def read(run):
    flushes = [e for e in run.events
               if e.get("name") == "dispatch" and e.get("site") == SITE
               and "kda_chunks_run" in e and "kda_chunks_real" in e]
    run_blocks = sum(e["kda_chunks_run"] for e in flushes)
    if not run_blocks:
        return None
    return 100.0 * sum(e["kda_chunks_real"] for e in flushes) / run_blocks
