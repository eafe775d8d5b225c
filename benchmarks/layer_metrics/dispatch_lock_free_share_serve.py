"""dispatch_lock_free_share.serve: the share of the window in which nobody
held a dispatch lock: 100 x (1 - held / window), ``held`` being the union
of the holds of the window's ``dispatch`` records.  A hold ends where its
record does (``mono``, stamped right after the release) and lasts
``hold_ms``.  Under the process-wide lock no two holds overlap and
the union is their sum; engines with a lock each (a replica pool) overlap,
and a stretch two of them hold counts once."""

LAYER = "serving"
UNIT = "%"
SOURCE = "program_span"
MOVES = "queries_per_s"


def read(run):
    holds = sorted((e["mono"] - e["hold_ms"] / 1e3, e["mono"])
                   for e in run.events
                   if e.get("name") == "dispatch" and "hold_ms" in e)
    if not holds or not run.window_s:
        return None
    held, reach = 0.0, holds[0][0]
    for start, end in holds:
        if end > reach:
            held += end - max(start, reach)
            reach = end
    return 100.0 * (1.0 - held / run.window_s)
