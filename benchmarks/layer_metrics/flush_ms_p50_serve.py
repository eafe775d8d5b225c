"""flush_ms_p50.serve: the median time of a batcher flush: ``dur_ms`` of
the ``batcher.flush`` records of the window."""

LAYER = "serving"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "query_p95_ms"


def read(run):
    import statistics

    durs = [e["dur_ms"] for e in run.events
            if e.get("name") == "batcher.flush" and "dur_ms" in e]
    return statistics.median(durs) if durs else None
