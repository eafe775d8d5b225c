"""batcher_wait_ms_p50.serve: the median time the oldest row of a flush sat
in the batcher's queue before the flush began: ``queue_wait_ms`` of the
``batcher.flush`` records of the window."""

LAYER = "serving"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "query_p95_ms"


def read(run):
    import statistics

    waits = [e["queue_wait_ms"] for e in run.events
             if e.get("name") == "batcher.flush" and "queue_wait_ms" in e]
    return statistics.median(waits) if waits else None
