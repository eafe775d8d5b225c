"""topk_lock_wait_ms_p50.serve: the median time a scan waited for the
dispatch lock: ``lock_wait_ms`` of the window's ``dispatch`` records of
site ``index.topk``."""

LAYER = "serving"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "query_p95_ms"
SITE = "index.topk"


def read(run):
    import statistics

    waits = [e["lock_wait_ms"] for e in run.events
             if e.get("name") == "dispatch" and e.get("site") == SITE
             and "lock_wait_ms" in e]
    return statistics.median(waits) if waits else None
