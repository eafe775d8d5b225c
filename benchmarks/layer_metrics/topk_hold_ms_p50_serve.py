"""topk_hold_ms_p50.serve: the median time a scan held the dispatch lock
(put, call and get on the host around the device's work): ``hold_ms`` of
the window's ``dispatch`` records of site ``index.topk``."""

LAYER = "serving"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "queries_per_s"
SITE = "index.topk"


def read(run):
    import statistics

    holds = [e["hold_ms"] for e in run.events
             if e.get("name") == "dispatch" and e.get("site") == SITE
             and "hold_ms" in e]
    return statistics.median(holds) if holds else None
