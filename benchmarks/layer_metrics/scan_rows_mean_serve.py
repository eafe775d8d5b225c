"""scan_rows_mean.serve: the mean number of real rows that shared one pass
over the index: ``rows`` of the window's ``dispatch`` records of site
``index.topk`` (1.0 where every caller scans for itself; up to the index's
top bucket where the service's scan coalescer gathers the callers that are
waiting)."""

LAYER = "serving"
UNIT = "rows"
SOURCE = "program_span"
MOVES = "queries_per_s"
SITE = "index.topk"


def read(run):
    rows = [e["rows"] for e in run.events
            if e.get("name") == "dispatch" and e.get("site") == SITE
            and "rows" in e]
    return sum(rows) / len(rows) if rows else None
