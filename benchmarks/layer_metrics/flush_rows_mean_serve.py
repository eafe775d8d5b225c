"""flush_rows_mean.serve: the mean number of rows in a batcher flush:
``rows`` of the ``batcher.flush`` records of the window."""

LAYER = "serving"
UNIT = "rows"
SOURCE = "program_span"
MOVES = "queries_per_s"


def read(run):
    rows = [e["rows"] for e in run.events
            if e.get("name") == "batcher.flush" and "rows" in e]
    return sum(rows) / len(rows) if rows else None
