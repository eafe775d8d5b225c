"""flush_chained_share.serve: the share of the rows the text batcher
flushed that were ranked by the pass immediately after their flush, no
other flush between: the window's sum of ``chained_rows`` of the
``topk.flush`` records (the rows of a pass that the flush right before it
embedded) over the sum of ``rows`` of its ``batcher.flush`` records.
``topk.flush`` records without the attribute (a program in which the
caller carries its row from the tower to the scan) read 0."""

LAYER = "serving"
UNIT = "%"
SOURCE = "program_span"
MOVES = "queries_per_s"


def read(run):
    flushed = sum(e["rows"] for e in run.events
                  if e.get("name") == "batcher.flush" and "rows" in e)
    if not flushed:
        return None
    chained = sum(e.get("chained_rows", 0) for e in run.events
                  if e.get("name") == "topk.flush")
    return 100.0 * chained / flushed
