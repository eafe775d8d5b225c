"""worker_scatter_us_per_row.serve: what handing one row's result back
costs the device worker: 1000 x the window's sum of ``scatter_ms`` of its
``worker.turn`` records over its sum of their ``rows`` (``set_result`` on
every request and its done-callbacks on the worker's thread: the cache's
put, the stack of a call's embeddings, the hand-over to the scan queue,
the wake of the caller)."""

LAYER = "serving"
UNIT = "us"
SOURCE = "program_span"
MOVES = "queries_per_s"


def read(run):
    from benchmarks.layer_metrics.worker_host_ms_p50_serve import turns

    records = turns(run)
    rows = sum(e["rows"] for e in records)
    if not rows:
        return None
    return 1e3 * sum(e["scatter_ms"] for e in records) / rows
