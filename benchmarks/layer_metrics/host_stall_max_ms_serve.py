"""host_stall_max_ms.serve: the longest the interpreter (or the whole
process) kept a thread waiting in the window: the largest ``late_max_ms``
of the window's ``runtime.beat`` records (the latest of the runtime
watcher's 20 ms wake-ups; one of 50 ms or more is also a ``runtime.stall``
record with the process's CPU time over it)."""

LAYER = "serving"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "query_p95_ms"


def read(run):
    from benchmarks.layer_metrics.interp_wait_ms_mean_serve import beats

    records = beats(run)
    return max(e["late_max_ms"] for e in records) if records else None
