"""worker_host_ms_p50.serve: the device worker's host time a turn outside
the program it runs: the median over the window's ``worker.turn`` records
of ``take_ms + prepare_ms + scatter_ms + account_ms`` (one record per
flush or pass the worker drove; ``run_ms`` is the program and the code
around its hold, ``sleep_ms`` the wait for work)."""

LAYER = "serving"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "queries_per_s"

HOST_PHASES = ("take", "prepare", "scatter", "account")


def turns(run):
    """The window's ``worker.turn`` records (none on a program without
    them)."""
    return [e for e in run.events
            if e.get("name") == "worker.turn" and "scatter_ms" in e]


def read(run):
    import statistics

    host = [sum(e[p + "_ms"] for p in HOST_PHASES) for e in turns(run)]
    return statistics.median(host) if host else None
