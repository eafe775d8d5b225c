"""worker_cpu_share.serve: how much of one core the one thread that drives
the chip spends in Python and in the runtime's host code: the sum of every
phase's ``_cpu_ms`` of the window's ``worker.turn`` records over the
window.  What is left of 100% less the worker's ``sleep`` share is
waiting: for the device inside ``run``, for the interpreter elsewhere."""

LAYER = "serving"
UNIT = "%"
SOURCE = "program_span"
MOVES = "queries_per_s"


def read(run):
    from benchmarks.layer_metrics.worker_host_ms_p50_serve import turns

    records = turns(run)
    if not records or not run.window_s:
        return None
    cpu_ms = sum(v for e in records for k, v in e.items()
                 if k.endswith("_cpu_ms"))
    return 100.0 * cpu_ms / (run.window_s * 1e3)
