"""worker_interp_wait_share.serve: of the device worker's own Python
between two programs, the share in which it did not run: over the
``take`` / ``prepare`` / ``scatter`` / ``account`` phases of the window's
``worker.turn`` records, the sum of (wall - CPU) over the sum of wall.
Those phases are pure Python, so wall less ``time.thread_time`` is the
worker standing runnable, or blocked on a lock, while another thread has
the interpreter (or no core is free): only fewer or shorter turns of the
other threads take it back; the CPU part, only less code."""

LAYER = "serving"
UNIT = "%"
SOURCE = "program_span"
MOVES = "queries_per_s"


def read(run):
    from benchmarks.layer_metrics.worker_host_ms_p50_serve import (
        HOST_PHASES, turns)

    records = turns(run)
    wall = sum(e[p + "_ms"] for e in records for p in HOST_PHASES)
    if not wall:
        return None
    cpu = sum(e[p + "_cpu_ms"] for e in records for p in HOST_PHASES)
    return 100.0 * max(0.0, wall - cpu) / wall
