"""hybrid_tower_roofline: ``text_tower_roofline``'s rule for the hybrid
sentence tower's program: over the text flushes of the TRACED window, the
sum of the least times their executions could take over the sum of the
device times those executions took.  A flush's least time: the larger of
the tower's held weights' bytes over peak bytes/s and its real tokens'
FLOPs over peak FLOP/s, from the shapes (``benchmarks/flops_granite4h.py``)
and the ``tokens`` / ``rows`` / ``moe_pairs_held`` of its ``dispatch``
record of site ``engine.text``.  Numerator and denominator are taken over
the same flushes, whatever rungs they ran at."""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "queries_per_s"

TOWER_MODULE = "text_hybrid_tower"      # jit name, train/step.py


def read(run):
    from benchmarks import flops, flops_granite4h
    from benchmarks.layer_metrics.ssd_scan_roofline import traced_flushes

    if run.trace is None:
        return None
    device_s = sum(d for name, ds in run.trace.module_seconds.items()
                   if TOWER_MODULE in name for d in ds)
    flushes = traced_flushes(run, "moe_pairs_held")
    if not device_s or not flushes:
        return None
    least_s = sum(flops.least_time_s(flops_granite4h.tower_work(
        run.cell.config, e["tokens"], e["rows"], e["moe_pairs_held"]),
        run.peaks)[0] for e in flushes)
    return 100.0 * least_s / device_s
