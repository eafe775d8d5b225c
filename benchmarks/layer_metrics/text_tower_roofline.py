"""text_tower_roofline: over the text flushes of the TRACED window, the sum
of the least times their executions of the sentence tower's program could
take over the sum of the device times those executions took.  A flush's
least time: the larger of the tower's held weights' bytes over peak bytes/s
and its real tokens' FLOPs over peak FLOP/s, from the shapes
(``benchmarks/flops_axk1.py``) and the ``tokens`` / ``moe_pairs_held`` of
its ``dispatch`` record of site ``engine.text``; under ~770 real tokens a
flush the bytes bound holds.  Numerator and denominator are taken over the
same flushes (the records whose hold overlaps the traced window, the
executions the trace saw there), whatever rungs they ran at: a share of
the time spent, not of one rung's median."""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "queries_per_s"

TOWER_MODULE = "text_lm_tower"      # jit name, train/step.py
SITE = "engine.text"


def read(run):
    from benchmarks import flops, flops_axk1

    window = run.extra.get("trace_window")
    if run.trace is None or not window:
        return None
    device_s = sum(d for name, ds in run.trace.module_seconds.items()
                   if TOWER_MODULE in name for d in ds)
    t_from, t_to = window
    flushes = [e for e in run.events
               if e.get("name") == "dispatch" and e.get("site") == SITE
               and "tokens" in e and "moe_pairs_held" in e
               and e["mono"] > t_from
               and e["mono"] - e.get("hold_ms", 0.0) * 1e-3 < t_to]
    if not device_s or not flushes:
        return None
    least_s = sum(flops.least_time_s(flops_axk1.tower_work(
        run.cell.config, e["tokens"], e["rows"], e["moe_pairs_held"]),
        run.peaks)[0] for e in flushes)
    return 100.0 * least_s / device_s
