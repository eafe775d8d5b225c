"""kda_scan_roofline: the least time the KDA layers' delta rule could take
over the device time of the operations under the scope
``text_hybrid/kda_scan``, both inside the TRACED window.  A flush's least
time: the larger of the rule's FLOPs at the chunks that hold a real token
(``kda_chunks_real`` of its ``dispatch`` record of site ``engine.text``)
over peak FLOP/s and the bytes it must move there (q, k, v, g and beta in,
o out, one state a chunk boundary) over peak bytes/s — from the shapes
(``benchmarks/flops_solar2.py``), whatever implements the rule.

``dlm_tower_roofline``'s rule for the window's edges: a flush counts by the
share of its hold that lies inside the window, and the device time is that
of the scope's operations inside it (``run.extra["scope_seconds"]
["inside"]``, ``benchmarks/scope_times.py``).  ``None`` where the records
lack the counter or the run has no such scope."""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "queries_per_s"

SCOPE = "text_hybrid/kda_scan"


def read(run):
    from benchmarks import flops, flops_solar2
    from benchmarks.layer_metrics.dlm_tower_roofline import flushes_inside

    scopes = run.extra.get("scope_seconds")
    flushes = flushes_inside(run, "kda_chunks_real")
    if not scopes or not scopes["inside"].get(SCOPE) or not flushes:
        return None
    least_s = sum(share * flops.least_time_s(flops_solar2.scan_work(
        run.cell.config, e["kda_chunks_real"]), run.peaks)[0]
        for e, share in flushes)
    return 100.0 * least_s / scopes["inside"][SCOPE]
