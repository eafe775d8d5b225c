"""dlm_tower_roofline: ``hybrid_tower_roofline``'s rule for the
block-diffusion sentence tower's program: over the text flushes of the
TRACED window, the sum of the least times their executions could take over
the device time of the program's operations there.  A flush's least time:
the larger of the bytes its passes must read (the matrices of the experts
that got a pair, the attention's and the router's a pass, the head a pass
that makes logits, the cache positions of earlier blocks) over peak
bytes/s and its real tokens' FLOPs over peak FLOP/s, from the shapes
(``benchmarks/flops_sdar.py``) and the counters of its ``dispatch`` record
of site ``engine.text`` (``gen_passes_*``, ``gen_row_passes``,
``moe_experts_touched``, ``moe_pairs_*``, ``kv_positions``).  The bound is
taken over the flush's sums: a lower bound of the sum over its passes, and
equal to it while every pass is bound the same way (by bytes, here).

A flush is 0.25 s and the traced window holds a dozen, so the window's
edges weigh: a flush counts by the share of its hold that lies INSIDE the
window (:func:`flushes_inside`), and the device time is that of the
program's operations inside it (``run.extra["scope_seconds"]["inside"]
["*"]``, ``benchmarks/scope_times.py``): the same stretch above and
below, whatever rungs ran in it."""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "queries_per_s"

SITE = "engine.text"


def flushes_inside(run, counter: str) -> list:
    """[(record, share of its hold inside the traced window), ...] for the
    ``engine.text`` flush records that carry ``counter`` and whose hold
    overlaps the window."""
    window = run.extra.get("trace_window")
    if not window:
        return []
    t_from, t_to = window
    out = []
    for e in run.events:
        if (e.get("name") != "dispatch" or e.get("site") != SITE
                or counter not in e or not e.get("hold_ms")):
            continue
        hold = e["hold_ms"] * 1e-3
        inside = min(e["mono"], t_to) - max(e["mono"] - hold, t_from)
        if inside > 0:
            out.append((e, inside / hold))
    return out


def read(run):
    from benchmarks import flops, flops_sdar

    scopes = run.extra.get("scope_seconds")
    flushes = flushes_inside(run, "moe_experts_touched")
    if not scopes or not scopes["inside"].get("*") or not flushes:
        return None
    least_s = sum(share * flops.least_time_s(
        flops_sdar.flush_work(run.cell.config, e), run.peaks)[0]
        for e, share in flushes)
    return 100.0 * least_s / scopes["inside"]["*"]
