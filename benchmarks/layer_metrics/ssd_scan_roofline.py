"""ssd_scan_roofline: over the text flushes of the TRACED window, the least
time the Mamba layers' state-space scans could take over the device time of
the operations under the scope ``text_hybrid/ssd``.  A flush's least time:
the larger of its scans' FLOPs over peak FLOP/s (the state's update and
read-out at its real tokens, the intra-chunk form at the real length) and
the bytes they must read and write (x, B, C, dt in, y out, bfloat16, at
every slot of the flush's rung) over peak bytes/s — from the shapes
(``benchmarks/flops_granite4h.py``) and the ``tokens`` / ``rows`` /
``pad_tokens`` of its ``dispatch`` record, whatever implements the scan.
The device time is the driver's reduction of the trace by scope
(``run.extra["scope_seconds"]``, ``benchmarks/scope_times.py``), over the
tower's executions that overlap the traced window, start to end: the same
flushes above and below."""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "queries_per_s"

SCOPE = "text_hybrid/ssd"
SITE = "engine.text"


def traced_flushes(run, counter: str):
    """The window's ``engine.text`` flush records that carry ``counter``
    and whose hold overlaps the traced window."""
    window = run.extra.get("trace_window")
    if not window:
        return []
    t_from, t_to = window
    return [e for e in run.events
            if e.get("name") == "dispatch" and e.get("site") == SITE
            and "tokens" in e and counter in e and e["mono"] > t_from
            and e["mono"] - e.get("hold_ms", 0.0) * 1e-3 < t_to]


def read(run):
    from benchmarks import flops, flops_granite4h

    scopes = run.extra.get("scope_seconds")
    flushes = traced_flushes(run, "ssm_chunks_run")
    if not scopes or not scopes["whole"].get(SCOPE) or not flushes:
        return None
    least_s = sum(flops.least_time_s(flops_granite4h.scan_work(
        run.cell.config, e["tokens"], e["rows"],
        e["tokens"] + e["pad_tokens"]), run.peaks)[0] for e in flushes)
    return 100.0 * least_s / scopes["whole"][SCOPE]
