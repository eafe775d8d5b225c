"""denoise_time_share.serve: the device time of the block loop of the
block-diffusion sentence tower — the operations under the scopes
``text_dlm/denoise`` (the denoise passes: layers and head) and
``text_dlm/commit`` (the commit pass of each block) — inside the traced
window, over the device's busy time there.  What is left of the tower is
the prefill and the pooled projection.  The time by scope is the driver's
reduction of the trace (``run.extra["scope_seconds"]``,
``benchmarks/scope_times.py``); ``None`` where the run has no such
scopes."""

LAYER = "model"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "queries_per_s"

SCOPES = ("text_dlm/denoise", "text_dlm/commit")


def read(run):
    scopes = run.extra.get("scope_seconds")
    if run.trace is None or not run.trace.busy_s or not scopes:
        return None
    inside = sum(scopes["inside"].get(s) or 0.0 for s in SCOPES)
    return 100.0 * inside / run.trace.busy_s if inside else None
