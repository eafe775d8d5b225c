"""kda_time_share.serve: the device time of the operations under the scope
``text_hybrid/kda`` (the Kimi Delta Attention mixers whole: projections,
convs, decays, the delta rule, norm, gate and output projection) inside the
traced window, over the device's busy time there.  The time by scope is
the driver's reduction of the trace (``run.extra["scope_seconds"]``,
``benchmarks/scope_times.py``); ``None`` where the run has no such scope (a
tower without KDA layers, or a program that names none)."""

LAYER = "model"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "queries_per_s"

SCOPE = "text_hybrid/kda"


def read(run):
    scopes = run.extra.get("scope_seconds")
    if (run.trace is None or not run.trace.busy_s or not scopes
            or not scopes["inside"].get(SCOPE)):
        return None
    return 100.0 * scopes["inside"][SCOPE] / run.trace.busy_s
