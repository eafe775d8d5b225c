"""expert_overhead_share.serve: of the device time of the hybrid tower's
expert layers (the operations under the scope ``text_hybrid/moe``) inside
the traced window, the share that is NOT the grouped products the layers
exist for (the operations whose ``op_name`` holds ``grouped_matmul``: the
three kernels a turn): routing, the sort by expert, the gathers, the mask
and the put-back of the products' rows to their tokens (scope ``putback``
inside ``text_lm.held_expert_sum``).  Lower is better.  The time by scope
is the driver's reduction of the trace (``run.extra["scope_seconds"]``,
``benchmarks/scope_times.py``); ``None`` where the run has no scopes."""

LAYER = "model"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "queries_per_s"

SCOPE = "text_hybrid/moe"
KERNELS = "grouped_matmul"


def read(run):
    scopes = run.extra.get("scope_seconds")
    if not scopes or not scopes["inside"].get(SCOPE):
        return None
    layers = scopes["inside"][SCOPE]
    return 100.0 * (layers - scopes["inside"].get(KERNELS, 0.0)) / layers
