"""text_tower_time_share.serve: the sentence tower's program's device time
over the device's busy time in the traced window: mean duration of its
executions x the executions inside the window (part ones by their share)
over ``busy_s``."""

LAYER = "model"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "queries_per_s"

TOWER_MODULE = "text_lm_tower"      # jit name, train/step.py


def read(run):
    if run.trace is None or not run.trace.busy_s:
        return None
    inside_s = 0.0
    for name, durs in run.trace.module_seconds.items():
        if TOWER_MODULE in name and durs:
            inside_s += (sum(durs) / len(durs)
                         * run.trace.module_inside.get(name, 0.0))
    if not inside_s:
        return None
    return 100.0 * inside_s / run.trace.chips / run.trace.busy_s
