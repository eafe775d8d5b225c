"""step_device_ms.train: the device time of one step: the busy time of the
traced window over the steps that ran in it (whole and part steps, by
their share inside the window)."""

LAYER = "step program"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_clips_per_s_per_chip"


def read(run):
    if run.trace is None or not run.extra.get("traced_steps"):
        return None
    return 1e3 * run.trace.busy_s / run.extra["traced_steps"]
