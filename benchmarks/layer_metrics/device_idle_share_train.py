"""device_idle_share.train: 1 - the union of the device's op intervals over
the traced window, averaged over the chips used."""

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_clips_per_s_per_chip"


def read(run):
    if run.trace is None or not run.trace.busy_s:
        return None
    return 100.0 * run.trace.idle_share
