"""train_step_mfu: the whole step as a share of the chip's peak:
forward + backward FLOPs per clip (benchmarks/flops.py, nothing recomputed
counted) x clips/s of the traced window, over chips x peak FLOP/s."""

LAYER = "step program"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_clips_per_s_per_chip"


def read(run):
    if run.trace is None or not run.traced_work:
        return None
    rate = run.traced_work / run.trace.window_s             # clips/s
    return (100.0 * run.extra["work_per_item_flops"] * rate
            / (run.cell.chips * run.peaks["flops_per_s"]))
