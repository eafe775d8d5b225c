"""query_mfu: the whole query path as a share of the chip's peak: (2 x N x D
for the scan + the text tower's FLOPs) per query x queries/s of the traced
window, over chips x peak FLOP/s."""

LAYER = "serving"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "queries_per_s"


def read(run):
    if run.trace is None or not run.traced_work:
        return None
    rate = run.traced_work / run.trace.window_s             # queries/s
    return (100.0 * run.extra["work_per_item_flops"] * rate
            / (run.cell.chips * run.peaks["flops_per_s"]))
