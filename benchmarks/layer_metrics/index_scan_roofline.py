"""index_scan_roofline: the least time one pass over the
index could take (the larger of its bytes over peak bytes/s and its FLOPs
over peak FLOP/s, from the shapes; at these sizes the bytes bound holds)
over the median device time of the top-k program in the trace."""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "queries_per_s"


TOPK_MODULE = "local_topk"      # jit name of serving/index.py make_topk_fn


def read(run):
    import statistics

    from benchmarks import flops

    if run.trace is None:
        return None
    durs = [d for name, ds in run.trace.module_seconds.items()
            if TOPK_MODULE in name for d in ds]
    if not durs:
        return None
    work = flops.index_scan_work(run.extra["index_rows"],
                                 run.extra["index_dim"],
                                 run.extra["scan_queries"])
    least, _bound = flops.least_time_s(work, run.peaks)
    # the rows are sharded over the chips: each scans its share
    return 100.0 * least / run.cell.chips / statistics.median(durs)
