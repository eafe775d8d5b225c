"""step_dispatch_ms.train: the median host time of dispatching one step:
``dur_ms`` of the ``step`` spans of the window."""

LAYER = "train loop"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "train_clips_per_s_per_chip"


def read(run):
    import statistics

    durs = [e["dur_ms"] for e in run.events
            if e.get("kind") == "span" and e.get("name") == "step"]
    return statistics.median(durs) if durs else None
