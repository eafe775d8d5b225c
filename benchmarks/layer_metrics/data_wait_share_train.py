"""data_wait_share.train: the share of the window the loop spent blocked on
batch data: the ``data.wait`` spans of RUN_EVENTS.jsonl (what the goodput
ledger books as ``data_wait``) over the window."""

LAYER = "input"
UNIT = "%"
SOURCE = "program_span"
MOVES = "train_clips_per_s_per_chip"


def read(run):
    waits = [e["dur_ms"] for e in run.events
             if e.get("kind") == "span" and e.get("name") == "data.wait"]
    if not run.window_s or not any(e.get("name") == "step"
                                   for e in run.events):
        return None
    return 100.0 * sum(waits) / 1e3 / run.window_s
