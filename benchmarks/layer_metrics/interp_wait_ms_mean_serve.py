"""interp_wait_ms_mean.serve: how long a thread that wants the interpreter
(and a core) waits for them, sampled: the runtime watcher asks to be woken
every 20 ms and reads how late it woke; the window's ``runtime.beat``
records (one a second) give the sum of ``late_mean_ms x beats`` over the
sum of ``beats``."""

LAYER = "serving"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "query_p95_ms"


def beats(run):
    """The window's ``runtime.beat`` records (none on a program without
    the watcher's beat)."""
    return [e for e in run.events
            if e.get("name") == "runtime.beat" and e.get("beats")]


def read(run):
    records = beats(run)
    if not records:
        return None
    return (sum(e["late_mean_ms"] * e["beats"] for e in records)
            / sum(e["beats"] for e in records))
