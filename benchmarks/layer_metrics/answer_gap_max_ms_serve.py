"""answer_gap_max_ms.serve: the longest stretch of the window in which no
call was answered: the largest distance between the ends (``mono``) of two
consecutive ``query`` records of the window, the window's edges counted.
The record has the window's length and not its instants, so what the
window holds before the first answer and after the last (the length less
first-to-last) counts as one stretch: right where one edge has a gap, the
sum of the two where both have."""

LAYER = "serving"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "queries_per_s"


def read(run):
    ends = sorted(e["mono"] for e in run.events
                  if e.get("name") == "query" and e.get("kind") == "span")
    if not ends:
        return None
    inner = max((b - a for a, b in zip(ends, ends[1:])), default=0.0)
    edges = run.window_s - (ends[-1] - ends[0])
    return 1e3 * max(inner, edges)
