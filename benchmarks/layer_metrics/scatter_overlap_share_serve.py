"""scatter_overlap_share.serve: the share of the rows the window's passes
ranked whose scatter rode a later program: over the ``topk.flush``
records that carry ``rode`` (where a pass's held scatter ran: ``topk``,
``text`` — inside that program's round trip, while the device computed —
or ``none``, at once), the sum of ``rows`` of those with ``topk`` or
``text`` over the sum of ``rows`` of them all.  ``None`` where no record
carries ``rode`` (a program that scatters every pass at once, before the
next is dispatched)."""

LAYER = "serving"
UNIT = "%"
SOURCE = "program_span"
MOVES = "queries_per_s"

RODE = ("topk", "text")


def read(run):
    passes = [e for e in run.events
              if e.get("name") == "topk.flush" and "rode" in e
              and e.get("rows")]
    rows = sum(e["rows"] for e in passes)
    if not rows:
        return None
    rode = sum(e["rows"] for e in passes if e["rode"] in RODE)
    return 100.0 * rode / rows
