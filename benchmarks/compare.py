"""The comparisons that decide ``correct``: what the timed path produced
against the plain reference.  Pure functions of host arrays, so that the
tests can break an input underneath and watch the verdict turn.
"""

from __future__ import annotations

import numpy as np

GRAD_FLOOR = 1e-3       # leaves whose reference gradient is under this
#                         share of the median leaf's are left out of the
#                         parameters' change (they move by round-off alone)


def _norm(x) -> float:
    return float(np.linalg.norm(np.asarray(x, np.float64).ravel()))


def norm_gaps(prog: dict, ref: dict, names=None) -> dict:
    """name -> |‖prog‖ − ‖ref‖| / max(‖ref‖ of the leaf, of the median
    leaf): the gap between the norms, not the norm of the difference."""
    names = sorted(ref) if names is None else names
    ref_norms = {n: _norm(ref[n]) for n in names}
    median = float(np.median(list(ref_norms.values()))) if names else 0.0
    out = {}
    for n in names:
        scale = max(ref_norms[n], median)
        out[n] = abs(_norm(prog[n]) - ref_norms[n]) / scale if scale else 0.0
    return out


def training_numbers(prog: dict, ref: dict, frozen=()) -> dict:
    """``prog``/``ref``: {"losses": [per followed step], "grad": {leaf:
    first gradient}, "delta": {leaf: parameters after the followed steps
    minus the initial ones}}.  -> the numbers compared, each the worst
    over steps or leaves, with the leaf that gave it."""
    steps = min(len(prog["losses"]), len(ref["losses"]))
    loss_gap = max(abs(prog["losses"][k] - ref["losses"][k])
                   / abs(ref["losses"][k]) for k in range(steps))
    leaves = [n for n in sorted(ref["grad"]) if n not in frozen]
    g_gaps = norm_gaps(prog["grad"], ref["grad"], leaves)
    g_norms = {n: _norm(ref["grad"][n]) for n in leaves}
    median = float(np.median(list(g_norms.values())))
    moving = [n for n in leaves if g_norms[n] >= GRAD_FLOOR * median]
    d_gaps = norm_gaps(prog["delta"], ref["delta"], moving)
    worst_g = max(g_gaps, key=g_gaps.get)
    worst_d = max(d_gaps, key=d_gaps.get)
    first = abs(prog["losses"][0] - ref["losses"][0]) / abs(ref["losses"][0])
    return {"loss_gap": loss_gap, "first_loss_gap": first,
            "grad_norm_gap": g_gaps[worst_g], "grad_norm_leaf": worst_g,
            "grad_norm_gap_median": float(np.median(list(g_gaps.values()))),
            "step_norm_gap": d_gaps[worst_d], "step_norm_leaf": worst_d,
            "step_norm_gap_median": float(np.median(list(d_gaps.values()))),
            "leaves_left_out": len(leaves) - len(moving)}


def retrieval_numbers(served_idx, served_scores, ref_at_served, ref_topk,
                      q_norm, corpus_rows: int) -> dict:
    """Per sampled query: ``served_idx``/``served_scores`` (S, k) as the
    service answered, ``ref_at_served`` (S, k) the reference's score of
    each served row, ``ref_topk`` (S, k) the reference's own best scores
    in order, ``q_norm`` (S,) the norm of the reference's query embedding
    (the corpus rows are unit normal, so a score's spread is ``q_norm``).

    - ``rank_gap``: the widest gap by which the served j-th row's
      reference score lies below the reference's j-th best, in units of
      ``q_norm``;
    - ``score_err``: the widest |served score − reference score of that
      row|, in the same units.
    An answer that is malformed (a row out of range or named twice)
    reads infinity."""
    served_idx = np.asarray(served_idx)
    scale = np.asarray(q_norm, np.float64)[:, None]
    bad = (served_idx < 0) | (served_idx >= corpus_rows)
    dup = np.array([len(set(r.tolist())) != len(r) for r in served_idx])
    if bad.any() or dup.any():
        return {"rank_gap": float("inf"), "score_err": float("inf")}
    gap = (np.asarray(ref_topk, np.float64)
           - np.asarray(ref_at_served, np.float64)) / scale
    err = np.abs(np.asarray(served_scores, np.float64)
                 - np.asarray(ref_at_served, np.float64)) / scale
    return {"rank_gap": float(gap.max()), "score_err": float(err.max())}
