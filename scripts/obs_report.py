#!/usr/bin/env python
"""Observability report + regression gate over the unified artifacts.

One tool reads everything the obs subsystem emits (OBSERVABILITY.md):

- ``RUN_EVENTS.jsonl`` span/event streams (train runs, obs/spans.py);
- ``milnce.obs/v1`` snapshot documents — serve_bench reports
  (``SERVE_BENCH_*.json``), raw registry snapshots, train bench records
  (the ``schema``/``kind`` keys discriminate producers).

Usage::

    python scripts/obs_report.py RUN_EVENTS.jsonl            # summarize
    python scripts/obs_report.py SERVE_BENCH_tiny_tiers.json
    python scripts/obs_report.py --check CURRENT --baseline BASELINE \
        [--tolerance 0.10]                                   # CI gate
    python scripts/obs_report.py --check CURRENT --baseline latest
    python scripts/obs_report.py --merge SNAP0 SNAP1 [...] \
        [--out POD.json]                                     # pod view

The gate compares the artifacts' *gate metrics* (step-time p50/p99 from
a span stream; latency p50/p99 + QPS from a serve_bench report;
clips/sec, MFU + predicted peak bytes from a train bench record;
``goodput_fraction`` + ``mfu`` from a goodput ledger) against a
committed baseline and exits nonzero when any drifts more than
``--tolerance`` (default 10%) in the bad direction — wired next to
``graft_lint.py --check`` in the README verify recipe.  Drift in the
*good* direction never fails: the gate is a regression fence, not a
pin.  ``--baseline latest`` auto-picks the newest same-kind artifact
in the current artifact's directory.

Run identity (obs/runctx.py): event streams holding records from more
than one ``run_id`` are a LOUD error (the documented cross-run append
ambiguity) — pass ``--run-id`` to select one.  ``--merge`` fuses >= 2
per-process snapshots (or event streams) of ONE run into a pod view:
counters summed, gauges min/median/max across hosts, straggler
detection as cross-host step-span skew; the merged snapshot gates with
``--check`` exactly like a single-process artifact (obs/aggregate.py).

stdlib-only, no jax import: the gate must cost milliseconds in CI.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from milnce_tpu.obs import aggregate  # noqa: E402  (jax-free)
from milnce_tpu.obs.export import SNAPSHOT_SCHEMA  # noqa: E402  (jax-free)
from milnce_tpu.obs.goodput import select_run, split_runs  # noqa: E402

# gate metric name -> direction ("lower" = lower is better)
GATE_DIRECTIONS = {
    "step_ms_p50": "lower",
    "step_ms_p99": "lower",
    "latency_ms_p50": "lower",
    "latency_ms_p99": "lower",
    "qps": "higher",
    "clips_per_sec_per_chip": "higher",
    # static HBM plan of the benched program (graftlint Pass 4,
    # ISSUE 8): a row that got faster by inflating its footprint is a
    # regression; cross-layout compares stay attributable via the
    # mesh/sharding_map_hash note
    "predicted_peak_bytes_per_chip": "lower",
    # attribution tier (ISSUE 9): live MFU + kept-compute fraction are
    # first-class gate metrics — a run that kept its clips/s by hiding
    # badput (skips, data waits) fails here
    "mfu": "higher",
    "goodput_fraction": "higher",
    # serving resilience tier (ISSUE 10): the UNSTRUCTURED failure
    # fraction of a serve_bench run (structured refusals — 429/503/504 —
    # are counted separately and do NOT gate here); chaos benches pin
    # error-rate drift with this
    "error_rate": "lower",
    # edge tier (ISSUE 19): retrieval quality of a serve_bench
    # ``--tier-class`` record, measured as top-10 overlap against the
    # f32 class's rankings on the same query pool.  Gating an edge-class
    # (int8 / distilled-student) record against the committed f32
    # baseline pins the quality floor; the dtype_census_hash note below
    # marks the compare as cross-precision so latency drift stays
    # attributable to the precision change
    "recall_at_10": "higher",
}


def gate_direction(name: str) -> str:
    """Direction for a gate metric name.  Per-tier metrics (ISSUE 14 —
    serve_bench ``--tiers``) are ``<base>@<tier>`` and inherit the base
    metric's direction, so ``latency_ms_p99@interactive`` gates exactly
    like the aggregate p99."""
    return GATE_DIRECTIONS[name.partition("@")[0]]


def _percentile(sorted_vals: list, q: float) -> float:
    """Linear-interpolated percentile over an already-sorted list."""
    if not sorted_vals:
        return float("nan")
    if len(sorted_vals) == 1:
        return float(sorted_vals[0])
    pos = (q / 100.0) * (len(sorted_vals) - 1)
    lo = int(pos)
    frac = pos - lo
    hi = min(lo + 1, len(sorted_vals) - 1)
    return float(sorted_vals[lo] * (1 - frac) + sorted_vals[hi] * frac)


# ---------------------------------------------------------------------------
# loading
# ---------------------------------------------------------------------------

def load_artifact(path: str, run_id: str | None = None) -> dict:
    """-> ``{"format": "events", "records": [...]}`` for a JSONL stream,
    or ``{"format": "snapshot", "doc": {...}}`` for a schema'd JSON
    document.  Unversioned JSON is an error, not a guess — the whole
    point of the shared schema is that this tool never sniffs.

    Event streams are split on ``run_id``: a stream holding more than
    one run (the append-only cross-run case OBSERVABILITY.md documents)
    is an error unless ``run_id`` picks one — mixed-run percentiles are
    confidently wrong, which is worse than failing."""
    with open(path) as fh:
        head = fh.read(1)
        fh.seek(0)
        if not head:
            raise ValueError(f"{path}: empty artifact")
        if path.endswith(".jsonl"):
            records = [json.loads(line) for line in fh if line.strip()]
            records = select_run(records, run_id)
            return {"format": "events", "records": records, "path": path}
        doc = json.load(fh)
    schema = doc.get("schema")
    if schema != SNAPSHOT_SCHEMA:
        raise ValueError(
            f"{path}: schema {schema!r} is not {SNAPSHOT_SCHEMA!r} — "
            "regenerate the artifact with the current tools "
            "(OBSERVABILITY.md 'Snapshot schema')")
    return {"format": "snapshot", "doc": doc, "path": path}


# ---------------------------------------------------------------------------
# summaries
# ---------------------------------------------------------------------------

def summarize_events(records: list) -> dict:
    """Per-name span duration stats + event counts."""
    spans: dict[str, list] = {}
    span_errors: dict[str, int] = {}
    events: dict[str, int] = {}
    for rec in records:
        name = rec.get("name", "?")
        if rec.get("kind") == "span":
            spans.setdefault(name, []).append(float(rec.get("dur_ms", 0.0)))
            if "error" in rec:
                span_errors[name] = span_errors.get(name, 0) + 1
        elif rec.get("kind") == "event":
            events[name] = events.get(name, 0) + 1
    span_stats = {}
    for name, durs in spans.items():
        durs = sorted(durs)
        span_stats[name] = {
            "count": len(durs),
            "total_ms": round(sum(durs), 3),
            "mean_ms": round(sum(durs) / len(durs), 4),
            "p50_ms": round(_percentile(durs, 50), 4),
            "p99_ms": round(_percentile(durs, 99), 4),
            "errors": span_errors.get(name, 0),
        }
    return {"spans": span_stats, "events": events}


def gate_metrics(artifact: dict) -> dict[str, float]:
    """The comparable numbers an artifact contributes to the gate."""
    out: dict[str, float] = {}
    if artifact["format"] == "events":
        stats = summarize_events(artifact["records"])["spans"].get("step")
        if stats:
            out["step_ms_p50"] = stats["p50_ms"]
            out["step_ms_p99"] = stats["p99_ms"]
        return out
    doc = artifact["doc"]
    lat = doc.get("latency_ms") or {}
    for src, dst in (("p50", "latency_ms_p50"), ("p99", "latency_ms_p99")):
        v = lat.get(src)
        if isinstance(v, (int, float)):
            out[dst] = float(v)
    for key in ("qps", "clips_per_sec_per_chip",
                "predicted_peak_bytes_per_chip", "mfu",
                "goodput_fraction", "error_rate", "recall_at_10"):
        v = doc.get(key)
        if isinstance(v, (int, float)):
            out[key] = float(v)
    # per-tier serve_bench block (ISSUE 14): each SLO tier contributes
    # its own p50/p99/qps/error_rate as <base>@<tier> gate metrics, so
    # a chaos run pins "interactive p99 inside its SLO" directly
    tiers = doc.get("tiers")
    if isinstance(tiers, dict):
        for tier, td in sorted(tiers.items()):
            if not isinstance(td, dict):
                continue
            lat = td.get("latency_ms") or {}
            for src in ("p50", "p99"):
                v = lat.get(src)
                if isinstance(v, (int, float)):
                    out[f"latency_ms_{src}@{tier}"] = float(v)
            for key in ("qps", "error_rate"):
                v = td.get(key)
                if isinstance(v, (int, float)):
                    out[f"{key}@{tier}"] = float(v)
    if "value" in doc and doc.get("unit") == "clips/sec/chip":
        out["clips_per_sec_per_chip"] = float(doc["value"])
    return out


def render_summary(artifact: dict) -> str:
    lines = [f"artifact: {artifact['path']} ({artifact['format']})"]
    if artifact["format"] == "events":
        s = summarize_events(artifact["records"])
        lines.append(f"  records: {len(artifact['records'])}")
        if s["spans"]:
            lines.append("  spans (name count mean/p50/p99 ms errors):")
            for name in sorted(s["spans"]):
                st = s["spans"][name]
                lines.append(
                    f"    {name:<16} {st['count']:>6}  "
                    f"{st['mean_ms']:>10.3f} {st['p50_ms']:>10.3f} "
                    f"{st['p99_ms']:>10.3f}  {st['errors']}")
        if s["events"]:
            lines.append("  events: " + ", ".join(
                f"{k}={v}" for k, v in sorted(s["events"].items())))
    else:
        doc = artifact["doc"]
        lines.append(f"  kind: {doc.get('kind')}  schema: {doc['schema']}")
        if doc.get("run_id") is not None:
            pi = doc.get("process_index")
            pod = doc.get("processes")
            lines.append(
                f"  run: {doc['run_id']}"
                + (f"  process: {pi}" if pi is not None else "")
                + (f"  processes merged: {pod}" if pod is not None else ""))
        for k, v in sorted(gate_metrics(artifact).items()):
            lines.append(f"  {k}: {v}")
        cats = doc.get("categories_s")
        if isinstance(cats, dict):      # goodput ledger attribution
            wall = float(doc.get("wall_s", 0.0)) or None
            lines.append("  wall-time attribution:")
            for name, sec in sorted(cats.items(), key=lambda kv: -kv[1]):
                frac = f" ({sec / wall:.1%})" if wall else ""
                lines.append(f"    {name:<14} {sec:>10.3f}s{frac}")
        spread = doc.get("spread")
        if isinstance(spread, dict):    # pod merge: per-host extremes
            lines.append("  cross-host spread (min/median/max):")
            for name in sorted(spread):
                s = spread[name]
                lines.append(f"    {name}: {s['min']:g} / "
                             f"{s['median']:g} / {s['max']:g}")
        metrics = doc.get("metrics") or {}
        if metrics:
            lines.append(f"  registry families: {len(metrics)}")
            for name in sorted(metrics):
                fam = metrics[name]
                if fam["type"] == "histogram":
                    tot = sum(v.get("count", 0) for v in fam["values"])
                    lines.append(f"    {name} (histogram): {tot} samples")
                else:
                    vals = ", ".join(
                        (("{" + ",".join(f"{lk}={lv}" for lk, lv in
                                         v["labels"].items()) + "}")
                         if v["labels"] else "") + f"{v['value']:g}"
                        for v in fam["values"][:6])
                    lines.append(f"    {name} ({fam['type']}): {vals}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# the gate
# ---------------------------------------------------------------------------

def check(current: dict, baseline: dict, tolerance: float) -> tuple[bool,
                                                                    str]:
    """-> (ok, report).  Fails on any shared gate metric drifting more
    than ``tolerance`` in its bad direction; errors (ok=False) when the
    artifacts share no gate metrics at all — a gate that silently
    compares nothing is worse than no gate."""
    cur, base = gate_metrics(current), gate_metrics(baseline)
    shared = sorted(set(cur) & set(base))
    if not shared:
        return False, (
            f"no shared gate metrics between {current['path']} "
            f"({sorted(cur) or 'none'}) and baseline {baseline['path']} "
            f"({sorted(base) or 'none'}) — artifacts are not comparable")
    lines = [f"gate: {current['path']} vs baseline {baseline['path']} "
             f"(tolerance {tolerance:.0%})"]
    # mesh layout / sharding-map identity (ISSUE 6): 1-D vs 2-D runs ARE
    # comparable (that comparison is the point of the fields), but a
    # drift across layouts must be ATTRIBUTABLE — say so in the report
    # instead of letting a layout change read as a plain regression
    cur_doc, base_doc = current.get("doc") or {}, baseline.get("doc") or {}
    # dtype_census_hash: a differing precision fingerprint (Pass 5)
    # means the two rows ran different-precision programs — the drift
    # below is attributable to the dtype change, not the code under test
    for key in ("mesh", "sharding_map_hash", "dtype_census_hash"):
        b, c = base_doc.get(key), cur_doc.get(key)
        if (b or c) and b != c:
            kind = ("cross-precision" if key == "dtype_census_hash"
                    else "cross-layout")
            lines.append(f"  [note] {key} differs: baseline {b or '-'} "
                         f"-> current {c or '-'} ({kind} compare)")
    ok = True
    compared = 0
    for name in shared:
        b, c = base[name], cur[name]
        if b == 0:
            lines.append(f"  [skip] {name}: baseline is 0")
            continue
        compared += 1
        drift = (c - b) / b
        bad = (drift > tolerance if gate_direction(name) == "lower"
               else drift < -tolerance)
        ok = ok and not bad
        lines.append(f"  [{'FAIL' if bad else 'ok'}] {name}: "
                     f"{b:g} -> {c:g} ({drift:+.1%}, "
                     f"{gate_direction(name)} is better)")
    if compared == 0:
        # every shared metric got skipped (all-zero baseline, e.g. a
        # bench error-path record committed by mistake) — a gate that
        # compared nothing must not pass
        lines.append("  FAIL: every shared gate metric has a zero "
                     "baseline — nothing was compared; fix the baseline "
                     "artifact")
        return False, "\n".join(lines)
    return ok, "\n".join(lines)


def resolve_latest_baseline(current: dict) -> str:
    """``--baseline latest``: the newest artifact of the SAME kind in
    the current artifact's directory (event streams match event
    streams; snapshots match on their ``kind``).  Kind mismatches are
    not silently compared — if nothing matches, the error names what
    WAS found so the refusal is as loud as the incomparable-pair one."""
    # a merged view has a placeholder path ("<merged:N>"); its "dir"
    # records the FIRST input artifact's directory so --baseline latest
    # scans where the snapshots actually live, never the cwd
    cur_path = os.path.abspath(current["path"])
    directory = (current.get("dir")
                 or os.path.dirname(cur_path) or ".")
    if current["format"] == "events":
        want_kind = None
    else:
        want_kind = current["doc"].get("kind")
    candidates, rejected = [], []
    for fname in sorted(os.listdir(directory)):
        path = os.path.join(directory, fname)
        if os.path.abspath(path) == cur_path or not os.path.isfile(path):
            continue
        if not fname.endswith((".json", ".jsonl")):
            continue
        try:
            art = load_artifact(path)
        except (OSError, ValueError, json.JSONDecodeError):
            continue                    # unreadable/mixed: not a baseline
        got_kind = (art["doc"].get("kind")
                    if art["format"] == "snapshot" else None)
        if art["format"] == current["format"] and got_kind == want_kind:
            candidates.append(path)
        else:
            rejected.append(f"{fname} ({got_kind or art['format']})")
    if not candidates:
        raise ValueError(
            f"--baseline latest: no other "
            f"{want_kind or 'event-stream'} artifact in {directory}"
            + (f" — kinds present: {', '.join(rejected)}" if rejected
               else " (directory holds no other artifacts)"))
    return max(candidates, key=os.path.getmtime)


def merge_artifacts(paths: list, run_id: str | None) -> dict:
    """``--merge``: >= 2 per-process artifacts -> one pod view
    (obs/aggregate.py).  All-snapshots -> a merged ``pod_<kind>``
    snapshot artifact; all-event-streams -> a straggler/skew report
    document.  Mixing the two formats is an error."""
    arts = [load_artifact(p, run_id) for p in paths]
    formats = {a["format"] for a in arts}
    if len(formats) > 1:
        raise ValueError("--merge needs all-snapshots or all-event-"
                         "streams, not a mix")
    src_dir = os.path.dirname(os.path.abspath(paths[0])) or "."
    if formats == {"snapshot"}:
        doc = aggregate.merge_snapshots([a["doc"] for a in arts])
        return {"format": "snapshot", "doc": doc,
                "path": f"<merged:{len(arts)}>", "dir": src_dir}
    view = aggregate.merge_event_streams([a["records"] for a in arts])
    return {"format": "pod_events", "doc": view,
            "path": f"<merged:{len(arts)}>", "dir": src_dir}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="observability summarizer + regression gate "
                    "(scripts/obs_report.py)")
    ap.add_argument("artifacts", nargs="+",
                    help="RUN_EVENTS.jsonl or milnce.obs/v1 JSON doc(s); "
                         ">= 2 with --merge")
    ap.add_argument("--check", action="store_true",
                    help="gate the artifact against --baseline; exit 1 "
                         "on regression")
    ap.add_argument("--baseline", default="",
                    help="committed baseline artifact to gate against, "
                         "or 'latest' to auto-pick the newest same-kind "
                         "artifact in the current artifact's directory")
    ap.add_argument("--tolerance", type=float, default=0.10,
                    help="allowed bad-direction drift fraction "
                         "(default 0.10)")
    ap.add_argument("--run-id", default=None,
                    help="select ONE run out of a shared append-only "
                         "event stream (mixed-run streams error "
                         "otherwise)")
    ap.add_argument("--merge", action="store_true",
                    help="merge >= 2 per-process artifacts of one run "
                         "into a pod view (counters summed, gauges "
                         "min/median/max, straggler skew)")
    ap.add_argument("--out", default="",
                    help="with --merge: write the merged pod snapshot "
                         "here (gate it later with --check)")
    args = ap.parse_args(argv)

    try:
        if args.merge:
            current = merge_artifacts(args.artifacts, args.run_id)
        else:
            if len(args.artifacts) != 1:
                print("obs_report: multiple artifacts need --merge",
                      file=sys.stderr)
                return 2
            current = load_artifact(args.artifacts[0], args.run_id)
    except (OSError, ValueError, json.JSONDecodeError) as exc:
        print(f"obs_report: cannot read {' '.join(args.artifacts)}: {exc}",
              file=sys.stderr)
        return 2

    if current["format"] == "pod_events":
        # straggler report: per-process step stats + cross-host skew
        view = current["doc"]
        print(f"pod event merge: run {view['run_id']}, "
              f"{view['processes']} processes")
        for pi in sorted(view["per_process"]):
            s = view["per_process"][pi]
            lines = (f"  p{pi}: {s['steps']} steps, step p50 "
                     f"{s['step_ms_p50']} ms, p99 {s['step_ms_p99']} ms")
            if pi in view["stragglers"]:
                lines += "   <-- STRAGGLER"
            print(lines)
        print(f"  step p50 skew (slowest/fastest): "
              f"{view['step_p50_skew']}x "
              f"(straggler threshold {view['straggler_ratio']}x)")
        if args.out:
            with open(args.out, "w") as fh:
                json.dump(view, fh, indent=2, sort_keys=True)
                fh.write("\n")
        # a skewed pod is a finding, not a gate failure — gating step
        # time happens against a baseline via --check on the streams
        return 0

    if args.merge and args.out:
        with open(args.out, "w") as fh:
            json.dump(current["doc"], fh, indent=2, sort_keys=True)
            fh.write("\n")
        current["path"] = args.out

    if not args.check:
        print(render_summary(current))
        return 0

    if not args.baseline:
        print("obs_report: --check requires --baseline", file=sys.stderr)
        return 2
    try:
        baseline_path = (resolve_latest_baseline(current)
                         if args.baseline == "latest" else args.baseline)
        # the baseline is a DIFFERENT run by definition — it must be a
        # clean single-run artifact on its own, so --run-id (which
        # selects out of the CURRENT stream) does not apply to it
        baseline = load_artifact(baseline_path)
    except (OSError, ValueError, json.JSONDecodeError) as exc:
        print(f"obs_report: cannot resolve baseline {args.baseline}: "
              f"{exc}", file=sys.stderr)
        return 2
    ok, report = check(current, baseline, args.tolerance)
    print(report)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
