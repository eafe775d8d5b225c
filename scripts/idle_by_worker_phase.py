"""The idle device, split by the device worker's phase.

    python scripts/idle_by_worker_phase.py --workload <cell> --seed <n> \
        --seconds <s> [--out chiprun_out/idle_<cell>.json]

One traced run of a serving cell, made as ``benchmarks/run.py`` makes it
(same driver, same result line as the last line of standard output), with
the profile read once more before the run's files go: every idle gap of
the device inside the traced window is CUT at the edges of the worker's
annotations (``worker.sleep / .take / .prepare / .run / .scatter /
.account``, and inside ``worker.run`` the hold's ``<site>.lock_wait /
.put / .call / .get``), so that each idle second lies under exactly one
innermost name of the ONE thread that drives the chip.  The benchmark's
own ``idle_gaps`` names a whole gap by the span that overlaps most of it,
which is the callers' ``query`` on every line (PERF.md section 7); this
is the split a ``benchmark`` issue can move into ``trace_reduce``.

A phase is prefixed by the batcher whose turn it belongs to (``text.`` /
``topk.``), read from the flush annotation inside the turn's
``worker.run``; ``worker.sleep`` belongs to no turn.  ``(no phase)`` is
idle time before the first phase the trace saw begin (a trace is blind
before it starts).  Seconds; nothing here is edited under ``benchmarks/``.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse     # noqa: E402
import bisect       # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import shutil       # noqa: E402
import sys          # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)

PHASE = "worker."
LEGS = (".lock_wait", ".put", ".call", ".get")
FLUSHES = {"batcher.flush": "text", "topk.flush": "topk"}


def _annotation(name: str) -> bool:
    return (name.startswith(PHASE) or name.endswith(LEGS)
            or name in FLUSHES
            or name in ("query", "dispatch", "index.topk", "bench.window"))


def _overlap(intervals, starts, lo: float, hi: float):
    """(index, seconds) of the sorted, disjoint ``intervals`` that
    overlap [lo, hi]."""
    i = max(0, bisect.bisect_right(starts, lo) - 1)
    while i < len(intervals) and intervals[i][0] < hi:
        s, e = intervals[i][0], intervals[i][1]
        if min(e, hi) > max(s, lo):
            yield i, min(e, hi) - max(s, lo)
        i += 1


def worker_segments(host_lines):
    """``host_lines``: per thread a list of (name, start, end).  -> the
    worker's line as two sorted lists of (start, end, label): its phases
    (each labelled by its turn's batcher) and the holds' legs."""
    lines = [ln for ln in host_lines
             if any(n.startswith(PHASE) for n, _, _ in ln)]
    if len(lines) != 1:
        raise ValueError(f"{len(lines)} host threads carry worker.* "
                         "annotations: one device worker is expected")
    events = sorted(lines[0], key=lambda ev: ev[1])
    flushes = [(s, e, FLUSHES[n]) for n, s, e in events if n in FLUSHES]
    phases, turn = [], []

    def close(kind):
        phases.extend((s, e, f"{kind}.{name}") for s, e, name in turn)
        turn.clear()

    kind = "?"
    for n, s, e in events:
        if not n.startswith(PHASE):
            continue
        name = n[len(PHASE):]
        if name == "sleep":
            phases.append((s, e, "sleep"))
            continue
        if name == "take" and any(p == "account" for _, _, p in turn):
            close(kind)
            kind = "?"
        if name == "run":
            kind = next((k for fs, fe, k in flushes if s <= fs and fe <= e),
                        "?")
        turn.append((s, e, name))
    close(kind)
    legs = [(s, e, n) for n, s, e in events if n.endswith(LEGS)]
    return sorted(phases), sorted(legs)


def split_idle(path: str, layout: dict, trace_reduce) -> dict:
    """-> {"window_s", "idle_s", "by_phase": {label: idle seconds}} of
    the profile at ``path``."""
    data = trace_reduce.load_profile(path)
    host_lines, ops = [], []
    for plane in data.planes:
        if plane.name == layout["host_plane"]:
            host_lines += [list(trace_reduce._events(line))
                           for line in plane.lines]
    marks = [(s, e) for line in host_lines for n, s, e in line
             if n == trace_reduce.WINDOW_SPAN]
    if not marks:
        raise ValueError(f"{path}: no {trace_reduce.WINDOW_SPAN} span")
    window = marks[-1]
    for plane in data.planes:
        if not plane.name.startswith(layout["device_plane_prefix"]):
            continue
        for line in plane.lines:
            if layout["op_lines"] is None:
                # the CPU backend (the tests): an "op" is any host event
                # that is no annotation of the program's or the benchmark's
                ops += [(s, e) for n, s, e in trace_reduce._events(line)
                        if not _annotation(n)]
            elif line.name in layout["op_lines"]:
                ops += [(s, e) for _, s, e in trace_reduce._events(line)]
        if ops:
            break                       # the first chip, as idle_gaps reads
    lo, hi = window
    gaps = trace_reduce.gaps_of(
        [(max(s, lo), min(e, hi)) for s, e in ops if e > lo and s < hi],
        lo, hi)
    phases, legs = worker_segments(host_lines)
    phase_starts = [s for s, _, _ in phases]
    leg_starts = [s for s, _, _ in legs]
    by_phase: dict = {}
    for gs, ge in gaps:
        covered = 0.0
        for i, sec in _overlap(phases, phase_starts, gs, ge):
            label = phases[i][2]
            by_phase[label] = by_phase.get(label, 0.0) + sec
            covered += sec
        for i, sec in _overlap(legs, leg_starts, gs, ge):
            s, e, name = legs[i]
            j = max(0, bisect.bisect_right(phase_starts, s) - 1)
            inside = phases[j][2] if phases and phases[j][0] <= s \
                and e <= phases[j][1] else None
            if inside is not None:      # a leg is part of its run phase
                by_phase[inside] -= sec
                by_phase[name] = by_phase.get(name, 0.0) + sec
        if ge - gs > covered:
            by_phase["(no phase)"] = by_phase.get("(no phase)", 0.0) \
                + (ge - gs) - covered
    idle = sum(ge - gs for gs, ge in gaps)
    return {"window_s": hi - lo, "idle_s": idle, "gaps": len(gaps),
            "by_phase": dict(sorted(by_phase.items(), key=lambda kv: -kv[1]))}


def records_summary(events, window_s: float) -> dict:
    """What the window's records say beside the trace: per batcher the
    mean wall and CPU milliseconds of each phase of a ``worker.turn``,
    whether the turns account for the window and join their flush
    records, each site's mean ``hold_ms`` / ``cpu_ms``, and the
    ``runtime.stall`` events."""
    turns = [e for e in events if e.get("name") == "worker.turn"]
    out = {"turns": len(turns), "window_s": window_s,
           "turns_dur_s": sum(e["dur_ms"] for e in turns) / 1e3}
    keys = {(e["epoch"], e["batcher"]) for e in turns}
    flushes = [e for e in events
               if e.get("name") in ("batcher.flush", "topk.flush")]
    out["flushes"] = len(flushes)
    out["flushes_without_turn"] = sum(
        (e.get("epoch"), e.get("batcher")) not in keys for e in flushes)
    out["turn_keys_unique"] = len(keys) == len(turns)
    phases = ("sleep", "take", "prepare", "run", "scatter", "account")
    for kind in sorted({e["batcher"] for e in turns}):
        mine = [e for e in turns if e["batcher"] == kind]
        out[kind] = {"n": len(mine),
                     "rows": sum(e["rows"] for e in mine) / len(mine)}
        for p in phases:
            out[kind][p + "_ms"] = sum(e[p + "_ms"] for e in mine) / len(mine)
            if p != "sleep":
                out[kind][p + "_cpu_ms"] = sum(
                    e[p + "_cpu_ms"] for e in mine) / len(mine)
    for site in ("engine.text", "index.topk"):
        holds = [e for e in events
                 if e.get("name") == "dispatch" and e.get("site") == site]
        if holds:
            out[site] = {k: sum(e[k] for e in holds) / len(holds)
                         for k in ("hold_ms", "cpu_ms", "put_ms", "call_ms",
                                   "get_ms")}
    out["stalls"] = [{k: e[k] for k in ("late_ms", "proc_cpu_ms", "runq_ms")
                      if k in e}
                     for e in events if e.get("name") == "runtime.stall"]
    out["gc"] = [e["dur_ms"] for e in events if e.get("name") == "runtime.gc"]
    out["long_turns"] = [
        {k: e[k] for k in ("batcher", "rows", "dur_ms", "run_ms",
                           "run_cpu_ms", "scatter_ms", "scatter_cpu_ms")}
        for e in sorted(turns, key=lambda e: -(e["dur_ms"] - e["sleep_ms"]))[:3]]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.chdir(CHECKOUT)

    from benchmarks import harness, peaks, trace_reduce

    bench = harness.load_benchmark(CHECKOUT)
    cell = harness.load_cell(bench, args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"idle_by_worker_phase: no TPU with {cell.chips} chips "
              f"(platform={devices[0].platform!r}): refusing to measure",
              file=sys.stderr)
        return 3
    devices = devices[:cell.chips]
    work = os.path.join(CHECKOUT, "build", "bench", cell.name)
    driver = harness.load_driver(cell.driver, cell.bench_dir)
    try:
        out = driver.run(cell, seed=args.seed, seconds=args.seconds,
                         trace=True, work=work, platform="tpu",
                         t_start=T_START)
        split = split_idle(
            trace_reduce.find_xplane(os.path.join(work, "trace")),
            trace_reduce.TPU_LAYOUT, trace_reduce)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    split["workload"], split["seed"] = cell.name, args.seed
    split["records"] = records_summary(out["record"].events,
                                       out["record"].window_s)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(split, fh, indent=1)
    print("idle_by_worker_phase " + json.dumps(split), flush=True)
    result = harness.result_line(
        bench, cell, out, devices,
        peaks.peaks_for(devices[0].device_kind), trace=True)
    harness.emit(result, out["compared"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
