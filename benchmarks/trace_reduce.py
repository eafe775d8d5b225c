"""From a profiler trace (``.xplane.pb``) to numbers: device busy and
idle time, time by operation, and the idle gaps named by what the host
was doing.  Read with nothing but ``jax.profiler.ProfileData``.

What the trace of a TPU v5e looks like (one of the query cell's looked
at by hand, my chip run, PR 25): one plane per chip named
``/device:TPU:<n>`` with the lines ``XLA Modules`` (one event per
executed program, named ``jit_<fn>(<hash>)``: ``jit_local_topk(...)``),
``XLA Ops`` (one event per executed HLO operation, named by the WHOLE
instruction text, ``%convolution_select_fusion = f32[16,3000000]...
fusion(...), kind=kOutput, calls=...``; no category stat, only
``device_offset_ps`` / ``device_duration_ps``), ``Async XLA Ops``
(copy-start/done pairs that overlap compute; not counted as busy) and
``TC Overlay``; beside it ``#Chip0 Host Interface``, ``#Chip0 Misc``,
``/host:metadata`` and ``/device:CUSTOM:Megascale Trace``, which hold
nothing read here.  The host is the plane ``/host:CPU``, one unnamed
line per thread, where ``jax.profiler.TraceAnnotation`` spans appear
under their own names.  On the CPU backend (the recorded trace of the tests) there
is no device plane: XLA's thunks run on host threads, and the same
reduction is pointed at those lines instead (``cpu_layout``).

Everything is in seconds.  ``busy`` is the union of the op intervals of
one chip inside the window, averaged over the chips used.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

TPU_LAYOUT = {"device_plane_prefix": "/device:TPU:", "op_lines": ("XLA Ops",),
              "module_lines": ("XLA Modules",), "host_plane": "/host:CPU"}
# XLA:CPU runs its thunks on the calling and pool threads of the host
# plane; an "op" is any event there that is not one of our annotations
CPU_LAYOUT = {"device_plane_prefix": "/host:CPU", "op_lines": None,
              "module_lines": (), "host_plane": "/host:CPU"}
WINDOW_SPAN = "bench.window"


# how every traced run starts the profiler: no Python frames (they are
# most of a trace's bytes and of the tracer's cost on the host), host
# spans down to the TraceAnnotations
PROFILER = {"python_tracer_level": 0, "host_tracer_level": 2}


def start_trace(trace_dir: str) -> None:
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = PROFILER["python_tracer_level"]
    options.host_tracer_level = PROFILER["host_tracer_level"]
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load_profile(path: str):
    """``.xplane.pb`` as the profiler wrote it, or gzipped (the recorded
    trace of the tests)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as fh:
            return ProfileData.from_serialized_xspace(fh.read())
    return ProfileData.from_file(path)


def union_length(intervals) -> float:
    """Total length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_of(intervals, lo: float, hi: float) -> list:
    """The (start, end) stretches of [lo, hi] that no interval covers."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def attribute_gaps(gaps, host_spans) -> dict:
    """Seconds of idle gap by the host span covering most of each gap:
    ``host_spans`` is a list of (name, start, end); where spans nest, the
    shortest span that overlaps the gap most wins; a gap that no span
    touches is ``(no span)``."""
    by_name: dict = {}
    for gs, ge in gaps:
        best, best_key = "(no span)", (0.0, 0.0)
        for name, s, e in host_spans:
            overlap = min(ge, e) - max(gs, s)
            if overlap <= 0:
                continue
            key = (overlap, -(e - s))
            if key > best_key:
                best, best_key = name, key
        by_name[best] = by_name.get(best, 0.0) + (ge - gs)
    return by_name


@dataclass
class Reduction:
    window_s: float
    busy_s: float                       # mean over the chips used
    chips: int
    op_seconds: dict = field(default_factory=dict)      # name -> s, per chip
    op_text: dict = field(default_factory=dict)         # name -> the whole
    #                     HLO instruction, where the trace names ops by it
    module_seconds: dict = field(default_factory=dict)  # name -> [durations]
    module_inside: dict = field(default_factory=dict)   # name -> executions
    #                     inside the window, part ones by their share inside
    gap_seconds: dict = field(default_factory=dict)     # host span -> s
    host_spans: dict = field(default_factory=dict)      # name -> [durations]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top_ops(self, n: int = 10) -> list:
        return sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:n]

    def top_gaps(self, n: int = 10) -> list:
        return sorted(self.gap_seconds.items(), key=lambda kv: -kv[1])[:n]


def _events(line):
    for ev in line.events:
        s = ev.start_ns * 1e-9
        yield ev.name, s, s + ev.duration_ns * 1e-9


def short_name(op: str) -> str:
    """The TPU's op events are named by the whole HLO instruction
    (``%fusion.7 = bf16[...] fusion(...), kind=kOutput, calls=...``):
    -> the instruction's own name, ``%fusion.7``."""
    return op.split(" = ", 1)[0]


def reduce_trace(path: str, layout: dict = TPU_LAYOUT,
                 span_names=(), chips: int | None = None) -> Reduction:
    """``span_names``: the host annotations to read (the window marker is
    always read).  ``chips``: how many device planes must be there."""
    data = load_profile(path)
    wanted = set(span_names) | {WINDOW_SPAN}
    host_spans, dev_planes = [], []
    for plane in data.planes:
        if plane.name == layout["host_plane"]:
            for line in plane.lines:
                host_spans += [(n, s, e) for n, s, e in _events(line)
                               if n in wanted]
        if plane.name.startswith(layout["device_plane_prefix"]):
            dev_planes.append(plane)
    per_chip = []
    for plane in dev_planes:
        ops, modules = [], []
        for line in plane.lines:
            if layout["op_lines"] is None:
                ops += [ev for ev in _events(line) if ev[0] not in wanted]
            elif line.name in layout["op_lines"]:
                ops += list(_events(line))
            if line.name in layout["module_lines"]:
                modules += list(_events(line))
        if ops:
            per_chip.append((ops, modules))
    if not per_chip:
        raise ValueError(f"{path}: no device operation in the trace "
                         f"(planes: {[p.name for p in data.planes]})")
    if chips is not None and len(per_chip) != chips:
        raise ValueError(f"{path}: {len(per_chip)} device planes with "
                         f"operations, the cell uses {chips}")
    marks = [(s, e) for n, s, e in host_spans if n == WINDOW_SPAN]
    if marks:
        lo, hi = min(s for s, _ in marks), max(e for _, e in marks)
    else:
        lo = min(s for ops, _ in per_chip for _, s, _ in ops)
        hi = max(e for ops, _ in per_chip for _, _, e in ops)
    red = Reduction(window_s=hi - lo, busy_s=0.0, chips=len(per_chip))
    for i, (ops, modules) in enumerate(per_chip):
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
                  if e > lo and s < hi]
        red.busy_s += union_length([(s, e) for _, s, e in inside]) \
            / len(per_chip)
        for n, s, e in inside:
            short = short_name(n)
            red.op_text.setdefault(short, n)
            red.op_seconds[short] = red.op_seconds.get(short, 0.0) \
                + (e - s) / len(per_chip)
        for n, s, e in modules:
            if e > lo and s < hi and e > s:
                red.module_seconds.setdefault(n, []).append(e - s)
                red.module_inside[n] = red.module_inside.get(n, 0.0) + (
                    (min(e, hi) - max(s, lo)) / (e - s))
        if i == 0:
            gaps = gaps_of([(s, e) for _, s, e in inside], lo, hi)
            others = [sp for sp in host_spans if sp[0] != WINDOW_SPAN]
            red.gap_seconds = attribute_gaps(gaps, others)
    for n, s, e in host_spans:
        if n != WINDOW_SPAN and e > lo and s < hi:
            red.host_spans.setdefault(n, []).append(e - s)
    return red


def dump_layout(path: str, limit: int = 6) -> str:
    """Planes, lines and a few event names: for the look by hand."""
    out = []
    for plane in load_profile(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            names = []
            for ev in events:
                if ev.name not in names:
                    names.append(ev.name)
                if len(names) >= limit:
                    break
            out.append(f"  line {line.name!r}: {len(events)} events, e.g. "
                       f"{names}")
    return "\n".join(out)
