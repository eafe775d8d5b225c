"""Device time by ``jax.named_scope``, which ``trace_reduce.Reduction``
does not hold.  A TPU trace names an operation by its HLO instruction
alone (``%fusion.12 = bf16[...] fusion(...)``: no scope, PERF.md section
3); the compiled program's text carries the same instruction with
``metadata={op_name="jit(...)/.../text_hybrid/ssd/dot_general" ...}``.  So:
the program's text gives instruction -> ``op_name`` (:func:`instruction_ops`),
the trace gives instruction -> intervals inside the executions of one jitted
program, and a scope's time is the union of the intervals of the
instructions whose ``op_name`` holds it (a ``while`` and the operations of
its body overlap: the union counts them once).

A fusion carries the ``op_name`` of ONE of the operations fused into it:
a norm fused into the product that follows is counted with the product.
"""

from __future__ import annotations

import bisect
import re

from benchmarks import trace_reduce

_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?(%[^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def instruction_ops(hlo_text: str) -> dict:
    """instruction name -> [(the line without its metadata, op_name), ...]
    over every computation of the module (names are unique in a module;
    the list holds what several modules gave the same name)."""
    out: dict = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        op = _OP_NAME.search(line)
        head = line.strip().removeprefix("ROOT ").split(", metadata=", 1)[0]
        out.setdefault(m.group(1), []).append(
            (head, op.group(1) if op else ""))
    return out


def merge(maps) -> dict:
    out: dict = {}
    for one in maps:
        for name, entries in one.items():
            out.setdefault(name, []).extend(entries)
    return out


def op_name_of(event_text: str, ops: dict) -> str:
    """The ``op_name`` of a trace event (its whole instruction text): by
    the instruction's name, and where several programs use the name, the
    one whose line starts as the event does."""
    entries = ops.get(trace_reduce.short_name(event_text))
    if not entries:
        return ""
    if len({op for _, op in entries}) == 1:
        return entries[0][1]
    probe = event_text[:160]
    for head, op in entries:
        if head[:160] == probe:
            return op
    return entries[0][1]


def scope_seconds(path: str, module: str, ops: dict, markers,
                  layout: dict = trace_reduce.TPU_LAYOUT):
    """Over the executions of the jitted program whose name holds
    ``module`` that overlap the traced window, per chip (mean): ->
    {"inside": {marker: s, "*": s}, "whole": {marker: s, "*": s},
    "ops": {instruction: [s, op_name]}} — ``inside``: the part within the
    window; ``whole``: those executions from start to end; ``*``: every
    operation of the program.  None where the trace has no such
    execution (or no line of programs, as on the CPU)."""
    data = trace_reduce.load_profile(path)
    marks, planes = [], []
    for plane in data.planes:
        if plane.name == layout["host_plane"]:
            for line in plane.lines:
                marks += [(s, e) for n, s, e in trace_reduce._events(line)
                          if n == trace_reduce.WINDOW_SPAN]
        if plane.name.startswith(layout["device_plane_prefix"]):
            planes.append(plane)
    if not marks or not layout["module_lines"]:
        return None
    lo, hi = min(s for s, _ in marks), max(e for _, e in marks)
    keys = tuple(markers) + ("*",)
    inside = {k: 0.0 for k in keys}
    whole = {k: 0.0 for k in keys}
    by_op: dict = {}
    chips = 0
    for plane in planes:
        events, runs = [], []
        for line in plane.lines:
            if line.name in layout["op_lines"]:
                events += list(trace_reduce._events(line))
            if line.name in layout["module_lines"]:
                runs += [(s, e) for n, s, e in trace_reduce._events(line)
                         if module in n and e > lo and s < hi]
        if not runs:
            continue
        chips += 1
        runs.sort()
        starts = [s for s, _ in runs]
        spans = {k: [] for k in keys}
        for text, s, e in events:
            at = bisect.bisect_right(starts, s) - 1
            if at < 0 or s >= runs[at][1]:
                continue
            op = op_name_of(text, ops)
            short = trace_reduce.short_name(text)
            seen = by_op.setdefault(short, [0.0, op])
            seen[0] += e - s
            for k in keys:
                if k == "*" or k in op:
                    spans[k].append((s, e))
        for k in keys:
            whole[k] += trace_reduce.union_length(spans[k])
            inside[k] += trace_reduce.union_length(
                [(max(s, lo), min(e, hi)) for s, e in spans[k]
                 if e > lo and s < hi])
    if not chips:
        return None
    return {"inside": {k: v / chips for k, v in inside.items()},
            "whole": {k: v / chips for k, v in whole.items()},
            "ops": {k: [v[0] / chips, v[1]] for k, v in by_op.items()}}
