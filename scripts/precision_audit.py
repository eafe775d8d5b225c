#!/usr/bin/env python
"""Precision-flow audit CLI (graftlint Pass 5 — analysis/numerics.py).

Usage:
    python scripts/precision_audit.py            # audit entries, write NUMERICS.md
    python scripts/precision_audit.py --check    # exit 1 on GL016/17/18 findings
    python scripts/precision_audit.py --what-if --dtype bfloat16 \
        --batch 256 --frames 32 --size 224       # the static half of the
                                                 # bf16-training decision
    python scripts/precision_audit.py --export /path/to/export  # quant
                                                 # readiness over an artifact

The default mode walks every registered trace-invariant entry's jaxpr on
the hermetic CPU mesh and writes the per-entry dtype census, the named
cast inventory and the f32-residency audit to NUMERICS.md — plus the
bf16 what-if table for the milnce train step at the paper operating
point, and a quantization-readiness report (per-layer weight dynamic
range, outlier ratio, per-channel-scale verdicts — the ROADMAP item 5
feed) over an export artifact.  ``--check`` is the CI half: the same
walk gated against the pins in analysis/numerics.py (GL016 low-precision
accumulation, GL017 exp-domain, GL018 census/cast drift), wired into
``graft_lint --check`` and the README verify recipe; on drift it prints
the paste-ready re-pin dicts.

``--what-if`` re-runs GL016/GL018 on a HYPOTHETICAL operating point
(sibling of ``mem_plan --what-if``, same traced program): ``--dtype
bfloat16`` names every reduction that would lose its f32 accumulator
and every log-domain operand that would demote — before anyone flips
the model dtype on a chip.
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def _parse_mesh(spec: str) -> dict:
    """'data=4,model=2' -> {'data': 4, 'model': 2} ('' -> {'data': 8},
    the hermetic default).  Malformed items fail here, not as a silently
    1-sized axis."""
    if not spec:
        return {"data": 8}
    out: dict = {}
    for item in spec.split(","):
        if "=" not in item:
            raise ValueError(f"mesh item {item!r}: expected axis=N "
                             "(e.g. data=4,model=2)")
        ax, n = item.split("=", 1)
        out[ax.strip()] = int(n)
    return out


def _force_devices(n: int) -> None:
    """Must run before any jax import: the what-if mesh needs that many
    virtual CPU devices in the hermetic platform."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")


HEADER = ("<!-- (auto-written by scripts/precision_audit.py — do not "
          "hand-edit; regenerate with "
          "`python scripts/precision_audit.py`) -->\n")

# The paper operating point the what-if section audits (BENCH_NOTES.md
# headline: batch 256, 32f@224) on the 8-way data mesh the script
# forces.
WHAT_IF_POINT = dict(batch=256, frames=32, size=224)

# Quantization-readiness rule: single-sourced from the quantizer
# (milnce_tpu/quant/quantize.py), so the committed NUMERICS.md verdicts
# and the calibration defaults that READ them back
# (quant/calibrate.py read_numerics_verdicts) can never drift apart.
# quantize.py is numpy-only at import time, so this import is safe
# before _force_devices/jax.
from milnce_tpu.quant.quantize import (OUTLIER_FRACTION,  # noqa: E402
                                       PER_CHANNEL_RATIO,
                                       weight_readiness_row)

# Deterministic short-train recipe for the committed readiness table:
# the verdicts must come from TRAINED weights (an init-table verdict
# says nothing about the ranges training grows — ISSUE 19), and regen
# must reproduce it bit-for-bit without a checkpoint lying around.
_TRAIN_STEPS = 25


def quant_readiness(npz_path: str) -> list:
    """Per-layer weight statistics for int8 planning: dynamic range,
    outlier ratio, per-channel spread — pure host numpy, no jax.  One
    row per QUANTIZABLE float param (ndim >= 2 — the quantizer's own
    eligibility rule; biases/BN vectors stay f32 and never get a
    verdict, so the table is exactly the set `milnce-quantize` reads
    back as calibration defaults)."""
    import numpy as np

    rows = []
    with np.load(npz_path) as z:
        for key in sorted(z.files):
            if not key.startswith("params/"):
                continue
            arr = np.asarray(z[key])
            if arr.dtype.kind != "f" or arr.size == 0 or arr.ndim < 2:
                continue
            rows.append(weight_readiness_row(key, arr))
    return rows


def _tiny_export(out_dir: str) -> str:
    """Deterministic short-TRAIN export for the committed
    quant-readiness table: the analysis entries' PRNGKey(0) state
    driven ``_TRAIN_STEPS`` MIL-NCE steps over fixed-seed synthetic
    batches (the trace-invariant ``batch(seed)`` generator), then
    exported.  Trained ranges are what the int8 verdicts are FOR —
    init-time ranges are an accident of the initializer — and the
    fixed seeds keep regen reproducible with no checkpoint dependency."""
    import jax

    from milnce_tpu.analysis.trace_invariants import (_FRAMES, _SIZE,
                                                      _TINY, _WORDS,
                                                      _setup)
    from milnce_tpu.config import ModelConfig
    from milnce_tpu.serving.export import (ARRAYS_FILE,
                                           export_inference_checkpoint)
    from milnce_tpu.train.step import make_train_step

    model, opt, mesh, state, batch = _setup()
    step = make_train_step(model, opt, mesh, donate=False)
    for seed in range(_TRAIN_STEPS):
        state, _metrics = step(state, *batch(seed))
    state = jax.device_get(state)
    mcfg = ModelConfig(embedding_dim=_TINY["embedding_dim"],
                       vocab_size=_TINY["vocab_size"],
                       word_embedding_dim=_TINY["word_embedding_dim"],
                       text_hidden_dim=_TINY["text_hidden_dim"],
                       inception_blocks=_TINY["inception_blocks"])
    export_inference_checkpoint(
        out_dir, state.params, state.batch_stats, mcfg,
        max_words=_WORDS, video_shape=(_FRAMES, _SIZE, _SIZE, 3),
        step=_TRAIN_STEPS,
        source=f"precision_audit deterministic {_TRAIN_STEPS}-step train "
               "(PRNGKey(0) init, fixed-seed synthetic batches)")
    return os.path.join(out_dir, ARRAYS_FILE)


_CENSUS_COLS = ("f32", "bf16", "f16", "i8", "i32", "u8", "bool")


def _census_cells(census: dict) -> list:
    cells = [f"{census.get(c, 0):,}" for c in _CENSUS_COLS]
    other = sum(b for k, b in census.items() if k not in _CENSUS_COLS)
    cells.append(f"{other:,}" if other else "0")
    return cells


def _render_report(audits: dict, results, what_ifs=None,
                   quant_rows=None, quant_src: str = "") -> str:
    lines = [HEADER, "# NUMERICS — static precision-flow audit", ""]
    lines.append(
        "Per-entry dtype census, named cast inventory and f32-residency "
        "audit from the jaxpr dtype-flow walk (graftlint Pass 5, "
        "`milnce_tpu/analysis/numerics.py`) on the hermetic CPU meshes. "
        " Pinned by `graft_lint --check` (GL016/GL017/GL018); model + "
        "known approximations: ANALYSIS.md \"Pass 5\".")
    lines.append("")
    lines.append("## Dtype census (program buffer bytes by dtype)")
    lines.append("")
    lines.append("| entry | mesh | " + " | ".join(_CENSUS_COLS)
                 + " | other | casts | unguarded exp | census hash |")
    lines.append("|---|---|" + "---|" * (len(_CENSUS_COLS) + 4))
    for name, a in audits.items():
        cells = _census_cells(a.census)
        lines.append(f"| {name} | {a.mesh} | " + " | ".join(cells)
                     + f" | {sum(a.casts.values())} | {len(a.exp_sites)} "
                     f"| `{a.census_hash()}` |")
    lines.append("")
    lines.append("## Cast inventory (every convert_element_type, named)")
    lines.append("")
    lines.append("The recurring boundaries: `u8->f32 @ video` is input "
                 "normalization (the ONE place raw frames widen), "
                 "`bool->f32 @ eq` the masked-mean denominators, "
                 "`i32->f32 @ .../count` the schedule step feeding the "
                 "learning rate; `@ nest-boundary` routes enter through "
                 "scan/grad-cache body invars.  An appearing or "
                 "vanishing row is a GL018 diff — re-pin consciously.")
    lines.append("")
    lines.append("| entry | cast | n |")
    lines.append("|---|---|---|")
    for name, a in audits.items():
        if not a.casts:
            lines.append(f"| {name} | (none — cast-free program) | 0 |")
        for route in sorted(a.casts):
            lines.append(f"| {name} | `{route}` | {a.casts[route]} |")
    lines.append("")
    lines.append("## f32-residency audit")
    lines.append("")
    total_resident = sum(len(a.f32_residency) for a in audits.values())
    total_bad = sum(len(a.residency_violations) for a in audits.values())
    lines.append(
        f"- leaves in the residency set (BatchNorm statistics + "
        f"optimizer moments): {total_resident} across "
        f"{len(audits)} entries — **all f32**" if not total_bad else
        f"- residency violations: **{total_bad}** (see check table)")
    lines.append("- log-domain accumulators (log/log1p operands — the "
                 "logsumexp/loss chain): all f32 on every registered "
                 "entry" if not total_bad else "")
    lines.append("")
    lines.append("Verdict: the f32 residency GL015 flagged on the bf16 "
                 "model (BatchNorm intermediates, PERF.md \"Batch "
                 "cliffs\") is LOAD-BEARING — BN statistics, Adam "
                 "moments and the loss's log-domain chain must stay "
                 "f32; the bf16 what-if below shows exactly what breaks "
                 "when the model dtype flips with no f32 islands.")
    lines.append("")
    lines.append("## Pass 5 checks")
    lines.append("")
    bad = [r for r in results if not r.ok]
    lines.append(f"- checks: {len(results)}, failing: **{len(bad)}**")
    lines.append("")
    lines.append("| entry | check | status |")
    lines.append("|---|---|---|")
    for r in results:
        status = "ok" if r.ok else f"**FAIL** — {r.detail}"
        lines.append(f"| {r.entry} | {r.check} | {status} |")
    lines.append("")
    if what_ifs:
        lines.append("## bf16 what-if — the milnce train step at the "
                     "paper operating point")
        lines.append("")
        point = WHAT_IF_POINT
        lines.append(
            f"`--what-if` at batch {point['batch']}, "
            f"{point['frames']}f@{point['size']} on the 8-way data mesh "
            "(the BENCH_NOTES.md headline point), f32 vs bf16 — the "
            "static half of the mixed-precision decision: which "
            "reductions lose their f32 accumulator (GL016), which "
            "log-domain operands demote, how the cast structure moves.")
        lines.append("")
        lines.append("| model dtype | f32 bytes | bf16 bytes | GL016 "
                     "sites | log-domain demotions | casts |")
        lines.append("|---|---|---|---|---|---|")
        for a in what_ifs:
            demote = sum("log" in v for v in a.residency_violations)
            lines.append(
                f"| {a.entry} | {a.census.get('f32', 0):,} "
                f"| {a.census.get('bf16', 0):,} "
                f"| {len(a.gl016_sites)} | {demote} "
                f"| {sum(a.casts.values())} |")
        lines.append("")
        bf16 = what_ifs[-1]
        if bf16.gl016_sites:
            from collections import Counter

            lines.append("Top bf16 low-precision accumulations "
                         "(grouped; each needs "
                         "`preferred_element_type=f32` or an f32 "
                         "island before the model dtype flips):")
            lines.append("")
            for site, n in Counter(bf16.gl016_sites).most_common(10):
                lines.append(f"- {n}x `{site}`")
            lines.append("")
    if quant_rows is not None:
        lines.append("## Quantization readiness (the int8 edge tier's "
                     "calibration defaults)")
        lines.append("")
        n_pc = sum(r["per_channel"] for r in quant_rows)
        lines.append(
            f"Host-side numpy over `{quant_src}`: per-layer weight "
            "dynamic range, >6-sigma outlier ratio and per-output-"
            "channel absmax spread, via the quantizer's own readiness "
            "rule (`milnce_tpu/quant/quantize.py` — single source).  "
            "Verdict `per-channel` = the channel range ratio exceeds "
            f"{PER_CHANNEL_RATIO:g}x (or outliers exceed "
            f"{OUTLIER_FRACTION:g}) — one per-tensor int8 scale would "
            "waste log2(ratio) of the 8 bits on quiet channels.  "
            f"{n_pc}/{len(quant_rows)} layers need per-channel scales.  "
            "`milnce-quantize` (quant/calibrate.py) reads these "
            "verdicts back from this table as its per-channel defaults "
            "— SERVING.md \"Edge tier\".")
        lines.append("")
        lines.append("| layer | shape | absmax | std | outliers>6σ "
                     "| channel ratio | int8 verdict |")
        lines.append("|---|---|---|---|---|---|---|")
        for r in sorted(quant_rows, key=lambda r: -r["channel_range_ratio"]):
            verdict = ("**per-channel**" if r["per_channel"]
                       else "per-tensor ok")
            lines.append(
                f"| `{r['key']}` | {r['shape']} | {r['absmax']:.3f} "
                f"| {r['std']:.4f} | {r['outlier_ratio']:.2%} "
                f"| {r['channel_range_ratio']:.1f}x | {verdict} |")
        lines.append("")
    return "\n".join(lines)


def _print_repin(audits: dict) -> None:
    """Both re-pin dicts, ready to paste — a DELIBERATE precision
    change (GL018 census or cast drift) should cost one copy, not
    archaeology."""
    print("\n# current values (re-pin consciously if intended):")
    print("EXPECTED_DTYPE_CENSUS = {")
    for name, a in audits.items():
        print(f'    "{name}": {a.census},')
    print("}")
    print("EXPECTED_CASTS = {")
    for name, a in audits.items():
        print(f'    "{name}": {a.casts},')
    print("}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--check", action="store_true",
                    help="exit 1 on any GL016/GL017/GL018 finding")
    ap.add_argument("--entries", default="",
                    help="comma list of entries (default: all registered)")
    ap.add_argument("--report", default=os.path.join(_REPO, "NUMERICS.md"),
                    help="report path ('' to skip writing)")
    ap.add_argument("--what-if", action="store_true",
                    help="audit one hypothetical operating point instead "
                         "of the registered entries")
    ap.add_argument("--batch", type=int, default=WHAT_IF_POINT["batch"])
    ap.add_argument("--frames", type=int, default=WHAT_IF_POINT["frames"])
    ap.add_argument("--size", type=int, default=WHAT_IF_POINT["size"])
    ap.add_argument("--words", type=int, default=20)
    ap.add_argument("--k", type=int, default=5)
    ap.add_argument("--dtype", default="bfloat16",
                    help="model dtype for --what-if (the bf16 decision "
                         "axis; 'float32' gives the baseline)")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--mesh", default="",
                    help="'data=4,model=2' (what-if; '' = 8-way data)")
    ap.add_argument("--preset", default="full", choices=["full", "tiny"],
                    help="model preset for --what-if (tiny = the test "
                         "config, seconds to trace)")
    ap.add_argument("--export", default="", dest="export_dir",
                    help="export artifact dir for the quantization-"
                         "readiness report (default: a deterministic "
                         "tiny export built in a temp dir)")
    ap.add_argument("--no-what-if", action="store_true",
                    help="skip the bf16 what-if section of the report "
                         "(full-preset tracing is the slow half of "
                         "regen)")
    ap.add_argument("--no-quant", action="store_true",
                    help="skip the quantization-readiness section")
    args = ap.parse_args(argv)
    # Census columns use the short names (f32/bf16/...), so accept them
    # here too — numpy only understands the long spellings.
    args.dtype = {"f32": "float32", "bf16": "bfloat16", "f16": "float16",
                  "f64": "float64"}.get(args.dtype, args.dtype)

    mesh_axes = _parse_mesh(args.mesh)
    import math

    _force_devices(math.prod(mesh_axes.values()) if args.what_if else 8)
    import jax

    jax.config.update("jax_platforms", "cpu")
    from milnce_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()

    from milnce_tpu.analysis import numerics

    if args.what_if:
        a = numerics.what_if_audit(
            batch=args.batch, frames=args.frames, size=args.size,
            words=args.words, k=args.k, dtype=args.dtype,
            grad_accum=args.grad_accum, mesh_axes=mesh_axes,
            preset=args.preset)
        print(f"{a.entry} on {a.mesh}:")
        print(f"  census: " + ", ".join(
            f"{k}={v:,} B" for k, v in sorted(a.census.items())))
        print(f"  casts: {sum(a.casts.values())} "
              f"({len(a.casts)} distinct routes)")
        print(f"  GL016 low-precision accumulations: "
              f"{len(a.gl016_sites)}")
        from collections import Counter

        for site, n in Counter(a.gl016_sites).most_common(10):
            print(f"    {n}x {site}")
        print(f"  unguarded exp sites: {len(a.exp_sites)}")
        for s in a.exp_sites[:5]:
            print(f"    {s}")
        demote = [v for v in a.residency_violations]
        print(f"  f32-residency violations: {len(demote)}")
        for v in demote[:5]:
            print(f"    {v}")
        return 0

    entries = [e for e in args.entries.split(",") if e] or None
    audits = numerics.audit_all(entries)
    results = numerics.run_numerics_checks(entries, audits=audits)
    for r in results:
        print(r.format())
    n_bad = sum(not r.ok for r in results)
    if n_bad:
        _print_repin(audits)
    if args.report:
        what_ifs = None
        if not args.no_what_if:
            what_ifs = [
                numerics.what_if_audit(dtype=dtype, **WHAT_IF_POINT)
                for dtype in ("float32", "bfloat16")]
        quant_rows, quant_src = None, ""
        if not args.no_quant:
            if args.export_dir:
                from milnce_tpu.serving.export import ARRAYS_FILE

                npz = os.path.join(args.export_dir, ARRAYS_FILE)
                quant_src = npz
            else:
                tmp = tempfile.mkdtemp(prefix="precision_audit_export_")
                npz = _tiny_export(tmp)
                quant_src = (f"deterministic tiny TRAINED export "
                             f"(PRNGKey(0) init + {_TRAIN_STEPS} "
                             "fixed-seed MIL-NCE steps, milnce-export "
                             "format)")
            quant_rows = quant_readiness(npz)
        with open(args.report, "w") as fh:
            fh.write(_render_report(audits, results, what_ifs=what_ifs,
                                    quant_rows=quant_rows,
                                    quant_src=quant_src))
        print(f"report: {args.report}")
    print(f"precision_audit: {len(audits)} entries audited, "
          f"{n_bad} finding(s)")
    return 1 if (args.check and n_bad) else 0


if __name__ == "__main__":
    raise SystemExit(main())
