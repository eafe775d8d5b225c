#!/usr/bin/env python
"""Static HBM planner CLI (graftlint Pass 4 — analysis/memplan.py).

Usage:
    python scripts/mem_plan.py                   # plan entries, write MEMPLAN.md
    python scripts/mem_plan.py --check           # exit 1 on GL013/14/15 findings
    python scripts/mem_plan.py --what-if --batch 256 --mesh data=4,model=2 \
        --hbm-gib 16                             # operating-point prediction;
                                                 # exit 1 when it doesn't fit

The default mode walks every registered trace-invariant entry on the
hermetic CPU mesh and writes the per-entry peak table + top contributors
to MEMPLAN.md.  ``--check`` is the CI half: the same walk gated against
the pins in analysis/memplan.py (GL013 peak budget, GL014 donation
audit, GL015 top-contributor attribution), wired into
``graft_lint --check`` and the README verify recipe.

``--what-if`` answers "will this config fit?" WITHOUT a chip: the full
(or tiny) preset model is built at the requested batch/frames/mesh,
traced abstractly (``jax.eval_shape`` state + ShapeDtypeStruct inputs —
no device bytes move), and the predicted per-chip peak is compared
against ``--hbm-gib``.  A config that doesn't fit is REFUSED with a
nonzero exit naming the top-3 contributors — the 192-batch-cliff /
curriculum-ladder / FSDP-threshold triage loop, minus the chip time.
"""

from __future__ import annotations

import argparse
import os
import sys

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


HEADER = ("<!-- (auto-written by scripts/mem_plan.py — do not hand-edit; "
          "regenerate with `python scripts/mem_plan.py`) -->\n")

# The committed curriculum operating-point ladder (PERF.md "Curriculum
# training"): the staged recipe recommended for the paper's full run,
# pre-flighted here so the 32f@224 final stage's fit is triaged before
# any chip time.  Regen recomputes every row, so the table tracks the
# current model + planner.  The ga=1 final-stage row is kept
# deliberately: it documents WHY the recipe carries grad_accum=8.
#   (label, frames, size, batch, grad_accum)
CURRICULUM_LADDER = (
    ("stage 0", 4, 64, 512, 1),
    ("stage 1", 8, 112, 256, 1),
    ("stage 2 (ga=1, naive)", 32, 224, 256, 1),
    ("stage 2 (ga=8)", 32, 224, 256, 8),
)
LADDER_MESH = {"data": 4, "model": 2}   # v5e-8 slice
LADDER_HBM_GIB = 16.0


def _plan_ladder(memplan) -> list:
    """(label, shape, batch, ga, peak_bytes, fits, top_label) per ladder
    row — the curriculum section of MEMPLAN.md."""
    rows = []
    for label, frames, size, batch, ga in CURRICULUM_LADDER:
        p = memplan.what_if_step(
            batch=batch, frames=frames, size=size, grad_accum=ga,
            mesh_axes=dict(LADDER_MESH))
        fits, _ = memplan.budget_verdict(p, LADDER_HBM_GIB)
        top = (f"{p.contributors[0][0]} "
               f"({p.contributors[0][1] / 2**20:.0f} MiB)"
               if p.contributors else "-")
        rows.append((label, f"{frames}f@{size}", batch, ga,
                     p.peak_bytes, fits, top))
    return rows


def _render_memplan(plans: dict, results, ladder=None) -> str:
    lines = [HEADER, "# MEMPLAN — static per-chip HBM plan", ""]
    lines.append(
        "Per-entry peak device bytes from jaxpr live-range analysis "
        "(graftlint Pass 4, `milnce_tpu/analysis/memplan.py`) on the "
        "hermetic CPU meshes — sharding-aware (bytes / mesh-axis extent "
        "per the committed specs) and donation-aware (the TPU path's "
        "`donate_argnums` applied).  Pinned by `graft_lint --check` "
        "(GL013/GL015); model + known approximations: ANALYSIS.md "
        "\"Pass 4\".")
    lines.append("")
    lines.append("| entry | mesh | peak/chip | args/chip | outs/chip "
                 "| top contributors |")
    lines.append("|---|---|---|---|---|---|")
    for name, p in plans.items():
        top = "<br>".join(f"{label} ({b / 2**20:.2f} MiB)"
                          for label, b in p.contributors[:3])
        lines.append(
            f"| {name} | {p.mesh} | {p.peak_bytes / 2**20:.2f} MiB "
            f"| {p.arg_bytes / 2**20:.2f} MiB "
            f"| {p.out_bytes / 2**20:.2f} MiB | {top} |")
    lines.append("")
    lines.append("## Sharding attribution")
    lines.append("")
    lines.append("Donated arg leaves per entry (the GL014 audit "
                 "surface):")
    lines.append("")
    for name, p in plans.items():
        n_don = len(p.donated)
        lines.append(f"- `{name}`: {n_don} donated leaves"
                     + (" (none — inference entry)" if not n_don else
                        f" (state tree; first: `{p.donated[0]}`)"))
    lines.append("")
    lines.append("## Pass 4 checks")
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
    lines.append("What-if mode (`python scripts/mem_plan.py --what-if "
                 "--batch 256 --mesh data=4,model=2 --hbm-gib 16`) "
                 "predicts TPU operating-point footprints from CPU "
                 "traces and refuses configs that don't fit — see "
                 "PERF.md \"Memory planning\".")
    lines.append("")
    if ladder:
        mesh = "x".join(str(n) for n in LADDER_MESH.values())
        axes = ",".join(LADDER_MESH)
        lines.append("## Curriculum ladder (operating points)")
        lines.append("")
        lines.append(
            f"The staged recipe from PERF.md \"Curriculum training\", "
            f"pre-flighted on {mesh} ({axes}) against the v5e "
            f"{LADDER_HBM_GIB:.0f} GiB/chip budget — the same per-stage "
            "prediction `run_training` performs at startup before any "
            "stage is traced.  One invocation reproduces it: "
            "`python scripts/mem_plan.py --what-if --curriculum "
            "'<spec>' --mesh data=4,model=2 --hbm-gib 16`.  The naive "
            "ga=1 final stage is listed to show the triage: 32f@224 at "
            "batch 256 only fits with gradient accumulation.")
        lines.append("")
        lines.append("| stage | shape | batch | grad-accum | peak/chip "
                     "| fits 16 GiB | top contributor |")
        lines.append("|---|---|---|---|---|---|---|")
        for label, shape, batch, ga, peak, fits, top in ladder:
            verdict = "yes" if fits else "**NO — refused at pre-flight**"
            lines.append(f"| {label} | {shape} | {batch} | {ga} "
                         f"| {peak / 2**30:.3f} GiB | {verdict} "
                         f"| {top} |")
        lines.append("")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--check", action="store_true",
                    help="exit 1 on any GL013/GL014/GL015 finding")
    ap.add_argument("--entries", default="",
                    help="comma list of entries (default: all registered)")
    ap.add_argument("--report", default=os.path.join(_REPO, "MEMPLAN.md"),
                    help="report path ('' to skip writing)")
    ap.add_argument("--what-if", action="store_true",
                    help="predict one operating point instead of "
                         "planning the registered entries")
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--frames", type=int, default=32)
    ap.add_argument("--size", type=int, default=224)
    ap.add_argument("--words", type=int, default=20)
    ap.add_argument("--k", type=int, default=5)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--loss-impl", default="dense",
                    choices=["dense", "chunked", "auto"],
                    help="MIL-NCE impl for --what-if (loss.milnce_impl): "
                         "predict the same operating point under the "
                         "dense cube vs the chunked stream")
    ap.add_argument("--milnce-chunk", type=int, default=0,
                    help="chunked-impl streamed block size (0 = the "
                         "milnce_default_chunk rule)")
    ap.add_argument("--curriculum", default="",
                    help="with --what-if: a train.curriculum spec (or "
                         "JSON artifact path) — predict EVERY stage as "
                         "its own operating point in one invocation and "
                         "exit 1 if any stage exceeds --hbm-gib; "
                         "--grad-accum/--words/--k/--dtype apply to all "
                         "stages")
    ap.add_argument("--mesh", default="",
                    help="'data=4,model=2' (what-if; '' = 8-way data)")
    ap.add_argument("--hbm-gib", type=float, default=16.0,
                    help="per-chip HBM budget the what-if verdict gates "
                         "against (v5e 16, v3 32, v5p 95)")
    ap.add_argument("--preset", default="full", choices=["full", "tiny"],
                    help="model preset for --what-if (tiny = the test "
                         "config, seconds to trace)")
    args = ap.parse_args(argv)

    mesh_axes = _parse_mesh(args.mesh)
    import math

    _force_devices(math.prod(mesh_axes.values()) if args.what_if else 8)
    import jax

    jax.config.update("jax_platforms", "cpu")
    from milnce_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()

    from milnce_tpu.analysis import memplan

    if args.what_if and args.curriculum:
        # stdlib parser (train/curriculum.py imports no jax at module
        # scope beyond what this process already initialised)
        from milnce_tpu.train.curriculum import parse_curriculum

        stages = parse_curriculum(args.curriculum,
                                  default_batch_size=args.batch)
        rows, refused = [], []
        for i, st in enumerate(stages):
            plan = memplan.what_if_step(
                batch=st.batch_size, frames=st.num_frames,
                size=st.resolution, words=args.words, k=args.k,
                dtype=args.dtype, grad_accum=args.grad_accum,
                mesh_axes=mesh_axes, preset=args.preset,
                loss_impl=args.loss_impl,
                milnce_chunk=args.milnce_chunk)
            fits, msg = memplan.budget_verdict(plan, args.hbm_gib)
            rows.append((i, st, plan, fits))
            if not fits:
                refused.append((i, st, msg))
        print("| stage | shape | batch | peak/chip | fits "
              f"{args.hbm_gib:g} GiB |")
        print("|---|---|---|---|---|")
        for i, st, plan, fits in rows:
            print(f"| {i} | {st.num_frames}f@{st.resolution} "
                  f"| {st.batch_size} | {plan.peak_bytes / 2**30:.3f} "
                  f"GiB | {'yes' if fits else '**NO**'} |")
        for i, st, msg in refused:
            print(f"\nstage {i} ({st.label()}) REFUSED: {msg}")
        return 1 if refused else 0

    if args.what_if:
        plan = memplan.what_if_step(
            batch=args.batch, frames=args.frames, size=args.size,
            words=args.words, k=args.k, dtype=args.dtype,
            grad_accum=args.grad_accum, mesh_axes=mesh_axes,
            preset=args.preset, loss_impl=args.loss_impl,
            milnce_chunk=args.milnce_chunk)
        fits, msg = memplan.budget_verdict(plan, args.hbm_gib)
        print(msg)
        return 0 if fits else 1

    entries = ([e for e in args.entries.split(",") if e]
               or None)
    plans = memplan.plan_all(entries)
    results = memplan.run_memplan_checks(entries, plans=plans)
    for r in results:
        print(r.format())
    n_bad = sum(not r.ok for r in results)
    if n_bad:
        # BOTH re-pin dicts, ready to paste — a DELIBERATE change (GL013
        # peak drift or GL015 contributor drift) should cost one copy,
        # not archaeology
        print("\n# current values (re-pin consciously if intended):")
        print("EXPECTED_PEAK_BYTES = {")
        for name, p in plans.items():
            print(f'    "{name}": {p.peak_bytes},')
        print("}")
        print("EXPECTED_TOP_CONTRIBUTORS = {")
        for name, p in plans.items():
            tops = ",\n        ".join(f'"{label}"' for label in p.top())
            print(f'    "{name}": (\n        {tops}),')
        print("}")
    if args.report:
        # recompute the committed curriculum ladder alongside the entry
        # plans (~9s/row of pure CPU tracing) so the operating-point
        # table can never go stale against the model
        ladder = _plan_ladder(memplan)
        with open(args.report, "w") as fh:
            fh.write(_render_memplan(plans, results, ladder=ladder))
        print(f"report: {args.report}")
    print(f"mem_plan: {len(plans)} entries planned, {n_bad} finding(s)")
    return 1 if (args.check and n_bad) else 0


if __name__ == "__main__":
    raise SystemExit(main())
