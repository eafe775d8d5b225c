"""XLA flag probe: re-measure the winning train-step operating point
under candidate XLA:TPU flags.

The measured MFU (18.2%, BENCH_NOTES.md) sits far under the analytic
roofline ceiling (~63%, PERF.md) and the gap is scheduling/tiling —
exactly the territory XLA flags move.  Each candidate flag set runs in
its own time-limited bench config child (bench._run_config: fresh
process, TERM-first stop, one child at a time — this parent never
imports JAX), so a flag that hangs the compiler costs one timeout, and
a flag the compiler rejects surfaces as a tagged error row WITH the
child's stderr, not a crash.

Round-5 lesson (XLA_FLAGS_PROBE.md): every non-baseline row died
``rc=1, no record`` because the ``--xla_tpu_*`` knobs went into
``XLA_FLAGS``, which the CLIENT-side XLA flag parser also reads — and
it hard-aborts the process on any flag its own build doesn't know
(the TPU-compiler knobs live in libtpu, not the client).  The fix is a
flag ROUTER (:func:`split_flags`): ``--xla_tpu_*`` candidates ride
``LIBTPU_INIT_ARGS`` (the TPU runtime's own flag channel), everything
else stays in ``XLA_FLAGS``; both are restored after every row, and the
child's stderr is captured into the report either way so the next
failure diagnoses itself.

The grid crosses the flag candidates with the winning stem lowering
when an autotune artifact exists (``scripts/stage_probe.py --autotune``
-> build/impl_map.json, or ``--impl_map``): the scoped-vmem limit is
exactly the knob that decides how big a tile the one large im2col
dot_general gets, so the two must be measured together.

    python scripts/xla_flag_probe.py                 # bf16 batch 128
    python scripts/xla_flag_probe.py --batch 64 --timeout 600

Writes one JSON line per flag set to stdout and XLA_FLAGS_PROBE.md,
incrementally.  Without a TPU the first child says so and the probe
exits nonzero with no row.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import bench  # noqa: E402

# Candidate sets, each relative to the baseline flags the environment
# already carries.  Conservative public knobs relevant to a single-chip
# conv workload; collectives-oriented flags are pointless on one chip.
CANDIDATES = [
    ("baseline", ""),
    # more scoped VMEM lets the conv emitter / dot tiler pick bigger
    # tiles (the small-temporal-dim stages are exactly the ones starved
    # for tile)
    ("vmem_64m", "--xla_tpu_scoped_vmem_limit_kib=65536"),
    ("vmem_128m", "--xla_tpu_scoped_vmem_limit_kib=131072"),
    # overlap-oriented scheduler; mostly collectives but also reorders
    # copies around the big fusions
    ("latency_hiding", "--xla_tpu_enable_latency_hiding_scheduler=true"),
    # both together
    ("vmem_128m+lhs", "--xla_tpu_scoped_vmem_limit_kib=131072 "
     "--xla_tpu_enable_latency_hiding_scheduler=true"),
]

def split_flags(flags: str) -> tuple[str, str]:
    """Route one candidate set: (xla_flags_part, libtpu_part).

    ``--xla_tpu_*`` knobs are TPU-compiler flags parsed by libtpu; fed
    to the client's XLA_FLAGS parser they abort the process before jax
    even initializes (rc=1, no record — the round-5 row killer)."""
    tpu, generic = [], []
    for tok in flags.split():
        (tpu if tok.startswith("--xla_tpu_") else generic).append(tok)
    return " ".join(generic), " ".join(tpu)


def build_grid(stem_impl_map: str) -> list:
    """(name, flags, extra _run_config kwargs) rows.

    When a winning stem lowering is known (autotune artifact or inline
    spec), it is crossed with the baseline and the two flag sets that
    interact with the big-matmul stem (scoped VMEM sizes the dot tiles;
    the latency-hiding scheduler reorders the copies around them)."""
    grid = [(name, flags, {}) for name, flags in CANDIDATES]
    if stem_impl_map:
        extra = {"conv_impl_map": stem_impl_map}
        cross = [("", ""),
                 ("+vmem_128m", "--xla_tpu_scoped_vmem_limit_kib=131072"),
                 ("+lhs", "--xla_tpu_enable_latency_hiding_scheduler=true")]
        for suffix, flags in cross:
            grid.append((f"stem_tuned{suffix}", flags, dict(extra)))
    return grid


def resolve_impl_map(arg: str) -> str:
    """--impl_map value -> the spec _run_config gets: '' (none), an
    inline spec passed through, or an artifact path made absolute (the
    child resolves from its own cwd).

    An EXPLICIT --impl_map is obeyed as given.  The default
    build/impl_map.json is auto-picked only when it is trustworthy for
    this run: marked complete, and not tuned on the CPU — a stage_probe
    sanity run on the CPU writes that path too, and a TPU probe silently
    crossing its flag grid with a CPU-chosen map would publish wrong
    winners."""
    if not arg:
        default = os.path.join(_REPO, "build", "impl_map.json")
        if not os.path.exists(default):
            return ""
        try:
            with open(default) as fh:
                art = json.load(fh)
        except (OSError, ValueError):
            return ""
        if not art.get("complete"):
            return ""
        tuned_on_cpu = str(art.get("device", "")).lower() == "cpu"
        return "" if tuned_on_cpu else default
    if "=" in arg:
        return arg
    return arg if os.path.isabs(arg) else os.path.join(_REPO, arg)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--frames", type=int, default=16)
    ap.add_argument("--size", type=int, default=224)
    ap.add_argument("--timeout", type=float, default=900.0)
    ap.add_argument("--impl_map", default="",
                    help="per-stage impl map to cross with the flag "
                         "grid: inline spec or artifact path; '' = "
                         "build/impl_map.json when it exists")
    args = ap.parse_args()

    # TERMing this probe must reach the live measurement child
    import signal

    signal.signal(signal.SIGTERM, bench._forward_term_and_exit)

    impl_map = resolve_impl_map(args.impl_map)
    grid = build_grid(impl_map)

    base_xla = os.environ.get("XLA_FLAGS", "")
    base_libtpu = os.environ.get("LIBTPU_INIT_ARGS", "")
    rows = []
    try:
        for name, flags, extra in grid:
            xla_part, libtpu_part = split_flags(flags)
            os.environ["XLA_FLAGS"] = (base_xla + " " + xla_part).strip()
            os.environ["LIBTPU_INIT_ARGS"] = (
                base_libtpu + " " + libtpu_part).strip()
            try:
                r = bench._run_config(
                    timeout_s=args.timeout,
                    dtype=args.dtype, batch=args.batch,
                    frames=args.frames, size=args.size, words=20, k=5,
                    remat=False, inner=4, s2d=False,
                    conv_impl="native", flops_hint=None, **extra)
                row = {"name": name, "flags": flags,
                       "impl_map": extra.get("conv_impl_map", ""),
                       "device_kind": r["device_kind"],
                       "clips_per_sec_per_chip": r["clips_per_sec_per_chip"],
                       "step_ms": r["step_ms"], "mfu": r.get("mfu")}
            except bench.NoTpuError as exc:
                # a healthy CPU backend is the wrong instrument: nothing
                # is measured, and no row is written
                print(json.dumps({"error": str(exc)}))
                sys.exit(1)
            except Exception as exc:
                # _run_config carries the child's stderr tail for
                # record-less deaths; keep the whole text — the report
                # table shows a truncation, the failure section the rest
                row = {"name": name, "flags": flags,
                       "impl_map": extra.get("conv_impl_map", ""),
                       "error": f"{type(exc).__name__}: {exc}"}
            print(json.dumps(row), flush=True)
            rows.append(row)
            _write_md(rows, args)
    finally:
        # an exception escaping the loop (e.g. _write_md IOError) must
        # not leave a candidate's flags polluting the parent environment
        os.environ["XLA_FLAGS"] = base_xla
        os.environ["LIBTPU_INIT_ARGS"] = base_libtpu


def _write_md(rows, args) -> None:
    lines = [
        "# XLA flag probe (auto-written by scripts/xla_flag_probe.py)", "",
        f"- config: {args.dtype} batch={args.batch} "
        f"{args.frames}f@{args.size}^2, full train step, differenced "
        "timing (4 inner steps/dispatch)",
        "- --xla_tpu_* candidates ride LIBTPU_INIT_ARGS (the client-side "
        "XLA_FLAGS parser aborts on flags it doesn't know — the round-5 "
        "rc=1 rows); stem_tuned rows apply the per-stage impl map.",
        "", "| name | flags | map | step_ms | clips/s/chip | MFU |",
        "|---|---|---|---|---|---|",
    ]
    failures = []
    for r in rows:
        mapped = "tuned" if r.get("impl_map") else "-"
        if "error" in r:
            failures.append(r)
            lines.append(f"| {r['name']} | `{r['flags'] or '(none)'}` | "
                         f"{mapped} | error (see below) | | |")
        else:
            lines.append(f"| {r['name']} | `{r['flags'] or '(none)'}` | "
                         f"{mapped} | {r['step_ms']} | "
                         f"{r['clips_per_sec_per_chip']} | "
                         f"{r.get('mfu', '-')} |")
    if failures:
        lines += ["", "## Failures (child stderr captured per row)"]
        for r in failures:
            lines += ["", f"### {r['name']}", "```",
                      r["error"][:2000], "```"]
    with open(os.path.join(_REPO, "XLA_FLAGS_PROBE.md"), "w") as fh:
        fh.write("\n".join(lines) + "\n")


if __name__ == "__main__":
    main()
