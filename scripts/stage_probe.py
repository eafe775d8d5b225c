"""Per-stage on-device timing of the S3D-G trunk.

BENCH_NOTES.md records whole-train-step MFU far below the analytic
roofline ceiling (PERF.md: weighted ceiling ~63%); this probe answers
*where* the gap lives by timing every trunk stage (conv1, pools,
conv_2b/2c, each Inception block, head) as its own jitted program on
the real chip, with the same chained-scan + differenced +
host-materialized timing the soft-DTW harness uses
(``milnce_tpu/utils/timing.py``).  One process, which takes the chip
itself; without a TPU the per-stage probe refuses to run.

Per stage it reports measured ms, the analytic roofline expectation at
the same shape (FLOPs, bytes, and the min(MXU, HBM) time bound from
``milnce_tpu/utils/roofline.py``), and the achieved fraction of that
bound — a stage far under its own bound is a scheduling/tiling problem,
not physics.

    python scripts/stage_probe.py                  # bf16 batch 32
    python scripts/stage_probe.py --batch 128 --dtype bfloat16

``--autotune`` turns the probe into a per-stage impl SELECTOR: every
conv stage is timed under each lowering in ``--impls`` (native, fold2d,
im2col — models/conv3d.py) for each mode in ``--modes`` (fwd, fwdbwd),
the winner per stage is the one with the lowest fwd+bwd time (the
training cost; PERF.md puts the backward near 13% MFU, so a
forward-picked winner could still lose the step), and the winning map
is written as a JSON artifact (``--out``, default build/impl_map.json)
that ``ModelConfig.conv_impl_map``, ``bench.py``
(MILNCE_BENCH_IMPL_MAP) and ``scripts/xla_flag_probe.py`` all consume:

    python scripts/stage_probe.py --autotune
    JAX_PLATFORMS=cpu python scripts/stage_probe.py --autotune \
        --batch 2 --frames 4 --size 32 --stages conv1 --iters 2

Writes one JSON line per stage to stdout and a summary table to
``STAGE_PROBE.md`` / ``STAGE_AUTOTUNE.md`` (TPU runs only).  The
autotune JSON artifact is written on every platform — it records its
device, and a map tuned on the CPU is a plumbing check, never a result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# HBM bandwidth (bytes/s) by device_kind substring — public figures,
# companion to utils/roofline.py PEAK_FLOPS_BY_KIND; the roofline bound
# needs both axes to track the device.
_HBM_BW = {
    "v6": 1640e9,       # Trillium / v6e
    "v5p": 2765e9,
    "v5e": 820e9,
    "v5 lite": 820e9,
    "v4": 1228e9,
    "v3": 900e9,
    "v2": 700e9,
}


def _validate_stage_filter(stages_csv: str) -> set:
    """--stages value -> set of conv stage names; a typo must fail HERE,
    not silently autotune zero stages and ship an empty map marked
    complete (config.parse_conv_impl_map guards the consume side; this
    guards the produce side)."""
    from milnce_tpu.config import CONV_STAGES

    only = {s for s in stages_csv.split(",") if s}
    unknown = only - set(CONV_STAGES)
    if unknown:
        raise ValueError(
            f"--stages names unknown conv stage(s) {sorted(unknown)} "
            f"(stages: {', '.join(CONV_STAGES)})")
    return only


def _hbm_bandwidth(device_kind: str) -> float:
    kind = device_kind.lower()
    for key, val in _HBM_BW.items():
        if key in kind:
            return val
    raise ValueError(f"no HBM bandwidth for device_kind {device_kind!r} "
                     "— add it, with its source, to _HBM_BW")


def _timed(fn, x, n_iters: int) -> float:
    """Seconds per fn(x) execution via the shared chained-scan protocol
    (milnce_tpu.utils.timing); short k1 keeps per-stage compiles cheap."""
    import jax.numpy as jnp

    from milnce_tpu.utils.timing import chained_seconds

    return chained_seconds(lambda d: jnp.sum(fn(d)), x, n_iters, k1=2)


def _stage_fns(model, variables, method, mode: str):
    """(fwd, probe) for one stage method of ``model``: probe is the
    forward in 'fwd' mode, or the fwd+bwd scalar (grads w.r.t. params
    AND input — what training pays at this stage) in 'fwdbwd' mode."""
    import jax
    import jax.numpy as jnp

    def fwd(x):
        return model.apply(variables, x, method=method)

    if mode == "fwd":
        return fwd, fwd

    def fwdbwd(x):
        # Both grads fold into one scalar so neither is DCE'd.  Only the
        # 'params' collection is differentiated (batch_stats and friends
        # stay closed over); grads of params the stage doesn't touch are
        # constant zeros XLA folds away, costing trace size, not runtime.
        rest = {k: v for k, v in variables.items() if k != "params"}

        def loss(p, xx):
            return jnp.sum(
                model.apply({"params": p, **rest}, xx, method=method)
                .astype(jnp.float32))

        dp, dx = jax.grad(loss, argnums=(0, 1))(variables["params"], x)
        acc = jnp.sum(dx.astype(jnp.float32))
        for leaf in jax.tree_util.tree_leaves(dp):
            acc = acc + jnp.sum(leaf.astype(jnp.float32))
        return acc

    return fwd, fwdbwd


def _build_stages(model, variables, mode: str):
    """The trunk as (name, (fwd, probe), pool_before, is_conv) tuples,
    in forward order — shared by the single-impl probe and the
    autotuner."""
    import jax
    import jax.numpy as jnp

    from milnce_tpu.models.s3dg import _tf_same_max_pool
    from milnce_tpu.utils import roofline

    def stage(method):
        return _stage_fns(model, variables, method, mode)

    def block_stage(name):
        def method(m, x):
            return getattr(m, name)(x, False)

        return stage(method)

    def pool_stage(window, strides):
        def fwd(x):
            return _tf_same_max_pool(x, window, strides)

        if mode == "fwd":
            return fwd, fwd
        return fwd, jax.grad(lambda x: jnp.sum(fwd(x).astype(jnp.float32)))

    stages = [
        ("conv1", stage(lambda m, x: m.conv1(x, False)), None, True),
        ("maxpool_2a", pool_stage((1, 3, 3), (1, 2, 2)), None, False),
        ("conv_2b", stage(lambda m, x: m.conv_2b(x, False)), None, True),
        ("conv_2c", stage(lambda m, x: m.conv_2c(x, False)), None, True),
        ("gating", stage(lambda m, x: m.stem_gating(x)), None, False),
        ("maxpool_3a", pool_stage((1, 3, 3), (1, 2, 2)), None, False),
    ]
    for idx, (name, _) in enumerate(roofline.INCEPTION_PLAN):
        stages.append((name, block_stage(name),
                       roofline.POOLS_BEFORE.get(idx), True))
    return stages


def _init_jitted(model, frames: int, size: int):
    """jit the init: eager Flax init dispatches every parameter's RNG +
    op individually, hundreds of tiny programs."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda key: model.init(
        key, jnp.zeros((2, frames, size, size, 3), jnp.float32),
        jnp.zeros((2, 6), jnp.int32)))(jax.random.PRNGKey(0))


def _setup_backend(args):
    import jax

    from milnce_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    dev = jax.devices()[0]
    return dev.device_kind, dev.platform == "tpu"


def _device_input_fn(args, compute_dtype):
    """Synthetic input generated ON DEVICE: shipping host-generated
    video from the host costs more than the measurement.  One jitted
    generator reused for all seeds (a fresh lambda per call would miss
    the jit trace cache and recompile)."""
    import jax
    import jax.numpy as jnp

    gen = jax.jit(lambda key: jax.random.uniform(
        key, (args.batch, args.frames, args.size, args.size, 3),
        jnp.float32).astype(compute_dtype))
    return lambda seed: gen(jax.random.PRNGKey(seed))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--frames", type=int, default=16)
    ap.add_argument("--size", type=int, default=224)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--conv_impl", default="native",
                    choices=["native", "fold2d", "im2col"])
    ap.add_argument("--iters", type=int, default=8,
                    help="chained executions per measurement")
    ap.add_argument("--mode", default="fwd", choices=["fwd", "fwdbwd"],
                    help="fwdbwd also differentiates each stage w.r.t. "
                         "its params AND input — the training cost.  The "
                         "backward is ~2/3 of a train step's FLOPs and "
                         "grad-conv lowerings tile differently from the "
                         "forward, so a stage at its forward roofline can "
                         "still be the step's MFU sink")
    ap.add_argument("--autotune", action="store_true",
                    help="time every conv stage under each impl in "
                         "--impls and emit the winning per-stage map "
                         "(see --out)")
    ap.add_argument("--impls", default="native,fold2d,im2col",
                    help="autotune candidates, comma-separated")
    ap.add_argument("--modes", default="fwd,fwdbwd",
                    help="autotune measurement modes; the LAST one "
                         "listed picks the winner (fwdbwd = training "
                         "cost, the default tiebreak)")
    ap.add_argument("--stages", default="",
                    help="autotune only these conv stages (comma list; "
                         "'' = all) — the CPU smoke path")
    ap.add_argument("--out", default=os.path.join("build", "impl_map.json"),
                    help="autotune artifact path (repo-relative)")
    args = ap.parse_args()

    if args.autotune:
        autotune(args)
        return

    dev_kind, on_tpu = _setup_backend(args)
    if not on_tpu:
        # the per-stage table is a share of THIS chip's peak and
        # bandwidth; off the chip there is nothing to measure
        sys.exit(f"stage_probe: no TPU (jax found {dev_kind!r}) — the "
                 "per-stage roofline probe runs on the chip only")

    import jax
    import jax.numpy as jnp

    from milnce_tpu.config import full_preset
    from milnce_tpu.models.build import build_model
    from milnce_tpu.models.s3dg import _tf_same_max_pool
    from milnce_tpu.utils import roofline

    cfg = full_preset()
    cfg.model.dtype = args.dtype
    cfg.model.conv_impl = args.conv_impl
    model = build_model(cfg.model)
    variables = _init_jitted(model, args.frames, args.size)

    # peak flops / HBM bytes/s for the roofline bound: the tables, or an
    # error for a device they do not hold
    peak_flops = roofline.chip_peak_flops(jax.devices()[0])
    hbm_gbs = _hbm_bandwidth(dev_kind)

    compute_dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32

    stages = _build_stages(model, variables, args.mode)

    # analytic per-stage roofline at this shape
    model_stages = roofline.s3d_video_stages(
        args.batch, args.frames, args.size,
        dtype_bytes=2 if args.dtype == "bfloat16" else 4)
    flops_by_prefix = {}
    bytes_by_prefix = {}
    for st in model_stages:
        prefix = st.name.split(".")[0]
        flops_by_prefix[prefix] = flops_by_prefix.get(prefix, 0.0) + st.flops
        bytes_by_prefix[prefix] = bytes_by_prefix.get(prefix, 0.0) + st.bytes

    device_input = _device_input_fn(args, compute_dtype)
    x = device_input(0)

    records = []
    total_ms = 0.0
    for name, (fwd_fn, probe_fn), pool, _ in stages:
        if pool is not None:
            x = _tf_same_max_pool(x, *pool)
        t = _timed(probe_fn, x, args.iters)
        if args.mode == "fwdbwd":
            # heuristics, stated in the artifact: fwd + dX + dW = ~3x
            # conv FLOPs (param-free pool stages pay no dW: ~2x);
            # activations re-read and grads written = ~2x traffic
            f_mult = 2.0 if name.startswith("maxpool") else 3.0
            b_mult = 2.0
        else:
            f_mult = b_mult = 1.0
        flops = f_mult * flops_by_prefix.get(name, 0.0)
        byts = b_mult * bytes_by_prefix.get(name, 0.0)
        bound_s = max(flops / peak_flops, byts / hbm_gbs) if byts else None
        rec = {
            "stage": name,
            "mode": args.mode,
            "in_shape": list(x.shape),
            "ms": round(t * 1e3, 3),
            "gflop": round(flops / 1e9, 2),
            "tflops_per_s": round(flops / t / 1e12, 2) if t else None,
            "pct_of_peak": round(100 * flops / t / peak_flops, 1) if t else None,
            "roofline_ms": round(bound_s * 1e3, 3) if bound_s else None,
            "x_over_roofline": (round(t / bound_s, 1)
                                if bound_s and bound_s > 0 else None),
        }
        print(json.dumps(rec), flush=True)
        records.append(rec)
        # rewrite after EVERY stage: a run cut at its time limit must not
        # cost the stages already measured
        _write_md(records, args)
        total_ms += t * 1e3
        x = jax.jit(fwd_fn)(x)          # advance via the FORWARD output

    # whole-trunk forward for reconciliation (sum of parts vs one program:
    # the difference is what XLA's cross-stage fusion buys)
    # _stage_fns's second element is already the mode-appropriate probe
    _, trunk_probe = _stage_fns(model, variables,
                                lambda m, v: m.forward_video(v), args.mode)
    x0 = device_input(1)
    t_trunk = _timed(trunk_probe, x0, args.iters)
    summary = {
        "stage": ("TRUNK_FWDBWD(one program)" if args.mode == "fwdbwd"
                  else "TRUNK_FWD(one program)"),
        "mode": args.mode,
        "ms": round(t_trunk * 1e3, 3),
        "sum_of_stage_ms": round(total_ms, 3),
        "device": dev_kind,
        "batch": args.batch,
        "dtype": args.dtype,
        "conv_impl": args.conv_impl,
    }
    print(json.dumps(summary), flush=True)
    records.append(summary)

    _write_md(records, args)


def _hbm_budget_bytes() -> float | None:
    """Per-chip device-memory budget for the autotune pre-flight:
    MILNCE_HBM_GIB (explicit, e.g. 16 for v5e) wins; otherwise the
    backend's own bytes_limit when it exposes one (TPU does, the CPU
    test platform doesn't).  None = no budget known, pre-flight off."""
    env = os.environ.get("MILNCE_HBM_GIB")
    if env:
        return float(env) * 2 ** 30
    import jax

    stats = getattr(jax.local_devices()[0], "memory_stats", lambda: None)()
    if stats and stats.get("bytes_limit"):
        return float(stats["bytes_limit"])
    return None


def _preflight_peak(probe_fn, x) -> float | None:
    """Predicted per-chip peak bytes of one candidate's probe program
    (graftlint Pass 4 planner) — None when the trace itself fails (the
    candidate will fail identically when timed; let the sweep surface
    that error, not the pre-flight)."""
    try:
        from milnce_tpu.analysis.memplan import preflight_fn_peak

        return float(preflight_fn_peak(probe_fn, x))
    except Exception as exc:  # graftlint: disable=GL007(pre-flight is advisory: a planner crash must not kill the sweep the planner exists to protect)
        print(json.dumps({"preflight_error": f"{type(exc).__name__}: "
                                             f"{exc}"}), flush=True)
        return None


def autotune(args) -> None:
    """Measure every conv stage under each candidate impl and emit the
    winning per-stage map as a config artifact.

    One model per impl, ONE shared parameter tree (the impls are
    layout-identical by design — models/conv3d.py), stage inputs
    advanced by the native forward so every impl times the same tensor.
    """
    from milnce_tpu.config import CONV_IMPLS

    # validate BEFORE paying for a backend: a typo'd filter would
    # otherwise autotune zero stages and ship an empty complete map
    impls = [s for s in args.impls.split(",") if s]
    modes = [s for s in args.modes.split(",") if s]
    only = _validate_stage_filter(args.stages)
    unknown = set(impls) - set(CONV_IMPLS)
    if unknown:
        raise ValueError(f"--impls names unknown impl(s) {sorted(unknown)} "
                         f"(impls: {', '.join(CONV_IMPLS)})")
    bad_modes = set(modes) - {"fwd", "fwdbwd"}
    if bad_modes:
        # _stage_fns treats anything non-'fwd' as fwdbwd; a typo'd mode
        # would burn a chip session and mislabel the artifact
        raise ValueError(f"--modes names unknown mode(s) {sorted(bad_modes)} "
                         "(modes: fwd, fwdbwd)")

    dev_kind, on_tpu = _setup_backend(args)

    import jax
    import jax.numpy as jnp

    from milnce_tpu.config import full_preset
    from milnce_tpu.models.build import build_model
    from milnce_tpu.models.s3dg import _tf_same_max_pool

    cfg = full_preset()
    cfg.model.dtype = args.dtype
    models = {}
    for impl in impls:
        cfg.model.conv_impl = impl
        models[impl] = build_model(cfg.model)
    variables = _init_jitted(models[impls[0]], args.frames, args.size)

    compute_dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    device_input = _device_input_fn(args, compute_dtype)
    x = device_input(0)

    # per-impl stage lists share the walk order; index them together
    per_impl = {impl: {mode: _build_stages(models[impl], variables, mode)
                       for mode in modes}
                for impl in impls}
    walk = per_impl[impls[0]][modes[0]]

    results = {}                        # stage -> impl -> mode -> ms
    impl_map = {}
    # pre-flight budget is sweep-invariant; resolving it per candidate
    # would re-query device memory stats ~impls x stages times
    budget = _hbm_budget_bytes()
    for idx, (name, _, pool, is_conv) in enumerate(walk):
        if pool is not None:
            x = _tf_same_max_pool(x, *pool)
        if is_conv and (not only or name in only):
            timings = {}
            for impl in impls:
                # pre-flight what-if (ISSUE 8): a candidate whose
                # PREDICTED peak exceeds the budget would OOM mid-grid
                # and cost the sweep its remaining stages — skip it with
                # the reason on record instead of crashing the probe
                if budget:
                    peak = _preflight_peak(
                        per_impl[impl][modes[-1]][idx][1][1], x)
                    if peak is not None and peak > budget:
                        print(json.dumps({
                            "stage": name, "impl": impl,
                            "skipped": "predicted peak "
                            f"{peak / 2**30:.2f} GiB exceeds the "
                            f"{budget / 2**30:.2f} GiB budget "
                            "(mem_plan pre-flight)"}), flush=True)
                        continue
                timings[impl] = {}
                for mode in modes:
                    _, probe_fn = per_impl[impl][mode][idx][1]
                    timings[impl][mode] = round(
                        _timed(probe_fn, x, args.iters) * 1e3, 3)
            if not timings:
                print(json.dumps({
                    "stage": name,
                    "skipped": "every candidate failed the mem_plan "
                               "pre-flight — stage keeps conv_impl "
                               "native (no map entry)"}), flush=True)
                fwd_fn = per_impl[impls[0]][modes[0]][idx][1][0]
                x = jax.jit(fwd_fn)(x)
                continue
            # the LAST mode listed picks the winner (fwdbwd by default —
            # the training cost) among candidates that passed pre-flight
            decide = modes[-1]
            winner = min(timings, key=lambda i: timings[i][decide])
            results[name] = timings
            if winner != "native":      # map only carries overrides
                impl_map[name] = winner
            print(json.dumps({"stage": name, "winner": winner,
                              "by": decide, "ms": timings}), flush=True)
            _write_artifact(results, impl_map, args, dev_kind)
            if on_tpu:
                _write_autotune_md(results, impl_map, args, dev_kind)
        # advance via the FIRST impl's forward: all impls compute the
        # same math, so the walk input is impl-independent
        fwd_fn = per_impl[impls[0]][modes[0]][idx][1][0]
        x = jax.jit(fwd_fn)(x)

    _write_artifact(results, impl_map, args, dev_kind, final=True)
    if on_tpu:
        _write_autotune_md(results, impl_map, args, dev_kind)
    print(json.dumps({"artifact": _artifact_path(args),
                      "impl_map": impl_map}), flush=True)


def _artifact_path(args) -> str:
    out = args.out
    return out if os.path.isabs(out) else os.path.join(_REPO, out)


def _write_artifact(results, impl_map, args, dev_kind, final=False) -> None:
    """Incrementally (re)write the autotune artifact — a run cut at
    its time limit must not cost the stages already decided.  The map
    feeds ModelConfig.conv_impl_map / bench.py MILNCE_BENCH_IMPL_MAP."""
    path = _artifact_path(args)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    payload = {
        "generator": "scripts/stage_probe.py --autotune",
        "device": dev_kind,
        "config": {"batch": args.batch, "frames": args.frames,
                   "size": args.size, "dtype": args.dtype,
                   "iters": args.iters, "modes": args.modes},
        "complete": final,
        "impl_map": impl_map,
        "stage_ms": results,
    }
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=2)
        fh.write("\n")


def _write_autotune_md(results, impl_map, args, dev_kind) -> None:
    modes = [s for s in args.modes.split(",") if s]
    impls = [s for s in args.impls.split(",") if s]
    lines = [
        "# Stage impl autotune (auto-written by scripts/stage_probe.py"
        " --autotune)", "",
        f"- config: batch={args.batch} {args.frames}f@{args.size}^2 "
        f"dtype={args.dtype} device={dev_kind}; winner per stage by "
        f"{modes[-1]} ms (the training cost)",
        f"- winning map (native omitted): "
        f"`{json.dumps(impl_map, sort_keys=True)}` -> {args.out}",
        "",
        "| stage | " + " | ".join(f"{i} {m} ms" for i in impls
                                  for m in modes) + " | winner |",
        "|---" * (1 + len(impls) * len(modes) + 1) + "|",
    ]
    for stage, timings in results.items():
        # a candidate absent from timings failed the mem_plan pre-flight
        cells = [str(timings.get(i, {}).get(m, "skipped"))
                 for i in impls for m in modes]
        winner = impl_map.get(stage, "native")
        lines.append(f"| {stage} | " + " | ".join(cells) + f" | {winner} |")
    with open(os.path.join(_REPO, "STAGE_AUTOTUNE.md"), "w") as fh:
        fh.write("\n".join(lines) + "\n")


def _write_md(records, args) -> None:
    path = os.path.join(_REPO, "STAGE_PROBE.md")
    lines = [
        "# Stage probe (auto-written by scripts/stage_probe.py)", "",
        f"- config: batch={args.batch} {args.frames}f@{args.size}^2 "
        f"dtype={args.dtype} conv_impl={args.conv_impl} mode={args.mode}"
        + (" (per-stage fwd+bwd incl. param grads; bound heuristics: "
           "FLOPs x3, x2 for param-free pools; bytes x2)"
           if args.mode == "fwdbwd" else ""),
        "- ms = chained-scan differenced host-materialized time; "
        "roofline_ms = max(FLOPs/peak, bytes/HBM) analytic bound; "
        "x_over = measured/bound (1.0 = at the roofline).", "",
        "| stage | ms | GFLOP | TFLOP/s | % peak | roofline ms | x over |",
        "|---|---|---|---|---|---|---|",
    ]
    for r in records:
        if "gflop" not in r:
            continue
        lines.append(
            f"| {r['stage']} | {r['ms']} | {r['gflop']} | "
            f"{r['tflops_per_s']} | {r['pct_of_peak']} | "
            f"{r['roofline_ms']} | {r['x_over_roofline']} |")
    tail = [r for r in records if r.get("stage", "").startswith("TRUNK")]
    if tail:
        what = ("fwd+bwd" if tail[0].get("mode") == "fwdbwd" else "forward")
        lines += ["", f"Whole-trunk {what} in ONE program: "
                  f"{tail[0]['ms']} ms vs sum-of-stages "
                  f"{tail[0]['sum_of_stage_ms']} ms "
                  "(difference = cross-stage fusion + per-program overhead)."]
    with open(path, "w") as fh:
        fh.write("\n".join(lines) + "\n")


if __name__ == "__main__":
    main()
