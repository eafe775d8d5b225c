"""Throughput benchmark: clips/sec/chip of the full jitted train step
(S3D-G fwd+bwd + MIL-NCE + Adam) on synthetic data, on a TPU.

Streams one-line JSON records to stdout:
    {"metric", "value", "unit", "vs_baseline", ...}
**Consumers take the LAST parsable record line** — an interim
best-so-far is emitted after every measured config, superseded by the
final record.  Detailed sweep results (per-dtype, per-batch, MFU) go to
stderr and ``BENCH_NOTES.md``.

Nothing is measured off the chip: without a TPU the script prints one
line saying so and exits nonzero, with no record.  Processes: this
parent never imports JAX; each config is measured in a child of its own
(a clean allocator after an OOM, a time limit per config), one child at
a time — a chip belongs to one process.

The reference publishes no throughput numbers (BASELINE.md: "to be
established"); the headline metric is the best clips/sec/chip across the
{bfloat16, float32} x batch sweep at 16f@224^2 (the reference's
published GPU input config, /root/reference/README.md:114-129).
``vs_baseline`` is measured against BASELINE_THROUGHPUT once a first
real-TPU number exists in round history; 1.0 until then.

Mesh sweep axis (ISSUE 6): ``MILNCE_BENCH_MESH=data,model[=N]`` runs
the whole sweep on the 2-D FSDP grid (state sharded per
parallel/sharding_map.py; batch over both axes); by default a
``mesh_2d`` comparison row is measured at the winning 1-D operating
point.  Every record carries its mesh shape and sharding-map hash so
``obs_report --check`` compares like with like, and a 2-D row whose
map shards nothing is REFUSED rather than measured as fake FSDP.
Related knobs: MILNCE_BENCH_FSDP_MIN (threshold override),
MILNCE_BENCH_MESH_2D=0 (skip the comparison row).

Curriculum axis (ISSUE 16): ``MILNCE_BENCH_CURRICULUM=<train.curriculum
spec>`` measures every stage of a staged-resolution schedule as its own
row (stage shape, winning dtype) and reports the whole-schedule
clips/sec against a flat full-res run of the same total clip count —
the measured answer to "what does the curriculum buy".  Stages must be
``until_step``-bounded; the open-ended final stage defaults to the
bounded stages' total steps (override:
MILNCE_BENCH_CURRICULUM_STEPS).  Stage rows ride in the record under
``curriculum`` and in BENCH_NOTES.md with a ``stage`` column; they
never displace the headline sweep measurement.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

_REPO = os.path.dirname(os.path.abspath(__file__))
_CONFIG_ENV = "MILNCE_BENCH_CONFIG_JSON"     # one-config measurement child
_NO_TPU_EXIT = 3                             # measurement child found no TPU

# clips/sec/chip anchor for vs_baseline: the first recorded v5e
# operating point (bfloat16 batch 256 @16f/224, taken before PR 1 with
# latency-inclusive timing — the record's anchor_timing field says so).
# To be re-measured by the benchmark (ROADMAP.md Queue 1 item 1).
BASELINE_THROUGHPUT = 95.35


class NoTpuError(RuntimeError):
    """The measurement child found no TPU: nothing is measured."""


def _emit(result):
    sys.stdout.write(json.dumps(result) + "\n")
    sys.stdout.flush()


def _last_tagged_json(raw: bytes, predicate):
    """The last JSON object in ``raw`` whose dict satisfies ``predicate``
    (the streaming protocols all agree: later lines supersede earlier
    ones; stray JSON-shaped log lines are filtered by the predicate)."""
    for line in reversed(raw.decode(errors="replace").splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                rec = json.loads(line)
            except Exception:
                continue
            if isinstance(rec, dict) and predicate(rec):
                return rec
    return None


def _last_json(raw: bytes):
    """The last parsable bench record in a child's captured stdout (the
    interim-streaming protocol: later records supersede earlier ones)."""
    return _last_tagged_json(raw, lambda r: "metric" in r and "value" in r)


def _note(msg):
    sys.stderr.write(msg + "\n")
    sys.stderr.flush()


def _step_flops(step_fn, args):
    """Per-step FLOPs from XLA's cost analysis of the lowered single-step
    program (unlike analyzing the inner_steps>1 scan program, this counts
    the whole step exactly once; lowering is compile-free).  Where the
    backend returns nothing here the caller falls back to the analytic
    roofline model rather than paying a second full-model compile just
    for the MFU diagnostic."""
    try:
        cost = step_fn.lower(*args).cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else None
        if cost:
            flops = float(cost.get("flops", 0.0))
            if flops > 0:
                return flops
    except Exception as exc:
        _note(f"bench: cost_analysis unavailable: {exc}")
    return None


def _parse_mesh_spec(spec: str):
    """``--mesh``/MILNCE_BENCH_MESH grammar: '' (1-D data mesh) or
    'data,model[=N]' (2-D FSDP grid, model axis N wide — default 2).
    Mirrors config's fail-at-parse-time discipline."""
    if not spec:
        return None, 1
    names = [p for p in spec.split(",") if p]
    if len(names) != 2 or names[0] != "data":
        raise ValueError(f"mesh spec {spec!r}: expected 'data,model[=N]'")
    axis, _, n = names[1].partition("=")
    return axis, int(n) if n else 2


def _bench_config(dtype: str, batch: int, frames: int, size: int,
                  words: int, k: int, remat: bool,
                  inner: int = 1, s2d: bool = False,
                  conv_impl: str = "native", conv_impl_map: str = "",
                  loss: str = "milnce", grad_accum: int = 1,
                  mesh_spec: str = "", loss_impl: str = "dense",
                  flops_hint: float | None = None):
    """Time the full train step at one operating point.

    ``inner`` optimizer steps run inside ONE XLA program per dispatch
    (lax.scan in make_train_step) so per-dispatch host latency doesn't
    masquerade as device time.
    ``loss`` selects the trained loss: 'milnce' (headline) or a DTW
    family name ('sdtw_3', 'cdtw', ...) with ``sdtw_backend='auto'`` —
    the Pallas kernel inside the full compiled train step.  FLOPs/MFU
    are reported for milnce only (the analytic model doesn't count the
    alignment DP).
    ``mesh_spec`` ('data,model[=N]') runs the row on the 2-D FSDP grid:
    state sharded per the sharding map, batch over both axes, the
    record carrying mesh shape + map hash so ``obs_report`` can compare
    1-D and 2-D runs.  A 2-D row whose map shards NOTHING is refused
    (RuntimeError) — paying model-axis collectives for pure replication
    must not masquerade as an FSDP measurement.
    Returns dict with clips/sec/chip (+flops) or raises on OOM."""
    import jax
    import jax.numpy as jnp

    from milnce_tpu.config import full_preset
    from milnce_tpu.models.build import build_model
    from milnce_tpu.parallel.mesh import batch_sharding, build_mesh, replicated
    from milnce_tpu.train.schedule import build_schedule
    from milnce_tpu.train.state import build_optimizer, create_train_state
    from milnce_tpu.train.step import make_train_step
    from milnce_tpu.utils.roofline import chip_peak_flops

    dev0 = jax.devices()[0]
    peak = chip_peak_flops(dev0)    # None off the TPU; unknown kind raises
    cfg = full_preset()
    cfg.model.dtype = dtype
    cfg.model.remat = remat
    cfg.model.space_to_depth = s2d
    cfg.model.conv_impl = conv_impl
    # per-stage overrides: inline spec or stage_probe --autotune artifact
    # path (config.parse_conv_impl_map handles both)
    cfg.model.conv_impl_map = conv_impl_map
    model_axis, model_n = _parse_mesh_spec(mesh_spec)
    if model_axis:
        cfg.parallel.model_axis = model_axis
        cfg.parallel.model_parallel_size = model_n
        min_env = os.environ.get("MILNCE_BENCH_FSDP_MIN")
        if min_env:
            cfg.parallel.fsdp_min_size = int(min_env)
    model = build_model(cfg.model)
    mesh = build_mesh(cfg.parallel)

    loss_cfg = None
    if loss != "milnce":
        cfg.loss.name = loss
        cfg.loss.sdtw_backend = "auto"   # Pallas where the measured
        loss_cfg = cfg.loss              # crossover says it wins
    elif loss_impl != "dense":
        # MIL-NCE impl axis (ISSUE 12): 'chunked'/'auto' swap the dense
        # similarity cubes for the streaming loss (losses/
        # milnce_chunked.py) inside the full compiled step; the row's
        # predicted_peak_bytes_per_chip then carries the memory delta
        # alongside the throughput cost (BENCH_MILNCE_LOSS.md)
        cfg.loss.milnce_impl = loss_impl
        loss_cfg = cfg.loss
    optimizer = build_optimizer(cfg.optim, build_schedule(cfg.optim, 1000))

    # Everything below runs ON DEVICE in three jitted programs.  The
    # obvious host-side version (eager model.init + optimizer.init +
    # device_put of host-generated arrays) issues hundreds of tiny
    # dispatches and ships ~0.1-1 GB of synthetic video from the host.
    repl = replicated(mesh)
    batch_axes = ((cfg.parallel.data_axis, model_axis) if model_axis
                  else cfg.parallel.data_axis)
    data_sh = batch_sharding(mesh, batch_axes)

    def init_state(key):
        variables = model.init(
            key, jnp.zeros((2, frames, size, size, 3), jnp.float32),
            jnp.zeros((2 * k, words), jnp.int32))
        return create_train_state(variables, optimizer)

    state = jax.jit(init_state, out_shardings=repl)(jax.random.PRNGKey(0))

    state_specs = None
    mesh_fields = {
        "mesh": "x".join(str(mesh.shape[a]) for a in mesh.axis_names)
                + f" ({','.join(mesh.axis_names)})"}
    if model_axis:
        from milnce_tpu.parallel.sharding_map import shard_and_place_state

        placement = shard_and_place_state(
            state, mesh, model_axis, min_size=cfg.parallel.fsdp_min_size,
            spec=cfg.parallel.sharding_map)
        if placement.n_sharded == 0:
            # refuse, don't measure: a 2-D row paying model-axis
            # collectives for pure replication is not an FSDP data point
            raise RuntimeError(
                "2-D mesh row with a sharding map that shards NOTHING "
                f"(threshold {cfg.parallel.fsdp_min_size} elements) — "
                "lower MILNCE_BENCH_FSDP_MIN or fix the map")
        state_specs = placement.specs
        mesh_fields["sharding_map_hash"] = placement.hash
        mesh_fields["params_sharded"] = placement.n_sharded
        state = placement.state

    if grad_accum > 1:
        # the two-pass embedding-cache program (the 8192-global-batch
        # recipe's step): ``batch`` clips consumed per update via
        # grad_accum microbatches.  No inner-step scan — one dispatch IS
        # already grad_accum sub-steps of work, which amortizes dispatch
        # latency the same way.
        assert inner == 1, "grad_accum rows measure with inner=1"
        from milnce_tpu.train.step import make_grad_cache_step

        step_fn = make_grad_cache_step(model, optimizer, mesh, grad_accum,
                                       donate=False, loss_cfg=loss_cfg,
                                       state_specs=state_specs,
                                       model_axis=model_axis)
    else:
        step_fn = make_train_step(model, optimizer, mesh, donate=False,
                                  inner_steps=inner, loss_cfg=loss_cfg,
                                  state_specs=state_specs,
                                  model_axis=model_axis)

    def make_inputs(key):
        kv, kt = jax.random.split(key)
        video = jax.random.randint(
            kv, (batch, frames, size, size, 3), 0, 255).astype(jnp.uint8)
        text = jax.random.randint(
            kt, (batch * k, words), 0, cfg.model.vocab_size, jnp.int32)
        start = jnp.zeros((batch,), jnp.float32)
        return video, text, start

    video_d, text_d, start_d = jax.jit(
        make_inputs, out_shardings=(data_sh, data_sh, data_sh))(
            jax.random.PRNGKey(1))

    if loss != "milnce" or grad_accum > 1:
        # DTW rows: neither the hint nor the analytic model counts the
        # alignment DP.  grad_accum rows: the two-pass step does ~2x the
        # forward FLOPs of the plain step, so the plain-model MFU would
        # be fiction.  Report raw throughput only.
        flops, flops_source = None, None
    elif flops_hint is not None:
        # Seeded from an earlier XLA-counted config of the same plan (see
        # run_bench's hint(), which rescales model and logits terms
        # separately) — avoids another full-model compile just for the
        # MFU diagnostic.
        flops, flops_source = flops_hint, "hint"
    else:
        single = (step_fn if inner == 1 else
                  make_train_step(model, optimizer, mesh, donate=False,
                                  state_specs=state_specs,
                                  model_axis=model_axis))
        flops = _step_flops(single, (state, video_d, text_d, start_d))
        if flops is not None:
            flops_source = "xla"
        else:
            # analytic model (valid-tap conv counting, pinned against
            # XLA's own analysis in tests/test_roofline.py) — no extra
            # compile, exact at every batch.  Arch fields
            # come from the SAME cfg.model the timed step was built from.
            from milnce_tpu.utils.roofline import train_step_flops

            flops = train_step_flops(
                batch, frames, size, k, words, space_to_depth=s2d,
                inception_blocks=cfg.model.inception_blocks,
                embedding_dim=cfg.model.embedding_dim,
                word_dim=cfg.model.word_embedding_dim,
                hidden=cfg.model.text_hidden_dim)
            flops_source = "analytic"
            _note(f"bench: using analytic FLOPs model ({flops:.3e}/step)")

    # static HBM plan of the timed program (graftlint Pass 4,
    # analysis/memplan.py): per-chip predicted peak bytes ride in the
    # record so obs_report --check gates memory drift alongside
    # step-time — a row that got faster by doubling its footprint is a
    # regression the throughput gate alone would wave through.  Traced
    # with the production donation intent (the trainer donates the
    # state; this harness builds donate=False to re-use it).  Best-effort: a planner error must cost the
    # memory field, never the measurement.
    predicted_peak = None
    try:
        from milnce_tpu.analysis.memplan import plan_fn
        from milnce_tpu.train.step import STATE_DONATION_ARGNUMS

        predicted_peak = plan_fn(
            step_fn, (state, video_d, text_d, start_d),
            argnames=("state", "video", "text", "start"),
            donate_argnums=STATE_DONATION_ARGNUMS).peak_bytes
    except Exception as exc:
        _note(f"bench: memplan prediction failed ({type(exc).__name__}: "
              f"{exc}) — row ships without predicted_peak_bytes_per_chip")

    # precision fingerprint of the timed program (graftlint Pass 5,
    # analysis/numerics.py): sha of the dtype census + cast inventory
    # rides in the record so obs_report --check can FLAG cross-precision
    # compares — a bf16 row beating an f32 baseline is a dtype change,
    # not a speedup.  Best-effort for the same reason as the plan.
    dtype_census_hash = None
    try:
        from milnce_tpu.analysis.numerics import audit_fn

        dtype_census_hash = audit_fn(
            step_fn, (state, video_d, text_d, start_d),
            argnames=("state", "video", "text", "start"),
            entry="bench").census_hash()
    except Exception as exc:
        _note(f"bench: numerics audit failed ({type(exc).__name__}: "
              f"{exc}) — row ships without dtype_census_hash")

    # warmup / compile (NOT `loss` — that name is the loss-selector arg
    # and ends up verbatim in the result record)
    state, warmup_loss = step_fn(state, video_d, text_d, start_d)
    float(warmup_loss)

    def wall(n_dispatch: int) -> float:
        nonlocal state
        t0 = time.perf_counter()
        loss = None
        for _ in range(n_dispatch):
            state, loss = step_fn(state, video_d, text_d, start_d)
        # the device->host transfer of the computed scalar is the sync
        float(loss)
        return time.perf_counter() - t0

    # Differenced timing: W(n) = latency + n * device_time when dispatches
    # pipeline, so (W(k2) - W(k1)) / (k2 - k1) cancels the per-dispatch
    # host latency that a plain W(n)/n measurement folds into the step
    # time.  If the backend serializes dispatches the difference degrades
    # to the plain estimate, never worse.
    k1, k2 = 1, 3
    w1 = min(wall(k1) for _ in range(2))
    w2 = min(wall(k2) for _ in range(2))
    if w2 - w1 < 0.05 * w2:
        # Difference lost in scheduler jitter (tiny test shapes): fall
        # back to the plain latency-inclusive estimate
        # rather than emitting absurd near-zero step times.
        _note(f"bench: differenced timing degenerate (w1={w1:.4f}s "
              f"w2={w2:.4f}s) — falling back to W(k2)/k2")
        dt = w2 / k2
    else:
        dt = (w2 - w1) / (k2 - k1)         # per-dispatch device time

    n_chips = len(jax.devices())
    guard_flops = flops
    if guard_flops is None and peak:
        # DTW / grad_accum rows report no FLOPs, but the plausibility
        # guard below must still hold: the PLAIN step's analytic FLOPs
        # are a strict lower bound on the true work per clip for both
        # (the DP / the second embedding pass only add work), so a
        # broken timing still trips the bound.
        from milnce_tpu.utils.roofline import train_step_flops

        guard_flops = train_step_flops(
            batch, frames, size, k, words, space_to_depth=s2d,
            inception_blocks=cfg.model.inception_blocks,
            embedding_dim=cfg.model.embedding_dim,
            word_dim=cfg.model.word_embedding_dim,
            hidden=cfg.model.text_hidden_dim)
    if guard_flops and peak:
        # Physical sanity: implied FLOP/s beyond this device's peak means
        # the measurement is broken (a timing that returned before the
        # device finished).  Better no row than a fantasy row.  flops
        # counts the whole sharded step, so scale the bound by chip count.
        implied = guard_flops * inner / dt
        bound = 1.5 * peak * n_chips
        if implied > bound:
            raise RuntimeError(
                f"implausible measurement: {implied:.3e} FLOP/s implied "
                f"(dt={dt:.6f}s for {inner} steps of >={guard_flops:.3e} "
                f"FLOPs on {n_chips} chips, bound {bound:.3e})")
    # record the EFFECTIVE loss impl: 'auto' resolves per shape
    # (prefers_chunked at this row's per-chip batch), and a row the rule
    # resolved to dense must not read as a streaming-loss measurement
    effective_impl = loss_impl if loss == "milnce" else None
    if effective_impl == "auto":
        from milnce_tpu.losses.milnce_chunked import prefers_chunked

        effective_impl = ("chunked" if prefers_chunked(
            batch // n_chips, batch, k) else "dense")
    result = {
        # the device this row ran on, as JAX reports it
        "platform": dev0.platform,
        "device_kind": dev0.device_kind,
        "n_chips": n_chips,
        "dtype": dtype,
        "batch": batch,
        "remat": remat,
        "s2d": s2d,
        "conv_impl": conv_impl,
        "impl_map": conv_impl_map,
        "loss": loss,
        "loss_impl": effective_impl,
        "loss_impl_requested": (loss_impl if loss == "milnce"
                                and loss_impl == "auto" else None),
        "grad_accum": grad_accum,
        "inner": inner,
        **mesh_fields,
        "step_ms": round(dt / inner * 1e3, 2),
        "clips_per_sec_per_chip": round(batch * inner / dt / n_chips, 3),
        "flops_per_step": flops,
        "flops_source": flops_source if flops else None,
        "flops_per_sec": (flops * inner / dt) if flops else None,
        "predicted_peak_bytes_per_chip": predicted_peak,
        "dtype_census_hash": dtype_census_hash,
    }
    if peak and flops:
        # the SHARED MFU definition (utils/roofline.py) — identical to
        # the train loop's live gauge given the same throughput
        from milnce_tpu.utils.roofline import mfu as _shared_mfu

        result["mfu"] = round(_shared_mfu(flops, inner / dt, peak,
                                          n_chips), 4)
    return result


# the measurement child currently running (None between configs) — the
# SIGTERM handler stops it, so this script leaves no process behind
_ACTIVE_CHILD_PROC = None


def _forward_term_and_exit(signum, frame):
    """SIGTERM handler of the parent: a time limit that TERMs this
    process must not orphan the measurement child, which holds the chip.
    Stop it, then exit."""
    del signum, frame
    proc = _ACTIVE_CHILD_PROC
    if proc is not None and proc.poll() is None:
        _graceful_stop(proc, grace=25)
    os._exit(1)


def _graceful_stop(proc, grace: float = 30.0):
    """TERM first with a grace period, then KILL: a SIGTERM lets the
    child release the chip cleanly.  Does not read the pipe — callers
    own proc.stdout (possibly from a reader thread)."""
    proc.terminate()
    try:
        proc.wait(timeout=grace)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()


def _run_config(timeout_s: float | None = None, **kwargs):
    """Run ONE _bench_config measurement in its own subprocess.

    Isolation buys two things an in-process sweep could not have:
    (a) a time limit — a compile that never ends costs ``timeout_s``,
    not the whole sweep; and (b) a clean allocator — after an OOM even
    tiny follow-up allocations can fail in the same process, so every
    config starts in a fresh one.  Children run one after another,
    never two at once: a chip belongs to one process.

    Raises :class:`NoTpuError` when the child found no TPU, else
    RuntimeError carrying the child's error text (so the caller's OOM
    detection keeps working) or a 'config timeout' marker.  The child's
    stderr is captured and re-streamed to OUR stderr; when the child
    dies with no record the stderr tail rides in the exception."""
    global _ACTIVE_CHILD_PROC
    env = dict(os.environ)
    env[_CONFIG_ENV] = json.dumps(kwargs)
    proc = subprocess.Popen([sys.executable, os.path.abspath(__file__)],
                            env=env, cwd=_REPO, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE)
    _ACTIVE_CHILD_PROC = proc
    err = b""
    try:
        out, err = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        # keep DRAINING the pipes while the TERM grace runs: a child
        # flushing a large traceback into a full 64KB pipe would
        # otherwise block, ignore the TERM, and get hard-killed
        drained = {}

        def _drain():
            drained["out"], drained["err"] = proc.communicate()

        reader = threading.Thread(target=_drain, daemon=True)
        reader.start()
        _graceful_stop(proc)
        reader.join(timeout=10)
        _forward_child_stderr(drained.get("err") or b"")
        raise RuntimeError(f"config timeout>{timeout_s}s: {kwargs}")
    finally:
        _ACTIVE_CHILD_PROC = None
    if proc.returncode == _NO_TPU_EXIT:
        raise NoTpuError((err or b"").decode(errors="replace").strip()
                         .splitlines()[-1])
    _forward_child_stderr(err)
    rec = _last_tagged_json(
        out or b"", lambda r: "config_result" in r or "config_error" in r)
    if rec is None:
        tail = (err or b"").decode(errors="replace").strip()[-2000:]
        raise RuntimeError(f"config child rc={proc.returncode}, no record; "
                           f"stderr tail: {tail or '(empty)'}")
    if "config_error" in rec:
        raise RuntimeError(rec["config_error"])
    return rec["config_result"]


def _forward_child_stderr(err: bytes) -> None:
    """Captured child stderr still belongs on our stderr (the sweep's
    per-config diagnostics read it live before capture existed)."""
    if err:
        sys.stderr.write(err.decode(errors="replace"))
        sys.stderr.flush()


def _is_oom(exc) -> bool:
    text = f"{type(exc).__name__}: {exc}".lower()
    return ("resource_exhausted" in text or "out of memory" in text
            or "oom" in text or "exceeds the memory" in text)


_BENCH_RUN_ID = None


def _bench_run_id():
    """One id per bench invocation, stamped into every record (interim
    and final) — the obs run-identity contract (obs/runctx.py), so a
    directory of bench records aggregates/splits like any other
    ``milnce.obs/v1`` artifact."""
    global _BENCH_RUN_ID
    if _BENCH_RUN_ID is None:
        from milnce_tpu.obs.runctx import auto_run_id

        _BENCH_RUN_ID = auto_run_id("bench-")
    return _BENCH_RUN_ID


def _make_record(best, frames, size):
    if best.get("platform") != "tpu":
        raise ValueError(
            "no record for a row that did not run on a TPU (platform="
            f"{best.get('platform')!r}): a CPU number is never written "
            "under the device metric's name")
    value = best["clips_per_sec_per_chip"]
    out = {
        # versioned obs envelope (milnce_tpu/obs/export.py): train bench
        # records share one schema with SERVE_BENCH_*.json and registry
        # snapshots, so scripts/obs_report.py can summarize/gate all of
        # them.
        "schema": "milnce.obs/v1",
        "kind": "train_bench",
        "run_id": _bench_run_id(),
        "process_index": 0,
        "metric": f"train_step clips/sec/chip ({frames}f@{size}, "
                  f"{best['dtype']}, batch {best['batch']}"
                  + (", s2d stem" if best.get("s2d") else "")
                  + (", fold2d convs"
                     if best.get("conv_impl") == "fold2d" else "")
                  + (", tuned impl map"
                     if best.get("impl_map") else "")
                  + (", chunked loss"
                     if best.get("loss_impl") not in (None, "dense")
                     else "") + ")",
        "value": value,
        "unit": "clips/sec/chip",
        "vs_baseline": round(value / BASELINE_THROUGHPUT, 3),
        "timing": "differenced+host-materialized",
        # The 95.35 anchor predates host-materialized differenced timing;
        # part of any ratio != 1 is that method change.  Dropped when the
        # anchor is re-measured under the current method.
        "anchor_timing": "latency-inclusive (pre-differencing)",
        "platform": best["platform"],
        "device_kind": best["device_kind"],
        "n_chips": best["n_chips"],
    }
    if "mfu" in best:
        out["mfu"] = best["mfu"]
    # mesh layout + sharding-map identity (ISSUE 6): obs_report --check
    # can only compare 1-D and 2-D runs if the record says which layout
    # (and which map) produced the number.  predicted_peak_bytes_per_chip
    # (ISSUE 8) makes memory drift gateable the same way.
    # dtype_census_hash (Pass 5) rides along so a cross-precision
    # compare is flagged, not silently scored as a speedup/regression
    for key in ("mesh", "sharding_map_hash", "params_sharded",
                "predicted_peak_bytes_per_chip", "dtype_census_hash"):
        if best.get(key) is not None:
            out[key] = best[key]
    return out


def run_bench():
    """Sweep orchestrator: picks configs, runs each in its own
    time-limited subprocess (_run_config), streams an interim
    best-so-far record after every row.  Never imports JAX itself: every
    row names the device it ran on, as its measuring process saw it.
    Raises :class:`NoTpuError` when the first child finds no TPU."""
    cfg_timeout = float(os.environ.get("MILNCE_BENCH_CONFIG_TIMEOUT", "900"))

    # opt-in: bench the space_to_depth stem (what the original TPU
    # training used) — densifies conv1, the stage most starved on the
    # 128-wide MXU (see BENCH_NOTES.md headroom notes)
    s2d = os.environ.get("MILNCE_BENCH_S2D") == "1"
    # conv lowering for the sweep: 'native' 3D convs, 'fold2d' (2D-conv
    # decomposition) or 'im2col' (patches + one dot_general,
    # models/conv3d.py); a fold2d row is also auto-measured at the
    # winning operating point (opt out: MILNCE_BENCH_FOLD2D=0)
    conv_impl = os.environ.get("MILNCE_BENCH_CONV", "native")
    # per-stage impl map for every sweep row: inline spec or the
    # stage_probe --autotune artifact path (absolute, or relative to the
    # repo root so the child resolves it from its own cwd)
    impl_map = os.environ.get("MILNCE_BENCH_IMPL_MAP", "")
    if impl_map and "=" not in impl_map and not os.path.isabs(impl_map):
        impl_map = os.path.join(_REPO, impl_map)
    # mesh layout for the sweep rows: '' = 1-D data mesh (default),
    # 'data,model[=N]' runs the WHOLE sweep on the 2-D FSDP grid; with
    # the default 1-D sweep a mesh_2d comparison row is auto-measured at
    # the winning operating point (opt out: MILNCE_BENCH_MESH_2D=0)
    mesh_spec = os.environ.get("MILNCE_BENCH_MESH", "")
    # MIL-NCE loss impl for every sweep row: 'dense' (default), 'chunked'
    # (streaming loss), or 'auto' (the prefers_chunked budget rule); with
    # the default a milnce_chunked comparison row is auto-measured at the
    # winning operating point (opt out: MILNCE_BENCH_MILNCE_CHUNKED=0)
    loss_impl = os.environ.get("MILNCE_BENCH_LOSS_IMPL", "dense")
    frames, size, words, k = 16, 224, 20, 5
    # differenced W(k2)-W(k1) timing cancels dispatch latency, so the
    # scan only needs enough inner steps to dominate scheduler jitter
    inner = 4
    # 512 stays excluded (OOMed even with remat before PR 1)
    plans = [("bfloat16", [64, 128, 192, 256, 384], False),
             ("float32", [32, 64], False)]

    results = []
    # (dtype, remat, s2d) -> (batch, flops) seeds, XLA-sourced only (the
    # analytic model is free to recompute exactly at every batch)
    flops_seen = {}

    def hint(dtype, remat, s2d_, batch):
        seen = flops_seen.get((dtype, remat, s2d_))
        if not seen:
            return None
        # model FLOPs scale linearly in batch; the MIL-NCE logits matmul
        # is quadratic — rescale the two terms separately
        from milnce_tpu.utils.roofline import milnce_logits_flops

        b0, f0 = seen
        linear = f0 - milnce_logits_flops(b0, k)
        return linear * batch / b0 + milnce_logits_flops(batch, k)

    def measure(dtype, batch, remat, s2d, conv_impl, loss="milnce",
                grad_accum=1, timeout_s=None, conv_impl_map=None,
                mesh=None, impl=None, frames_=None, size_=None):
        # frames_/size_ override the sweep's fixed input shape (the
        # curriculum stage rows); hint() seeds are keyed per-shape
        # implicitly (one sweep shape), so off-shape rows skip the hint
        off_shape = frames_ is not None or size_ is not None
        return _run_config(
            timeout_s=timeout_s or cfg_timeout,
            dtype=dtype, batch=batch,
            frames=frames if frames_ is None else frames_,
            size=size if size_ is None else size_, words=words, k=k,
            remat=remat,
            inner=1 if grad_accum > 1 else inner, s2d=s2d,
            conv_impl=conv_impl,
            conv_impl_map=impl_map if conv_impl_map is None else conv_impl_map,
            loss=loss, grad_accum=grad_accum,
            mesh_spec=mesh_spec if mesh is None else mesh,
            loss_impl=loss_impl if impl is None else impl,
            flops_hint=None if grad_accum > 1 or off_shape
            else hint(dtype, remat, s2d, batch))

    for dtype, batches, plan_remat in plans:
        prev = 0.0
        remat = plan_remat
        for batch in batches:
            try:
                r = measure(dtype, batch, remat, s2d, conv_impl)
            except NoTpuError:
                raise       # the sweep's first child: nothing to measure
            except Exception as exc:
                if _is_oom(exc) and not remat:
                    _note(f"bench: {dtype} batch={batch} OOM — retrying with "
                          "remat (kept on for larger batches)")
                    remat = True   # larger batches can only need MORE memory
                    # remat recomputes activations, so this row dropping
                    # below the last non-remat row is expected — reset the
                    # knee reference so the drop doesn't end the plan
                    # before larger remat batches get their shot.
                    prev = 0.0
                    try:
                        r = measure(dtype, batch, True, s2d, conv_impl)
                    except Exception as exc2:
                        _note(f"bench: {dtype} batch={batch} remat also failed: "
                              f"{type(exc2).__name__} — stopping sweep")
                        break
                else:
                    # Never discard the measurements already in hand for a
                    # mid-sweep failure: stop this plan, keep the results.
                    _note(f"bench: {dtype} batch={batch} failed "
                          f"({type(exc).__name__}: {exc}) — stopping sweep")
                    break
            if r["flops_per_step"] and r.get("flops_source") == "xla":
                flops_seen.setdefault((dtype, remat, s2d),
                                      (batch, r["flops_per_step"]))
            if prev and r["clips_per_sec_per_chip"] < 0.90 * prev:
                # >10% regression vs a SMALLER batch is not the usual
                # diminishing-returns knee — it's a padded-batch/tiling
                # cliff (the observed 281-vs-393 clips/s drop at batch
                # 192; PERF.md "Batch cliffs") and the row is flagged so
                # BENCH_NOTES readers don't average across it
                r["cliff_vs_smaller_batch"] = round(
                    1.0 - r["clips_per_sec_per_chip"] / prev, 3)
                _note(f"bench: {dtype} batch={batch} regresses "
                      f"{100 * r['cliff_vs_smaller_batch']:.0f}% vs the "
                      "smaller batch — padded-batch/tiling cliff "
                      "(PERF.md)")
            _note(f"bench: {r}")
            results.append(r)
            # Interim record after every config: a later config that
            # never ends must not cost the rows already measured.
            _emit(_make_record(
                max(results, key=lambda x: x["clips_per_sec_per_chip"]),
                frames, size))
            # stop climbing only once throughput actually DECLINES past a
            # small noise margin: with 192 interposed in the ladder a
            # healthy 128->256 climb splits into two small steps, so a
            # large gain threshold would end the plan before 256/384 ever
            # ran — but an exact <= would let run-to-run jitter (either a
            # dead-flat repeat or a 0.1% dip) decide whether the larger
            # batches get measured at all
            if r["clips_per_sec_per_chip"] < prev * 0.99:
                break
            prev = max(prev, r["clips_per_sec_per_chip"])

    if not results:
        raise RuntimeError(
            "no config produced a measurement — every sweep arm failed "
            "(see stderr for per-config errors)")
    best = max(results, key=lambda r: r["clips_per_sec_per_chip"])

    def extra_row(label, **overrides):
        """One comparison row at the winning operating point, with the
        same record/interim-emit protocol as the sweep rows."""
        nonlocal best
        try:
            kw = dict(dtype=best["dtype"], batch=best["batch"],
                      remat=best["remat"], s2d=best.get("s2d", False),
                      conv_impl=conv_impl)
            kw.update(overrides)
            r = measure(**kw)
            _note(f"bench: {r}")
            results.append(r)
            # comparison rows that change the WORK per clip — a
            # different loss, grad-accum, or the chunked stream's
            # backward recompute — must not displace the headline: the
            # vs_baseline anchor is a dense-loss measurement.  Only a
            # sweep PINNED to chunked (MILNCE_BENCH_LOSS_IMPL=chunked)
            # lifts the impl filter — it is its own headline population;
            # an 'auto' sweep resolves per row, and its forced
            # milnce_chunked comparison row must not slip in on noise.
            pool = [x for x in results
                    if x.get("loss", "milnce") == "milnce"
                    and x.get("grad_accum", 1) == 1
                    and x.get("stage") is None
                    and (loss_impl == "chunked"
                         or x.get("loss_impl") in (None, "dense"))]
            if pool:    # empty = every auto row resolved chunked; keep
                best = max(pool,            # the sweep's own best then
                           key=lambda x: x["clips_per_sec_per_chip"])
            _emit(_make_record(best, frames, size))
        except Exception as exc:
            _note(f"bench: {label} row failed ({type(exc).__name__}: {exc})"
                  " — keeping prior results")

    # space_to_depth row at the winning operating point: the original TPU
    # training used the s2d stem (s3dg.py:214-215, 248-253) precisely
    # because it densifies conv1 for the MXU — always measure the
    # comparison (opt out: MILNCE_BENCH_S2D=0).
    # comparison rows pin conv_impl_map="" so each measures its PURE
    # configuration — with a global MILNCE_BENCH_IMPL_MAP the sweep rows
    # carry the map (the operating point) while these stay the labeled
    # baselines they claim to be (an s2d row under a plain-stem-tuned
    # map would even misapply the conv1 entry to the 2x4x4 kernel)
    if not s2d and os.environ.get("MILNCE_BENCH_S2D") != "0":
        extra_row("s2d", s2d=True, conv_impl_map="")
    # fold2d row: same math lowered as 2D convs (models/conv3d.py) — if
    # XLA's 3D-conv tiling is the MFU sink (PERF.md headroom reading)
    # this row shows it directly.
    if (conv_impl == "native"
            and os.environ.get("MILNCE_BENCH_FOLD2D") != "0"):
        extra_row("fold2d", conv_impl="fold2d", conv_impl_map="")
    # im2col-stem row: the fwd+bwd stage probe convicts conv1 (1% of
    # peak, 102x roofline — STAGE_PROBE_native_fwdbwd.md); this measures
    # the patches+dot_general stem at the winning operating point.  A
    # full autotuned map (MILNCE_BENCH_IMPL_MAP) supersedes it (opt out:
    # MILNCE_BENCH_IM2COL=0).
    if (conv_impl == "native" and not impl_map
            and os.environ.get("MILNCE_BENCH_IM2COL") != "0"):
        extra_row("im2col_stem", s2d=False, conv_impl_map="conv1=im2col")
    # DTW-family row: the Pallas soft-DTW kernel inside the FULL compiled
    # train step (loss sdtw_3, backend auto) at the winning operating
    # point — the fork's signature loss measured on the real chip, not
    # just in the kernel microbench (opt out: MILNCE_BENCH_SDTW=0).
    if os.environ.get("MILNCE_BENCH_SDTW") != "0":
        extra_row("sdtw_3", loss="sdtw_3", s2d=False, conv_impl="native",
                  conv_impl_map="")
    # Chunked MIL-NCE row: the streaming loss (losses/milnce_chunked.py)
    # inside the full compiled step at the winning operating point — the
    # predicted_peak_bytes_per_chip delta vs the dense sweep rows is the
    # memory win, step_ms the recompute cost (opt out:
    # MILNCE_BENCH_MILNCE_CHUNKED=0).  Measured unless the sweep was
    # ALREADY pinned to chunked via MILNCE_BENCH_LOSS_IMPL=chunked — an
    # 'auto' sweep still needs it, since at typical bench shapes the
    # prefers_chunked budget resolves every row to dense.
    if (loss_impl != "chunked"
            and os.environ.get("MILNCE_BENCH_MILNCE_CHUNKED") != "0"):
        extra_row("milnce_chunked", impl="chunked", s2d=False,
                  conv_impl="native", conv_impl_map="")
    # 2-D mesh row: the FSDP (data, model) grid at the winning operating
    # point — mesh shape + sharding-map hash land in the record so
    # obs_report can diff it against the 1-D rows (opt out:
    # MILNCE_BENCH_MESH_2D=0; a sweep already pinned to a 2-D mesh via
    # MILNCE_BENCH_MESH measures nothing extra).
    if (not mesh_spec
            and os.environ.get("MILNCE_BENCH_MESH_2D") != "0"):
        extra_row("mesh_2d", mesh="data,model", s2d=False,
                  conv_impl="native", conv_impl_map="")
    # North-star recipe row: the per-chip slice of the 8192-global-batch
    # training step — 8 embedding-cache microbatches of the winning batch
    # in ONE update (BASELINE.md HMDB-53.1 recipe; memory- and
    # equivalence-proven in tests, measured here).  The row inherits the
    # sweep's mesh and carries mesh/map-hash fields, so the ga=8
    # operating point is comparable against the 25%-down reading taken
    # before PR 1 (and against a 2-D sweep) in obs_report.  Bigger compile + 8x
    # the work per dispatch -> double timeout (opt out:
    # MILNCE_BENCH_GRAD_ACCUM=0).
    if os.environ.get("MILNCE_BENCH_GRAD_ACCUM") != "0":
        extra_row("grad_accum8", batch=8 * best["batch"], grad_accum=8,
                  s2d=False, conv_impl="native", conv_impl_map="",
                  timeout_s=2 * cfg_timeout)

    # Curriculum axis (ISSUE 16): MILNCE_BENCH_CURRICULUM holds a
    # train.curriculum spec — each stage is measured as its own row at
    # the stage's (frames, resolution, batch) on the winning dtype, and
    # the whole-schedule rate (steps-weighted composition of the
    # per-stage rates) is compared against running the SAME total clip
    # count flat at the final stage's full-res shape.  Stage rows carry
    # ``stage``/``stage_label`` and never enter the headline pool:
    # different input shapes are not comparable operating points.
    curriculum_spec = os.environ.get("MILNCE_BENCH_CURRICULUM", "")
    curriculum_summary = None
    if curriculum_spec:
        try:
            # jax-free at module scope (the orchestrator must not hold
            # a backend) — same parser the train loop uses, so the axis
            # refuses exactly the specs run_training would refuse
            from milnce_tpu.train.curriculum import parse_curriculum

            stages = parse_curriculum(curriculum_spec,
                                      default_batch_size=best["batch"])
            # per-stage step counts from the until_step boundaries.  The
            # bench axis requires step-bounded stages (epoch bounds need
            # a dataset size a synthetic bench doesn't have); the
            # open-ended final stage defaults to the bounded stages'
            # total (override: MILNCE_BENCH_CURRICULUM_STEPS).
            stage_steps, prev_bound = [], 0
            for i, st in enumerate(stages[:-1]):
                if st.until_step is None:
                    raise ValueError(
                        f"bench curriculum stage {i} must be bounded by "
                        "until_step — epoch bounds need a dataset size")
                stage_steps.append(st.until_step - prev_bound)
                prev_bound = st.until_step
            final_steps = int(os.environ.get(
                "MILNCE_BENCH_CURRICULUM_STEPS", "0"))
            stage_steps.append(final_steps or sum(stage_steps) or 1000)
            stage_rows = []
            for i, (st, n_steps) in enumerate(zip(stages, stage_steps)):
                r = measure(best["dtype"], st.batch_size, best["remat"],
                            False, "native", conv_impl_map="",
                            frames_=st.num_frames, size_=st.resolution)
                r["stage"] = i
                r["stage_label"] = st.label()
                r["stage_steps"] = n_steps
                _note(f"bench: {r}")
                results.append(r)
                stage_rows.append(r)
            total_clips = sum(r["stage_steps"] * r["batch"]
                              for r in stage_rows)
            # chip-seconds per chip of the whole schedule: each stage
            # contributes steps*batch clips at its own per-chip rate
            sched_time = sum(r["stage_steps"] * r["batch"]
                             / r["clips_per_sec_per_chip"]
                             for r in stage_rows)
            schedule_cps = total_clips / sched_time
            flat_cps = stage_rows[-1]["clips_per_sec_per_chip"]
            curriculum_summary = {
                "spec": curriculum_spec,
                "stages": [{
                    "stage": r["stage"], "label": r["stage_label"],
                    "steps": r["stage_steps"], "batch": r["batch"],
                    "step_ms": r["step_ms"],
                    "clips_per_sec_per_chip": r["clips_per_sec_per_chip"],
                } for r in stage_rows],
                "total_clips": total_clips,
                "schedule_clips_per_sec_per_chip": round(schedule_cps, 3),
                "flat_clips_per_sec_per_chip": flat_cps,
                # flat comparator = the final stage's full-res rate over
                # the same clip COUNT (a throughput comparison — the
                # learning-curve question is PERF.md's, not bench's)
                "speedup_vs_flat": round(schedule_cps / flat_cps, 3),
            }
            _note(f"bench: curriculum schedule "
                  f"{schedule_cps:.2f} clips/s/chip vs flat {flat_cps} "
                  f"at {total_clips} total clips "
                  f"({curriculum_summary['speedup_vs_flat']}x)")
        except Exception as exc:
            _note(f"bench: curriculum axis failed "
                  f"({type(exc).__name__}: {exc}) — keeping prior results")

    _write_notes(results, best, curriculum=curriculum_summary)
    final = _make_record(best, frames, size)
    if curriculum_summary:
        # attached to the headline record, never emitted as its own
        # final line: consumers take the LAST parsable record, and a
        # stage-shaped row must not displace the sweep's measurement
        final["curriculum"] = curriculum_summary
    return final


def _write_notes(results, best, curriculum=None):
    notes = os.path.join(_REPO, "BENCH_NOTES.md")
    hand_notes = ""
    if os.path.exists(notes):
        with open(notes) as fh:
            existing = fh.read()
        # durable hand-written context (methodology caveats, operating-
        # point history) survives the auto-rewrite: everything from the
        # '## Hand notes' heading down is carried over verbatim
        marker = existing.find("## Hand notes")
        if marker >= 0:
            hand_notes = existing[marker:].rstrip()
    try:
        lines = ["# BENCH notes (auto-written by bench.py)", "",
                 f"- device: {best['device_kind']} x{best['n_chips']} "
                 f"(platform={best['platform']})",
                 f"- chosen operating point: dtype={best['dtype']} "
                 f"batch={best['batch']} remat={best['remat']} -> "
                 f"{best['clips_per_sec_per_chip']} clips/sec/chip",
                 "", "| dtype | batch | remat | s2d | conv | map | loss | ga | mesh | stage | step_ms | clips/s/chip | MFU |",
                 "|---|---|---|---|---|---|---|---|---|---|---|---|---|"]
        for r in results:
            clips = str(r["clips_per_sec_per_chip"])
            if r.get("cliff_vs_smaller_batch"):
                clips += (f" (cliff: -{100 * r['cliff_vs_smaller_batch']:.0f}"
                          "% vs smaller batch)")
            loss_lbl = r.get("loss", "milnce")
            if r.get("loss_impl") not in (None, "dense"):
                loss_lbl += f"({r['loss_impl']})"      # streaming MIL-NCE
            stage_lbl = ("-" if r.get("stage") is None
                         else f"{r['stage']} ({r.get('stage_label', '?')})")
            lines.append(f"| {r['dtype']} | {r['batch']} | {r['remat']} | "
                         f"{r.get('s2d', False)} | "
                         f"{r.get('conv_impl', 'native')} | "
                         f"{'tuned' if r.get('impl_map') else '-'} | "
                         f"{loss_lbl} | "
                         f"{r.get('grad_accum', 1)} | "
                         f"{r.get('mesh', '-')} | "
                         f"{stage_lbl} | "
                         f"{r['step_ms']} | {clips} | "
                         f"{r.get('mfu', '-')} |")
        maps2d = sorted({r["sharding_map_hash"] for r in results
                         if r.get("sharding_map_hash")})
        if maps2d:
            lines += ["", "2-D rows' sharding-map hash: "
                      + "; ".join(f"`{h}`" for h in maps2d)
                      + " (per-param layout: parallel/sharding_map.py "
                      "describe_map; PERF.md '2-D mesh & sharding map')."]
        maps = sorted({r["impl_map"] for r in results if r.get("impl_map")})
        if maps:
            lines += ["", "Per-stage impl map for 'tuned' rows: "
                      + "; ".join(f"`{m}`" for m in maps)
                      + " (stage_probe --autotune artifact / inline spec)."]
        if any(r.get("cliff_vs_smaller_batch") for r in results):
            lines += ["", "Rows marked 'cliff' regress >10% clips/s vs a "
                      "SMALLER batch — a padded-batch/tiling cliff, not "
                      "the usual diminishing-returns knee (PERF.md "
                      "'Batch cliffs')."]
        if curriculum:
            lines += ["", "## Curriculum schedule", "",
                      f"- spec: `{curriculum['spec']}`",
                      f"- whole-schedule rate: "
                      f"{curriculum['schedule_clips_per_sec_per_chip']} "
                      "clips/sec/chip vs flat full-res "
                      f"{curriculum['flat_clips_per_sec_per_chip']} at "
                      f"equal total clips ({curriculum['total_clips']}) "
                      f"-> **{curriculum['speedup_vs_flat']}x**",
                      "- throughput-equal comparison only: same clip "
                      "count, not necessarily the same learning curve "
                      "(PERF.md 'Curriculum training'); stage rows above "
                      "carry their per-stage shapes in the `stage` "
                      "column and are excluded from the headline "
                      "operating point."]
        lines += ["", "Roofline context for these numbers: PERF.md "
                  "(analytic per-stage FLOPs/bytes/intensity model)."]
        if hand_notes:
            lines += ["", hand_notes]
        with open(os.path.join(_REPO, "BENCH_NOTES.md"), "w") as fh:
            fh.write("\n".join(lines) + "\n")
    except Exception as exc:
        _note(f"bench: could not write BENCH_NOTES.md: {exc}")


def _measure_child(cfg_json: str) -> int:
    """Measurement child: time exactly ONE config in this fresh process
    and hand the result dict up as a tagged JSON line.  The only place
    bench.py touches JAX.  Errors are data too — the parent's OOM and
    timeout handling needs the text — so they go to stdout tagged."""
    import jax

    from milnce_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        _note(f"bench: no TPU (jax found platform={dev.platform!r}, "
              f"device_kind={dev.device_kind!r}) — nothing is measured "
              "off the chip")
        return _NO_TPU_EXIT
    try:
        r = _bench_config(**json.loads(cfg_json))
    except Exception as exc:
        _emit({"config_error": f"{type(exc).__name__}: {exc}"})
        return 1
    _emit({"config_result": r})
    return 0


def main() -> int:
    cfg_json = os.environ.get(_CONFIG_ENV)
    if cfg_json:
        return _measure_child(cfg_json)
    import signal

    signal.signal(signal.SIGTERM, _forward_term_and_exit)
    try:
        _emit(run_bench())
    except NoTpuError as exc:
        _note(str(exc))
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
