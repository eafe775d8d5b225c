"""Epoch driver: the orchestration layer.

Mirrors the reference's main_worker + train loop responsibilities
(main_distributed.py:65-224) minus everything XLA/the mesh already does:
no DDP wrapper, no per-GPU batch arithmetic, no CUDA device pinning.

Logging format parity: every ``n_display`` steps emit epoch, elapsed
time, epoch progress, windowed mean loss, and current LR
(main_distributed.py:211-222), to stdout and a logfile under
``log_root`` (:304-306).
"""

from __future__ import annotations

import json
import os
import signal
import time
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from milnce_tpu import elastic
from milnce_tpu.config import Config
from milnce_tpu.data.pipeline import (ShardedLoader, device_prefetch,
                                      flatten_text, shard_placer)
from milnce_tpu.data.synthetic import SyntheticVideoTextSource
from milnce_tpu.models.build import build_model
from milnce_tpu.obs import export as obs_export
from milnce_tpu.obs import goodput as obs_goodput
from milnce_tpu.obs import metrics as obs_metrics
from milnce_tpu.obs import runctx as obs_runctx
from milnce_tpu.obs import spans as obs_spans
from milnce_tpu.obs.anomaly import EwmaSpikeDetector
from milnce_tpu.obs.capture import ProfilerCapture
from milnce_tpu.parallel.mesh import (broadcast_str, build_mesh,
                                      describe_devices,
                                      initialize_distributed,
                                      replicate_to_mesh)
from milnce_tpu.resilience import faults
from milnce_tpu.train import curriculum
from milnce_tpu.train.checkpoint import CheckpointManager
from milnce_tpu.train.schedule import (build_host_schedule_total,
                                       build_schedule_total)
from milnce_tpu.train.state import TrainState, build_optimizer, create_train_state
from milnce_tpu.train.step import make_train_step
from milnce_tpu.utils.logging import RunLogger
from milnce_tpu.utils.profiling import StepTimer, maybe_trace
from milnce_tpu.utils.roofline import (chip_peak_flops as roofline_peak,
                                       mfu as roofline_mfu,
                                       train_step_flops as
                                       roofline_step_flops)


def build_source(cfg: Config, log_fn=None):
    if cfg.data.synthetic:
        return SyntheticVideoTextSource(cfg.data, vocab_size=cfg.model.vocab_size)
    from milnce_tpu.data.datasets import HowTo100MSource

    return HowTo100MSource(cfg.data, cfg.model, log_fn=log_fn)


def resume_batch_offset(restored_step: int, steps_per_epoch: int) -> int:
    """Mid-epoch resume position: how many global batches of the current
    epoch the restored step counter has already consumed (an end-of-epoch
    save lands on the boundary -> 0).  Only valid while steps_per_epoch
    matches the run being resumed.

    Flat-run reference semantics only: run_training itself derives the
    offset from the curriculum plan's ``locate`` (train/curriculum.py),
    which reduces to exactly this modulo for a single-stage plan — the
    equivalence is pinned by tests/test_curriculum.py."""
    return int(restored_step) % steps_per_epoch


def stop_save_label(epoch: int, opt_step: int,
                    steps_per_epoch: int) -> tuple:
    """(checkpoint label, force) for a mid-epoch stop at ``opt_step``.

    A stop landing ON the epoch's last batch labels epoch+1 (a
    current-epoch label with offset 0 would retrain the whole epoch on
    resume); any other stop labels the CURRENT epoch and must FORCE the
    save — the previous epoch's boundary save holds the same label and
    Orbax would otherwise silently skip it, dropping the partial epoch."""
    done = opt_step % steps_per_epoch == 0
    return (epoch + 1 if done else epoch), (not done)


def stop_save_label_planned(epoch: int, opt_step: int, plan) -> tuple:
    """Plan-aware twin of :func:`stop_save_label`: per-stage batch sizes
    make the epoch boundary a plan lookup, not a modulo.  Identical to
    the flat helper for single-stage plans (tests/test_curriculum.py)."""
    done = opt_step == plan.epoch_end_step(epoch)
    return (epoch + 1 if done else epoch), (not done)


# Finite-guard window accumulators: pure device-side jnp (jitted), so the
# per-step bookkeeping adds one tiny async dispatch and ZERO host syncs.
# Skipped (non-finite) steps are excluded from the windowed loss mean —
# their loss is the NaN the guard just refused to apply — and drive a
# consecutive-skip counter for the loop's circuit breaker.
def _guard_restart(loss, skipped, consec, total):
    keep = skipped == 0
    running = jnp.where(keep, loss, jnp.zeros_like(loss))
    valid = keep.astype(jnp.int32)
    consec = jnp.where(keep, jnp.zeros_like(consec), consec + 1)
    return running, valid, consec, total + skipped


def _guard_acc(running, valid, consec, total, loss, skipped):
    keep = skipped == 0
    return (jnp.where(keep, running + loss, running),
            valid + keep.astype(valid.dtype),
            jnp.where(keep, jnp.zeros_like(consec), consec + 1),
            total + skipped)


_guard_restart_j = jax.jit(_guard_restart)
_guard_acc_j = jax.jit(_guard_acc)


def _fetch_guard_window(running, valid, consec, total):
    """Display-cadence fetch of the guarded window: ONE host transfer for
    the mean-over-valid-steps loss plus both skip counters."""
    r, v, c, t = jax.device_get((running, valid, consec, total))
    mean = float(r) / int(v) if int(v) else float("nan")
    return mean, int(c), int(t)


@dataclass
class TrainResult:
    state: TrainState
    steps: int
    last_loss: float
    skipped_steps: int = 0      # finite-guard: updates skipped on
                                # non-finite gradients (0 when disabled)
    rollbacks: int = 0          # circuit-breaker checkpoint restores
    stage: int = 0              # curriculum stage at exit (flat runs: 0)
    drained: bool = False       # exited on a preemption drain (SIGTERM /
                                # signal file / host.preempt) with a
                                # forced checkpoint + ELASTIC_STAMP —
                                # the CLI maps this to DRAINED_EXIT_CODE


def _finalize_goodput_ledger(rec, rec_path, run_id, process_index,
                             registry, obs_dir, log_fn,
                             extra: Optional[dict] = None) -> None:
    """End-of-run goodput ledger (obs/goodput.py): read back this run's
    event stream (the JSONL file when one exists — the ring is bounded
    — selecting THIS run out of a shared append-only file by run_id),
    export the attribution as ``milnce.obs/v1`` gauges, and write the
    per-run summary snapshot next to the stream.  Best-effort by
    design: the ledger must never turn a finished (or already-failing)
    run into an error."""
    try:
        if rec_path and os.path.exists(rec_path):
            with open(rec_path) as fh:
                records = [json.loads(line) for line in fh if line.strip()]
        else:
            records = rec.tail()
        ledger = obs_goodput.compute_ledger(records, run_id=run_id)
        obs_goodput.ledger_to_registry(ledger, registry)
        if rec_path:
            name = ("GOODPUT.json" if not process_index
                    else f"GOODPUT.p{process_index}.json")
            payload = ledger.to_extra()
            payload.update(extra or {})     # e.g. the live mfu gauge's
            #                                 last value, gate-able at
            #                                 top level like clips/s
            obs_export.write_snapshot(
                os.path.join(obs_dir, name), registry, kind="goodput",
                extra=payload)
        log_fn(ledger.summary_line())
    except Exception as exc:
        log_fn(f"goodput ledger failed ({type(exc).__name__}: {exc}) — "
               "telemetry only, run result unaffected")


def _in_training_eval(cfg: Config, model, state: TrainState, mesh,
                      logger) -> None:
    """Periodic downstream eval during training.  The reference intended
    an HMDB probe here but shipped it dead (main_distributed.py:243-287,
    NameError'd test_loader — SURVEY §2.4); ours runs, and also covers
    the retrieval tasks (train.eval_task: hmdb | youcook | msrvtt).
    Dispatch is shared with the eval CLI (eval/runner.py)."""
    from milnce_tpu.data.datasets import build_tokenizer
    from milnce_tpu.eval.runner import evaluate_task

    decoder = None
    if cfg.data.synthetic:      # hermetic runs eval on the fake decoder too
        from milnce_tpu.data.video import FakeDecoder

        decoder = FakeDecoder()
    variables = {"params": state.params, "batch_stats": state.batch_stats}
    task = cfg.train.eval_task
    tokenizer = (None if task == "hmdb" else
                 build_tokenizer(cfg.model, cfg.data.eval_max_words))
    metrics = evaluate_task(
        task, model, variables, mesh, data_cfg=cfg.data,
        csv_path=cfg.data.eval_csv, video_root=cfg.data.eval_video_root,
        tokenizer=tokenizer, num_clip=cfg.train.num_windows_test,
        batch_size=cfg.train.batch_size_val, decoder=decoder,
        max_words=cfg.data.eval_max_words)
    if task == "hmdb":
        logger.log(f"HMDB linear probe: {metrics}")
    else:
        from milnce_tpu.eval.metrics import format_metrics

        logger.log(f"{task} retrieval: {format_metrics(metrics)}")


def run_training(cfg: Config, max_steps: Optional[int] = None) -> TrainResult:
    if max_steps is None:
        max_steps = cfg.train.max_steps
    if cfg.model.text_tower != "bow":   # fail before any init
        raise ValueError(
            f"model.text_tower={cfg.model.text_tower!r} cannot be trained: "
            "a language-model sentence tower is served from an export only "
            "(no optimizer share or parameter mask for routed layers); "
            "train with model.text_tower='bow'")
    if cfg.train.evaluate:
        from milnce_tpu.eval.runner import EVAL_TASKS

        if cfg.train.eval_task not in EVAL_TASKS:   # fail before any init
            raise ValueError(
                f"unknown train.eval_task {cfg.train.eval_task!r}; "
                f"expected one of {'|'.join(EVAL_TASKS)}")
    if cfg.train.faults:
        # deterministic fault injection (chaos tests / failure drills):
        # armed before any decode or step build so every site sees it
        faults.arm(cfg.train.faults)
    initialize_distributed(cfg.parallel)
    # Elastic capacity (milnce_tpu/elastic/): parallel.num_devices builds
    # the mesh over a PREFIX of the local devices — how a drained run
    # resumes onto a smaller mesh on the same host (8-way -> 4-way) and
    # how the chaos tests change topology within one process.
    mesh_devices = None
    if cfg.parallel.num_devices:
        avail = jax.devices()
        if cfg.parallel.num_devices > len(avail):
            raise ValueError(
                f"parallel.num_devices={cfg.parallel.num_devices} exceeds "
                f"the {len(avail)} visible devices")
        mesh_devices = avail[:cfg.parallel.num_devices]
    mesh = build_mesh(cfg.parallel, devices=mesh_devices)
    axis = cfg.parallel.data_axis
    # 2-D (data, model) mesh: the batch shards over BOTH axes (every
    # chip is a data shard — global-batch semantics identical to a 1-D
    # mesh of the same size) and the train state shards per the FSDP
    # sharding map (parallel/sharding_map.py, PERF.md).
    model_axis = cfg.parallel.model_axis
    if model_axis and model_axis not in mesh.axis_names:
        # refuse-loudly, like every other silent-replication path in the
        # 2-D stack (GL009, build_param_specs, bench's shards-NOTHING):
        # a model_axis that never made it into the mesh would quietly
        # train 1-D while the config claims FSDP
        raise ValueError(
            f"parallel.model_axis={model_axis!r} is set but the mesh has "
            f"axes {mesh.axis_names} — set parallel.model_parallel_size "
            f"> 1 (it is {cfg.parallel.model_parallel_size})")
    batch_axes = (axis, model_axis) if model_axis else axis

    logger = RunLogger(cfg.train.log_root, cfg.train.checkpoint_dir,
                       enabled=jax.process_index() == 0 and cfg.train.verbose)
    # first line: where this run really executes
    logger.log(f"{describe_devices(mesh)} | mesh: {dict(mesh.shape)} "
               f"| global batch: {cfg.train.batch_size}")

    # Observability (obs/, OBSERVABILITY.md): an append-only span/event
    # stream (RUN_EVENTS.jsonl) plus display-cadence metrics on the
    # process-wide registry.  Recording is HOST-side only — the gauges
    # are fed exclusively from values the display fetch already
    # materialized, and the per-step span times host dispatch, never the
    # device (pinned by the train_step_milnce_instrumented trace
    # invariant: identical collectives, survives the transfer guard).
    #
    # Run identity: ONE run_id across the whole pod (process 0's value,
    # broadcast), stamped on every event line and snapshot so streams
    # sharing an obs_dir split cleanly and pod aggregation can verify
    # same-run before merging (obs/runctx.py, obs/aggregate.py).
    process_index = jax.process_index()
    run_id = cfg.train.run_id or broadcast_str(obs_runctx.auto_run_id())
    prev_runctx = obs_runctx.set_run_context(run_id, process_index)
    obs_dir = cfg.train.obs_dir or cfg.train.log_root
    rec_path = None
    if cfg.train.verbose and obs_dir:
        # EVERY process writes its own stream (process 0 keeps the
        # unsuffixed name) — the per-host streams are what obs_report
        # --merge turns into the pod view with straggler skew
        os.makedirs(obs_dir, exist_ok=True)
        name = ("RUN_EVENTS.jsonl" if process_index == 0
                else f"RUN_EVENTS.p{process_index}.jsonl")
        rec_path = os.path.join(obs_dir, name)
    rec = obs_spans.SpanRecorder(path=rec_path)
    rec.event("run.start", seed=cfg.train.seed,
              batch_size=cfg.train.batch_size,
              processes=jax.process_count())
    reg = obs_metrics.registry()
    m_steps = reg.counter("milnce_train_steps_total",
                          "optimizer steps dispatched (display-cadence fed)")
    g_loss = reg.gauge("milnce_train_loss",
                       "windowed mean training loss at the last display")
    g_lr = reg.gauge("milnce_train_learning_rate",
                     "current LR (numpy host-schedule twin)")
    g_tput = reg.gauge("milnce_train_clips_per_sec",
                       "windowed throughput at the last display")
    g_skipped = reg.gauge("milnce_train_skipped_steps",
                          "finite-guard skipped updates (run total)")
    m_rollbacks = reg.counter("milnce_train_rollbacks_total",
                              "circuit-breaker checkpoint restores")
    g_mfu = reg.gauge("milnce_train_mfu",
                      "live MFU at the last display (roofline step FLOPs "
                      "over device peak; only set when both are known)")
    g_goodput = reg.gauge("milnce_train_goodput_fraction",
                          "windowed goodput at the last display: elapsed "
                          "minus data-wait, times the applied-update "
                          "fraction, over elapsed")
    g_stage = reg.gauge("milnce_train_stage",
                        "live curriculum stage index (0-based; flat runs "
                        "stay 0)")
    # the data-wait accumulator device_prefetch feeds (create-or-get:
    # same child) — window deltas drive the live goodput gauge
    m_data_wait = reg.counter(
        "milnce_data_wait_seconds_total",
        "host seconds the training loop blocked waiting for batch data")

    # Live MFU denominator/numerator (utils/roofline.py — the SAME
    # table + formula bench.py uses, pinned within 2% by
    # tests/test_goodput.py).  FLOPs only for configs the analytic
    # model covers (bench.py applies the identical guard: DTW losses
    # and the two-pass grad-accum step would make the number fiction).
    n_chips = int(mesh.devices.size)    # the mesh's chips, not the
    #                                     host's — an elastic 4-way resume
    #                                     on an 8-device host must not
    #                                     halve its MFU by fiction
    # None off the TPU (gauge off); a TPU kind the table lacks raises
    peak = roofline_peak(mesh.devices.flat[0])

    def _stage_step_flops(st) -> Optional[float]:
        # per-stage: the curriculum changes batch/frames/resolution, and
        # a stale FLOPs count would make the live MFU gauge fiction
        if not (peak and cfg.loss.name == "milnce"
                and cfg.train.grad_accum == 1):
            return None
        return roofline_step_flops(
            st.batch_size, st.num_frames, st.resolution,
            cfg.data.num_candidates, cfg.data.max_words,
            space_to_depth=cfg.model.space_to_depth,
            inception_blocks=cfg.model.inception_blocks,
            embedding_dim=cfg.model.embedding_dim,
            word_dim=cfg.model.word_embedding_dim,
            hidden=cfg.model.text_hidden_dim)

    # Anomaly-triggered profiler capture (obs/anomaly.py + obs/
    # capture.py): the EWMA detector watches the window step time the
    # display already computes (host-side, no new syncs); a spike emits
    # an 'anomaly' event and — when a capture dir is configured — arms
    # ONE bounded jax.profiler capture.  SIGUSR1 arms it manually.
    profiler_capture = None
    if cfg.train.capture_dir:
        profiler_capture = ProfilerCapture(
            cfg.train.capture_dir,
            duration_s=cfg.train.capture_ms / 1e3,
            cooldown_s=cfg.train.anomaly_cooldown_s,
            max_captures=cfg.train.capture_max, recorder=rec)
    spike_detector = None
    if cfg.train.anomaly_detect:
        spike_detector = EwmaSpikeDetector(
            "train.step_ms", ratio=cfg.train.anomaly_ratio,
            warmup=cfg.train.anomaly_warmup,
            cooldown_s=cfg.train.anomaly_cooldown_s, recorder=rec,
            on_anomaly=((lambda v, e: profiler_capture.arm(
                reason="step_time_spike"))
                if profiler_capture is not None else None))
    capture_requested = {"flag": False}     # SIGUSR1, acted on at display
    prev_usr1 = None
    if profiler_capture is not None:
        def _on_sigusr1(signum, frame):
            capture_requested["flag"] = True

        try:
            prev_usr1 = signal.signal(signal.SIGUSR1, _on_sigusr1)
        except ValueError:       # non-main thread (tests)
            prev_usr1 = None

    # ----- curriculum plan (train/curriculum.py) -----
    # Flat runs are a single open-ended stage through the SAME plan
    # machinery, so resume offsets / epoch progress / schedule totals
    # have exactly one derivation (pinned equal to the historical
    # modulo helpers by tests/test_curriculum.py).
    stages = curriculum.parse_curriculum(
        cfg.train.curriculum, default_batch_size=cfg.train.batch_size)
    curriculum_on = bool(stages)
    if not curriculum_on:
        stages = curriculum.flat_stages(cfg.data, cfg.train.batch_size)
    stage_cfgs = [curriculum.stage_config(cfg, st) for st in stages]
    source0 = build_source(stage_cfgs[0], log_fn=logger.log)
    plan = curriculum.plan_curriculum(stages, len(source0),
                                      cfg.optim.epochs)
    if curriculum_on:
        rec.event("curriculum.plan", total_steps=plan.total_steps,
                  stages=[{"num_frames": s.num_frames,
                           "resolution": s.resolution,
                           "batch_size": s.batch_size}
                          for s in plan.stages])
        logger.log("curriculum: "
                   + " -> ".join(s.label() for s in plan.stages)
                   + f" ({plan.total_steps} steps planned)")

    def _stage_pipeline(idx: int):
        """(source, loader, zero_start, step_flops) for one stage —
        rebuilt at every boundary (the decode shapes and the hoisted
        start fallback are per-stage; the model/optimizer are not)."""
        st = plan.stages[idx]
        src = (source0 if idx == 0
               else build_source(stage_cfgs[idx], log_fn=logger.log))
        ldr = ShardedLoader(src, st.batch_size, seed=cfg.train.seed,
                            num_threads=cfg.data.num_reader_threads,
                            lookahead_batches=cfg.data.decode_lookahead,
                            sample_timeout=cfg.data.sample_timeout,
                            timeout_retries=cfg.data.sample_timeout_retries,
                            log_fn=logger.log)
        zstart = shard_placer(mesh, batch_axes)(
            np.zeros((st.batch_size // jax.process_count(), ),
                     np.float32))
        return src, ldr, zstart, _stage_step_flops(st)

    model = build_model(cfg.model, bn_axis_name=batch_axes)
    rng = jax.random.PRNGKey(cfg.train.seed)
    # init at stage-0 shapes: the TrainState tree is shape-invariant
    # across stages (conv/BN params don't depend on frames/resolution),
    # so transitions and checkpoints ride place_state untouched
    st0 = plan.stages[0]
    sample_video = np.zeros((2, st0.num_frames, st0.resolution,
                             st0.resolution, 3), np.float32)
    sample_text = np.zeros((2 * cfg.data.num_candidates, cfg.data.max_words),
                           np.int32)
    # init and optimizer init each as ONE jitted program: eagerly, Flax
    # runs the whole forward op by op, and on a cold chip every op is a
    # compile of its own — 470 s before the first step of the full-width
    # model on a v5e against 138 s for the step's own compile
    variables = jax.jit(model.init)(rng, sample_video, sample_text)
    if cfg.train.pretrain_ckpt:
        # converted reference weights (main_distributed.py:81-83)
        from milnce_tpu.utils.torch_convert import load_torch_checkpoint_as_flax

        variables = load_torch_checkpoint_as_flax(cfg.train.pretrain_ckpt)
        logger.log(f"loaded pretrained weights from {cfg.train.pretrain_ckpt}")

    # Schedule over the PLAN's total (satellite: per-stage batch sizes
    # make steps_per_epoch * epochs wrong for warmup/cosine totals) — a
    # pure function of the global step, so opt-state structure and
    # checkpoints are identical to a flat run's.
    schedule = build_schedule_total(cfg.optim, plan.total_steps)
    optimizer = build_optimizer(cfg.optim, schedule)
    state = jax.jit(lambda v: create_train_state(v, optimizer))(variables)

    # State placement: the ONE path every arrival sharding goes through
    # (fresh init, Orbax restore, rollback restore) — on the 2-D mesh it
    # also RESHARDS, so a 1-D-mesh checkpoint opens on a (data, model)
    # grid and vice versa (MIGRATING.md).
    if model_axis:
        from milnce_tpu.parallel.sharding_map import (place_tree,
                                                      shard_and_place_state)

        placement = shard_and_place_state(
            state, mesh, model_axis, min_size=cfg.parallel.fsdp_min_size,
            spec=cfg.parallel.sharding_map)
        state_specs = placement.specs
        logger.log(f"sharding map: {placement.n_sharded}/"
                   f"{len(placement.summary)} params "
                   f"sharded on '{model_axis}' "
                   f"(threshold {cfg.parallel.fsdp_min_size} elements, "
                   f"hash {placement.hash})")
        if placement.n_sharded == 0:
            logger.log("sharding map WARNING: no parameter shards — the "
                       "2-D mesh is paying model-axis collectives for "
                       "pure replication (lower parallel.fsdp_min_size "
                       "or fix parallel.sharding_map)")
        # the fresh state is already placed; a restore below then uses
        # the PLACED state as its template, so Orbax reads any
        # checkpoint (1-D or 2-D origin) straight into the FSDP layout
        # and the explicit place_state after it is an identity
        state = placement.state
        place_state = lambda s: place_tree(s, state_specs, mesh)  # noqa: E731
    else:
        state_specs = None
        place_state = lambda s: replicate_to_mesh(s, mesh)  # noqa: E731

    ckpt_dir = os.path.join(cfg.train.checkpoint_root,
                            cfg.train.checkpoint_dir or "run")
    manager = CheckpointManager(ckpt_dir, keep=cfg.train.checkpoint_keep,
                                save_retries=cfg.train.checkpoint_save_retries)
    start_epoch = 0
    resume_step = 0
    if cfg.train.resume:
        # Resume-compatibility guard BEFORE any Orbax I/O: a curriculum
        # checkpoint resumed with train.curriculum removed would
        # otherwise silently continue at the flat config's full shape
        # (the state tree is shape-invariant, so nothing else fails).
        curriculum.check_resume_compatible(
            curriculum.read_stage_stamp(ckpt_dir),
            curriculum_spec=cfg.train.curriculum,
            flat_frames=cfg.data.num_frames,
            flat_resolution=cfg.data.video_size,
            flat_batch=cfg.train.batch_size)
        # Topology guard (elastic/stamp.py), also before any Orbax I/O:
        # indivisible per-stage batches and a stale sidecar pair refuse
        # loudly; a mesh-shape change is logged and the restore runs
        # under the elastic.resume span so the reshard cost lands in the
        # ledger's reshard bucket instead of hiding in checkpoint.
        estamp = elastic.read_elastic_stamp(ckpt_dir)
        topo_note = elastic.check_topology_resume(
            estamp, mesh_shape=dict(mesh.shape),
            batch_sizes=[st.batch_size for st in plan.stages],
            curriculum_stamp=curriculum.read_stage_stamp(ckpt_dir))
        if topo_note:
            logger.log(topo_note)
        if estamp is not None:
            with rec.span("elastic.resume", label="latest",
                          from_mesh=str(dict(estamp.get("mesh") or {})),
                          to_mesh=str(dict(mesh.shape))):
                start_epoch, state = manager.restore_latest(state)
        else:
            with rec.span("ckpt.restore", label="latest"):
                start_epoch, state = manager.restore_latest(state)
        # Mid-epoch checkpoints (preemption / max_steps) are labeled
        # with the CURRENT epoch; the restored step counter places us
        # inside it via the plan's locate() — the containing stage
        # segment plus its batch offset — so the loader skips the
        # consumed batches at the index level and no sample is trained
        # twice (an end-of-epoch save lands on a boundary -> offset 0).
        resume_step = int(state.step)
        resume_seg, resume_off = plan.locate(resume_step)
        logger.log(
            f"resumed from epoch {start_epoch}"
            + (f" at batch {resume_seg.skip_batches + resume_off}"
               if resume_step else "")
            + (f" (curriculum stage {resume_seg.stage}, "
               f"{plan.stages[resume_seg.stage].label()})"
               if curriculum_on else ""))

    # Explicitly place the state (freshly initialized OR restored — both
    # land committed to one device) over the mesh NOW: leaving it
    # single-device made the first step_fn call perform the re-placement
    # as an IMPLICIT device-to-device transfer — invisible until the
    # steady-state transfer guard flagged it.  Multihost-safe: assembles
    # from process-local data instead of a cross-host device_put, so it
    # composes with the batch-sharded step inputs.
    state = place_state(state)
    if model_axis:
        # the FSDP storage win, made visible: per-chip bytes of the
        # placed state (host-side shard inspection, no transfer)
        from milnce_tpu.train.state import per_device_state_bytes

        per_dev = per_device_state_bytes(state)
        if per_dev:
            logger.log(f"state bytes/chip: "
                       f"{max(per_dev.values()) / 2 ** 20:.2f} MiB "
                       f"(params + moments + stats, post-sharding)")

    guard_on = cfg.train.finite_guard
    if cfg.train.grad_accum > 1:
        from milnce_tpu.train.step import make_grad_cache_step

        step_fn = make_grad_cache_step(
            model, optimizer, mesh, cfg.train.grad_accum, data_axis=axis,
            loss_cfg=cfg.loss, finite_guard=guard_on,
            state_specs=state_specs, model_axis=model_axis,
            overlap_grad_reduce=cfg.parallel.overlap_grad_reduce)
    else:
        step_fn = make_train_step(
            model, optimizer, mesh, data_axis=axis, loss_cfg=cfg.loss,
            finite_guard=guard_on, state_specs=state_specs,
            model_axis=model_axis,
            overlap_grad_reduce=cfg.parallel.overlap_grad_reduce)

    # Curriculum mem_plan pre-flight (train/curriculum.py, reusing the
    # PR 8 autotune planner): every stage's step is statically planned
    # against the per-chip HBM budget HERE — an over-budget stage is
    # refused with its top-3 contributors named before anything traces
    # or compiles, never an OOM at a mid-run boundary.
    if curriculum_on:
        budget = curriculum.hbm_budget_bytes()
        if budget:
            for note in curriculum.preflight_stages(
                    step_fn, state, plan.stages,
                    num_candidates=cfg.data.num_candidates,
                    max_words=cfg.data.max_words, budget_bytes=budget):
                logger.log(f"curriculum pre-flight: {note}")
        else:
            logger.log("curriculum pre-flight skipped: no per-chip HBM "
                       "budget known (set MILNCE_HBM_GIB to arm the "
                       "refusal gate)")

    # Preemption-safe shutdown: TPU-VM maintenance events deliver SIGTERM;
    # save a checkpoint and exit cleanly instead of losing the epoch (the
    # reference has no preemption handling — SURVEY.md §5 failure-detection
    # note; recovery there is manual restart from the last epoch file).
    # The controller (elastic/drain.py) latches SIGTERM, the
    # train.drain_signal_file path, and the host.preempt fault site into
    # one per-step poll; a drained exit forces a checkpoint + writes
    # ELASTIC_STAMP.json and returns TrainResult(drained=True).
    drain = elastic.DrainController(
        signal_file=cfg.train.drain_signal_file, recorder=rec)
    drain.install()

    # Straggler policy (elastic/straggler.py): the display cadence feeds
    # this host's window step-time p50 into the live twin of obs_report
    # --merge's skew rule; demotions ride the goodput snapshot.
    straggler_policy = elastic.StragglerPolicy(
        ratio=cfg.train.straggler_ratio,
        window=cfg.train.straggler_window,
        recommend_resize=cfg.train.straggler_resize, recorder=rec)

    # Multi-process: a maintenance event may signal only SOME workers; a
    # worker acting on its local flag alone would leave the rest wedged
    # in their next collective.  All-reduce the flag every
    # preempt_sync_steps so the whole cluster agrees to checkpoint at
    # the same step boundary (tests/test_multihost.py drives this with a
    # real one-worker SIGTERM).
    multi = jax.process_count() > 1
    if multi:
        from milnce_tpu.parallel.mesh import make_flag_reducer

        any_preempted = make_flag_reducer(mesh)
        sync_every = max(1, cfg.train.preempt_sync_steps)

    # In-training eval cadence: every total_batch//512 epochs, like the
    # reference's gate (main_distributed.py:188-189) — which is dead code
    # there (undefined test_loader, SURVEY.md §2.4); here it works.
    eval_every = max(1, cfg.train.batch_size // 512)

    # The loss stays ON DEVICE in the hot loop: a per-step ``float(loss)``
    # would block the host on every step's completion and defeat the async
    # dispatch that device_prefetch exists to enable (the reference has the
    # same flaw implicitly — loss.item() per batch, main_distributed.py:212).
    # Host transfer happens only every ``n_display`` steps and at exit, and
    # the steady state runs under ``jax.transfer_guard("disallow")`` so a
    # smuggled implicit sync RAISES instead of silently stalling the
    # pipeline (tests/test_transfer_guard.py); the display/checkpoint
    # branches re-enter "allow" — the audited escape hatch.
    total_steps = 0
    last_loss_dev = None
    running_dev = None
    valid_dev = None            # finite guard: non-skipped steps in window
    consec_dev = None           # finite guard: consecutive skipped updates
    skips_total_dev = None      # finite guard: run-total skipped updates
    rollbacks = 0
    last_rollback = None        # (total_steps, total_skips) at the last
                                # breaker trip — bounds the rollback loop
    window = 0
    # Wall clock feeds the human-facing elapsed display only; bench numbers
    # come from utils/timing.py's differenced protocol.
    # graftlint: disable=GL005(elapsed-display only; the windowed loss fetch at the same cadence is the device sync)
    tick = time.time()

    # Step counter tracked ON HOST: state.step is a device scalar, and
    # reading it back (int(state.step)) at display/stop cadence was a
    # hidden sync — graftlint GL001.  The restored value is read ONCE
    # here; afterwards host arithmetic stays exact.
    opt_step0 = int(state.step)

    # Live-goodput window baselines (host counters, reset per display):
    # data-wait delta off the prefetcher's accumulator, skip delta off
    # the guard fetch — everything the gauge needs already exists.
    window_wait0 = m_data_wait.value
    prev_k_total = 0
    last_mfu = None

    # LR display comes from the numpy twin of the device schedule:
    # float(schedule(step)) of the jnp form was a per-display device
    # round-trip (the original graftlint finding this PR fixes).
    host_schedule = build_host_schedule_total(cfg.optim, plan.total_steps)

    # Initial stage pipeline (a resume may land past stage 0 — the plan
    # says where).  The hoisted zero_start fallback: building np.zeros
    # INSIDE the loop fed the jitted step an implicit H2D transfer every
    # step; placed once per STAGE, explicitly, mesh-sharded via the same
    # placement helper the prefetcher uses.
    stage_idx = plan.stage_at(resume_step)
    source, loader, zero_start, step_flops = _stage_pipeline(stage_idx)
    timer = StepTimer(clips_per_step=plan.stages[stage_idx].batch_size)
    g_stage.set(stage_idx)

    def fetch(dev_val) -> float:
        # the ONE audited transfer of the display path (off-cadence by
        # design; see the n_display branch)
        return (float(jax.device_get(dev_val))
                if dev_val is not None else float("nan"))

    def exit_metrics():
        # one transfer covers both the final loss and the skip counter
        if skips_total_dev is None:
            return fetch(last_loss_dev), 0
        last, k = jax.device_get((last_loss_dev, skips_total_dev))
        return float(last), int(k)

    def check_finite(mean_loss: float, step_label: int) -> None:
        """Divergence guard, evaluated only at display fetches (no extra
        host syncs): a non-finite windowed loss snapshots the run state
        for post-mortem and halts instead of burning the rest of the
        epoch budget on NaNs.

        The snapshot goes to a SEPARATE ``nan_postmortem/`` directory,
        step-labeled: the rotation manager would both silently refuse the
        save (Orbax rejects a label <= the last saved one) and — worse —
        hand the NaN-poisoned params straight back to the next
        ``--resume``, which restores from the rotation only."""
        if np.isfinite(mean_loss) or not cfg.train.halt_on_nan:
            return
        pm = CheckpointManager(os.path.join(ckpt_dir, "nan_postmortem"),
                               keep=1)
        pm.save(step_label, state)
        pm.wait()
        logger.log(f"non-finite training loss ({mean_loss}) — post-mortem "
                   f"state saved under nan_postmortem/{step_label}; halting")
        raise FloatingPointError(
            f"training loss became non-finite ({mean_loss}) at step "
            f"{step_label}")

    prev_rec = obs_spans.install(rec)   # pipeline watchdog events land
                                        # in this run's stream
    try:
      with maybe_trace(cfg.train.trace_dir or None):
        # Steady state: IMPLICIT device transfers are a bug (a hidden
        # host sync or a per-step H2D upload) and raise immediately.
        # Explicit device_put/device_get stay legal; the display /
        # preemption-sync / checkpoint branches re-enter "allow" — every
        # escape hatch is a deliberate, cadenced one.
        with jax.transfer_guard("disallow"):
          for epoch in range(start_epoch, cfg.optim.epochs):
            if (cfg.train.evaluate and cfg.data.eval_video_root
                    and epoch % eval_every == 0):
                with jax.transfer_guard("allow"):   # epoch-cadence eval
                    _in_training_eval(cfg, model, state, mesh, logger)
            for seg in plan.segments_for_epoch(epoch):
              # Resume offsets come from the plan's locate() semantics:
              # segments fully consumed by the restored step are skipped
              # whole; the containing one starts at its batch offset.
              seg_done = 0
              if resume_step:
                  if resume_step >= seg.end_step:
                      continue
                  seg_done = max(0, resume_step - seg.start_step)
                  resume_step = 0       # applies once
              if seg.stage != stage_idx:
                  # Curriculum boundary: the previous stage's prefetcher
                  # is already drained (closed below); rebuild the
                  # pipeline at the new shapes.  The stage.switch span
                  # feeds the goodput ledger's stage_switch bucket; the
                  # NEXT step dispatch blocks on the new stage's
                  # trace+compile (one fresh jit entry per stage) and
                  # the ledger attributes that step there too.
                  st = plan.stages[seg.stage]
                  with jax.transfer_guard("allow"):   # boundary cadence
                    with rec.span("stage.switch", stage=seg.stage,
                                  prev_stage=stage_idx,
                                  step=opt_step0 + total_steps,
                                  num_frames=st.num_frames,
                                  resolution=st.resolution,
                                  batch_size=st.batch_size):
                        (source, loader, zero_start,
                         step_flops) = _stage_pipeline(seg.stage)
                  stage_idx = seg.stage
                  g_stage.set(stage_idx)
                  logger.log(f"curriculum: entering stage {stage_idx} "
                             f"({st.label()}) at step "
                             f"{opt_step0 + total_steps}")
                  # fresh stage, fresh display window — the windowed
                  # loss/throughput must not mix shapes across the
                  # boundary (the loss-continuity acceptance compares
                  # post-switch windows against a flat run at the new
                  # shape)
                  running_dev = None
                  valid_dev = None
                  window = 0
                  timer = StepTimer(clips_per_step=st.batch_size)
                  window_wait0 = m_data_wait.value
                  tick = time.time()
              prefetch = device_prefetch(
                  loader.epoch(epoch,
                               skip_batches=seg.skip_batches + seg_done),
                  mesh, batch_axes, depth=cfg.data.prefetch_depth)
              for batch in prefetch:
                video, text = flatten_text(batch)
                start = batch.get("start", zero_start)
                # span times HOST dispatch of the async step (device
                # truth needs the profiler bridge / trace_dir) — no
                # sync, no transfer, file write is line-buffered host IO
                with rec.span("step", step=total_steps + 1):
                    # host.slow chaos site: inflate THIS process's step
                    # wall time (a persistently slow host for the
                    # straggler policy); the sleep lands inside the step
                    # span so the recorded skew is the injected one
                    faults.maybe_hang("host.slow", default_sleep=0.05)
                    if guard_on:
                        state, loss, skipped = step_fn(state, video, text,
                                                       start)
                        skipped = skipped.addressable_data(0)
                    else:
                        state, loss = step_fn(state, video, text, start)
                # Accumulate on the PROCESS-LOCAL replica of the (P()-
                # replicated) loss: a zero-copy shard view.  Eager/jit
                # arithmetic on the multi-process global array itself is
                # a cross-process XLA computation — unsupported on the
                # CPU backend and pure waste on TPU (every process holds
                # the full value; SPMD determinism keeps the per-process
                # accumulators identical, so display/breaker verdicts
                # stay cluster-uniform).
                loss = loss.addressable_data(0)
                total_steps += 1
                seg_done += 1
                window += 1
                timer.tick()
                # async device-side accumulation — no host sync here (the
                # guard trackers are jitted jnp updates on device scalars)
                if guard_on:
                    if consec_dev is None:
                        consec_dev = skipped - skipped      # local-shard 0
                    if skips_total_dev is None:
                        skips_total_dev = skipped - skipped
                    if running_dev is None:
                        (running_dev, valid_dev, consec_dev,
                         skips_total_dev) = _guard_restart_j(
                            loss, skipped, consec_dev, skips_total_dev)
                    else:
                        (running_dev, valid_dev, consec_dev,
                         skips_total_dev) = _guard_acc_j(
                            running_dev, valid_dev, consec_dev,
                            skips_total_dev, loss, skipped)
                else:
                    running_dev = (loss if running_dev is None
                                   else running_dev + loss)
                last_loss_dev = loss
                if window % cfg.train.n_display == 0:
                  # LR + progress from the host step counter (seeded by
                  # the RESTORED device counter once, before the loop),
                  # so they stay correct across resumes with no sync.
                  opt_step = opt_step0 + total_steps
                  lr = host_schedule(opt_step)
                  # epoch progress from the plan (per-stage batch sizes
                  # make a run-constant steps_per_epoch meaningless);
                  # the modulo keeps the flat-run display byte-identical
                  ep_len = max(1, plan.epoch_steps(epoch))
                  progress = ((opt_step - plan.epoch_start_step(epoch))
                              % ep_len) / ep_len
                  with jax.transfer_guard("allow"):  # display-cadence fetch
                    consec = 0
                    k_total = 0
                    extra = ""
                    # the sync span is where the async pipeline's device
                    # work surfaces on the host — the goodput ledger's
                    # compute category reads step-dispatch + sync spans
                    with rec.span("sync", cause="display", step=opt_step):
                        if guard_on:
                            (mean_loss, consec,
                             k_total) = _fetch_guard_window(
                                running_dev, valid_dev, consec_dev,
                                skips_total_dev)
                        else:
                            mean_loss = fetch(running_dev) / window
                    if curriculum_on:
                        extra += f", Stage: {stage_idx}"
                    if guard_on:
                        extra += f", Skipped steps: {k_total}"
                    fails = getattr(source, "decode_failures", 0)
                    extra += f", Decode failures: {fails}"
                    if loader.decode_timeouts:
                        extra += (f", Decode timeouts: "
                                  f"{loader.decode_timeouts}")
                    # ONE timer read feeds throughput, MFU and the
                    # detector, so the three can never disagree on the
                    # window they describe
                    sps = timer.steps_per_sec
                    elapsed = timer.elapsed_s
                    clips_per_sec = sps * plan.stages[stage_idx].batch_size
                    if step_flops is not None and sps > 0:
                        last_mfu = roofline_mfu(step_flops, sps, peak,
                                                n_chips)
                        g_mfu.set(last_mfu)
                        extra += f", MFU: {last_mfu:.3f}"
                    # windowed goodput: elapsed minus host data-wait,
                    # scaled by the applied-update fraction (a skipped
                    # step burnt chip time for no kept progress)
                    wait_now = m_data_wait.value
                    wait_delta = max(0.0, wait_now - window_wait0)
                    window_wait0 = wait_now
                    applied_frac = 1.0
                    if guard_on and window > 0:
                        skip_delta = max(0, k_total - prev_k_total)
                        prev_k_total = k_total
                        applied_frac = max(0.0, 1.0 - skip_delta / window)
                    goodput_frac = 0.0
                    if elapsed > 0:
                        goodput_frac = (max(0.0, elapsed - wait_delta)
                                        / elapsed) * applied_frac
                    g_goodput.set(goodput_frac)
                    logger.log(
                        f"Epoch {epoch + 1}, Elapsed Time: "
                        f"{time.time() - tick:.3f}, Epoch status: "
                        f"{progress:.4f}, Training loss: "
                        f"{mean_loss:.4f}, "
                        f"Learning rate: {lr:.6f}, Throughput: "
                        f"{clips_per_sec:.1f} clips/s{extra}")
                    # registry feed: ONLY host values the fetch above
                    # already materialized (the tentpole invariant —
                    # no extra device_get, no per-step recording)
                    m_steps.inc(window)
                    g_loss.set(mean_loss)
                    g_lr.set(lr)
                    g_tput.set(clips_per_sec)
                    if guard_on:
                        g_skipped.set(k_total)
                    rec.event("display", step=opt_step, epoch=epoch + 1,
                              loss=float(mean_loss), lr=float(lr),
                              clips_per_sec=clips_per_sec,
                              goodput_fraction=round(goodput_frac, 5),
                              stage=stage_idx,
                              skipped_total=k_total,
                              **({"mfu": round(last_mfu, 5)}
                                 if last_mfu is not None else {}))
                    # anomaly path (host-side): feed the window's mean
                    # step wall time; a spike arms the bounded capture.
                    # The window containing the run's FIRST step is
                    # excluded — its compile time would set the EWMA
                    # baseline several times too high and mask every
                    # real spike for the rest of the run (the ledger
                    # excludes it from compute for the same reason).
                    if (spike_detector is not None and window > 0
                            and (opt_step - window) != opt_step0):
                        spike_detector.observe(elapsed * 1e3 / window,
                                               step=opt_step)
                    # straggler feed: THIS host's window mean step wall
                    # time, same first-window exclusion as the spike
                    # detector (compile time is not skew).  Single-host
                    # runs accumulate but never flag — skew needs a
                    # second host to compare against.
                    if window > 0 and (opt_step - window) != opt_step0:
                        straggler_policy.observe(
                            process_index, elapsed * 1e3 / window,
                            step=opt_step)
                    if (profiler_capture is not None
                            and capture_requested["flag"]):
                        capture_requested["flag"] = False
                        verdict = profiler_capture.arm(reason="sigusr1")
                        logger.log(f"SIGUSR1 profiler capture: {verdict}")
                    # a guarded window with ZERO applied updates displays
                    # nan by construction — that is the breaker's case to
                    # handle, not the halt-on-nan divergence guard's
                    if not (guard_on and np.isnan(mean_loss)):
                        check_finite(mean_loss, opt_step)
                    if (guard_on and cfg.train.skip_rollback_after
                            and consec >= cfg.train.skip_rollback_after):
                        # Circuit breaker: K consecutive non-finite
                        # updates means the guard alone isn't enough (a
                        # poisoned data window, diverged state).  Roll the
                        # WEIGHTS back to the last rotation checkpoint but
                        # keep the CURRENT step counter — it tracks
                        # batches consumed, so the run resumes PAST the
                        # poisoned window instead of replaying it (or
                        # halting, as the pre-breaker NaN guard did).
                        latest = manager.latest_epoch()
                        if latest is None:
                            raise FloatingPointError(
                                f"{consec} consecutive non-finite updates "
                                "and no rotation checkpoint to roll back "
                                "to — halting")
                        # Termination bound: a rollback is only worth
                        # repeating if SOME update applied since the last
                        # one.  Zero applied updates between trips means
                        # the failure is persistent (LR bug, corrupted
                        # hardware, every-step injection), and looping
                        # rollback-skip-rollback would burn the pod
                        # forever — halt like the pre-breaker NaN guard.
                        if last_rollback is not None:
                            applied = ((total_steps - last_rollback[0])
                                       - (k_total - last_rollback[1]))
                            if applied <= 0:
                                raise FloatingPointError(
                                    f"circuit breaker: {consec} consecutive "
                                    "non-finite updates with ZERO applied "
                                    "updates since the previous rollback — "
                                    "the failure is persistent, halting "
                                    "instead of rolling back in a loop")
                        last_rollback = (total_steps, k_total)
                        manager.wait()
                        with rec.span("ckpt.restore", label=int(latest)):
                            restored = manager.restore(latest, state)
                        state = restored.replace(
                            step=jnp.asarray(opt_step, jnp.int32))
                        state = place_state(state)
                        rollbacks += 1
                        m_rollbacks.inc()
                        # rollback-lost attribution (goodput ledger):
                        # applied updates since the restored boundary
                        # save are now discarded — the skipped streak
                        # is already badput, so it doesn't count twice
                        # checkpoint labeled L holds state at epoch L's
                        # start — the plan maps that to a global step
                        # even when stages change the per-epoch count
                        lost = max(0, (opt_step
                                       - plan.epoch_start_step(int(latest))
                                       - consec))
                        rec.event("rollback", step=opt_step,
                                  restored_epoch=int(latest),
                                  consecutive_skips=consec,
                                  lost_updates=lost)
                        consec_dev = None       # fresh weights: reset streak
                        logger.log(
                            f"circuit breaker: {consec} consecutive "
                            f"non-finite updates — restored rotation "
                            f"checkpoint {latest}, resuming at step "
                            f"{opt_step} past the poisoned data window")
                  running_dev = None
                  valid_dev = None
                  window = 0
                  timer.reset()
                  tick = time.time()
                # one drain poll per optimizer step (host-side: a dict
                # read + disarmed-fault check — the host.preempt
                # occurrence count is therefore the step number)
                local_drain = drain.poll(total_steps)
                if multi:
                    # every process evaluates the collective at the SAME
                    # steps (total_steps advances in lockstep), so they
                    # all see the same verdict.  The guard escape opens
                    # only on the cadence hit — the 1-in-sync_every step
                    # where the reducer materializes its verdict on host.
                    stopping = False
                    if total_steps % sync_every == 0:
                        with jax.transfer_guard("allow"):
                            stopping = any_preempted(local_drain)
                else:
                    stopping = local_drain
                if stopping or (max_steps is not None
                                and total_steps >= max_steps):
                  with jax.transfer_guard("allow"):  # checkpoint + exit
                    drained = bool(stopping)
                    if drained:
                        logger.log(
                            f"drain ({drain.source or 'cluster peer'}) — "
                            "checkpointing and exiting"
                            + (" (cluster-coordinated)" if multi else ""))
                    # label/force semantics: stop_save_label (module
                    # top); the planned twin handles per-stage epoch
                    # lengths.  Edge cases pinned in
                    # tests/test_resilience.py + test_train.py
                    label, force = stop_save_label_planned(
                        epoch, opt_step0 + total_steps, plan)
                    # a drain's forced save is badput the preemption
                    # caused: it lands in the ledger's drain bucket
                    # (span INSTEAD of ckpt.save — overlapping both
                    # would double-count against the sum-to-wall pin)
                    with rec.span(
                            "elastic.drain" if drained else "ckpt.save",
                            label=label, forced=force, stage=stage_idx,
                            **({"source": drain.source} if drained
                               else {})):
                        manager.save(label, state, force=force)
                        manager.wait()
                    if process_index == 0:
                        opt_step = opt_step0 + total_steps
                        curriculum.write_stage_stamp(
                            ckpt_dir, spec=cfg.train.curriculum,
                            stage_index=stage_idx,
                            stage=plan.stages[stage_idx],
                            step=opt_step)
                        seg_c, off_c = plan.locate(opt_step)
                        elastic.write_elastic_stamp(
                            ckpt_dir, mesh_shape=dict(mesh.shape),
                            sharding_hash=(placement.hash if model_axis
                                           else ""),
                            step=opt_step, stage_index=stage_idx,
                            batch_offset=seg_c.skip_batches + off_c,
                            drained=drained)
                    last, skips = exit_metrics()
                    return TrainResult(state, total_steps, last,
                                       skips, rollbacks, stage_idx,
                                       drained)
                if seg_done >= seg.n_steps:
                    break       # segment complete (stage boundary or
                                # epoch tail) — drain + re-arm below
              # Deterministic drain at the segment edge: close the
              # prefetch generator so its in-flight decode futures and
              # device puts retire via the loader's finally blocks NOW,
              # not at GC — the old stage's readers must not race the
              # new stage's (and the stage.switch span must not start
              # while they run).
              prefetch.close()
            with jax.transfer_guard("allow"):       # epoch-boundary save
                # the span times the async SUBMIT (Orbax writes in the
                # background); the stop-save span above times a full
                # submit+wait
                with rec.span("ckpt.save", label=epoch + 1, forced=False,
                              stage=stage_idx):
                    manager.save(epoch + 1, state)
                if process_index == 0:
                    opt_step = opt_step0 + total_steps
                    curriculum.write_stage_stamp(
                        ckpt_dir, spec=cfg.train.curriculum,
                        stage_index=stage_idx,
                        stage=plan.stages[stage_idx],
                        step=opt_step)
                    # the topology sidecar rides EVERY save (the pair
                    # must stay in lockstep — check_topology_resume
                    # cross-checks their plan cursors on resume)
                    seg_c, off_c = plan.locate(opt_step)
                    elastic.write_elastic_stamp(
                        ckpt_dir, mesh_shape=dict(mesh.shape),
                        sharding_hash=(placement.hash if model_axis
                                       else ""),
                        step=opt_step, stage_index=stage_idx,
                        batch_offset=seg_c.skip_batches + off_c,
                        drained=False)
    finally:
        manager.wait()
        if cfg.train.faults:
            faults.disarm()     # a config-armed registry dies with the run
        drain.uninstall()
        if prev_usr1 is not None:
            signal.signal(signal.SIGUSR1, prev_usr1)
        if profiler_capture is not None:
            profiler_capture.close()    # flush a mid-capture trace
        rec.event("run.end", steps=total_steps)
        # per-run attribution (obs/goodput.py): partition this run's
        # wall time, export gauges + the GOODPUT snapshot — best-effort,
        # AFTER run.end so the ledger's wall covers the whole run
        ledger_extra = dict(straggler_policy.ledger_extra())
        if last_mfu is not None:
            ledger_extra["mfu"] = round(last_mfu, 5)
        _finalize_goodput_ledger(
            rec, rec_path, run_id, process_index, reg, obs_dir,
            logger.log, extra=ledger_extra or None)
        obs_spans.install(prev_rec)     # this run's stream detaches
        rec.close()
        obs_runctx.set_run_context(*prev_runctx)
        logger.close()
    last, skips = exit_metrics()
    return TrainResult(state, total_steps, last, skips, rollbacks,
                       stage_idx)
