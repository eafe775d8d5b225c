"""Drives ``milnce_tpu.train.loop.run_training`` — the entry a user runs —
for one measured window, and compares its first steps with the plain
reference.

The run, in order (everything before the window is set-up):

1. weights from the seed (``benchmarks/weights.py``), handed to the
   program the way a user hands it weights: a checkpoint to resume from;
2. ``run_training`` in the main thread with the synthetic source and the
   loader threads live, ``--train.n_display`` steps between fetches of
   the loss (the loop's only sync), an epoch longer than any window, a
   drain signal file;
3. a watcher thread follows ``RUN_EVENTS.jsonl``: the window opens at the
   ``warmup_displays``-th ``display`` record (compile and warm-up behind
   it) and closes at the last ``display`` record at or before
   ``--seconds`` later; then the watcher touches the signal file and the
   loop drains by its own preemption path.  In a traced run the observer
   of point 4 starts and stops the profiler, in the loop's own thread,
   around ``trace_steps`` steps inside the window (a
   ``stop_trace`` from the watcher's thread never came back on the chip
   while the loop kept dispatching, PR 25);
4. an observer around the step the loop builds (as ``chip_smoke.py``'s
   ``_StepRecorder``: it changes nothing) keeps, for the first
   ``follow_steps`` steps, the batch as the loader fed it, the loss, the
   first gradient (Adam's first moment after one step, over 1 - b1) and
   the parameters handed to step ``follow_steps + 1``;
5. once the window has closed and the state is freed, the reference
   follows those steps from the same weights and batches.
"""

from __future__ import annotations

import gc
import json
import os
import shutil
import threading
import time

import numpy as np

from benchmarks import compare, flops, harness, trace_reduce, weights
from benchmarks.reference import s3dg_milnce as reference

SPAN_NAMES = ("step", "sync", "data.wait", "display")


def build_argv(cell, seed: int, work: str, platform: str, trace: bool) -> list:
    """The trainer's command line from the configuration's groups and the
    traffic file's flags (``chip_smoke._train_argv``'s pattern)."""
    cfg, traffic = cell.config, cell.traffic
    batch = cfg["train"]["batch_per_chip"] * cell.chips
    argv = ["--preset", cfg.get("preset", "full")]
    argv += harness.group_flags(cfg, ("model", "data", "optim", "loss"))
    for key, value in traffic.get("flags", {}).items():
        argv += [f"--{key}", harness.flag(value)]
    argv += ["--data.synthetic_num_samples",
             str(batch * traffic["epoch_steps"]),
             "--train.batch_size", str(batch),
             "--train.n_display", str(traffic["n_display"]),
             "--train.seed", str(seed % (2 ** 31 - 1)),
             "--parallel.platform", platform,
             "--parallel.num_devices", str(cell.chips),
             "--train.checkpoint_root", os.path.join(work, "ckpt"),
             "--train.checkpoint_dir", "run",
             "--train.log_root", os.path.join(work, "log"),
             "--train.drain_signal_file", os.path.join(work, "DRAIN"),
             "--train.resume", "true",
             "--train.obs_profiler_bridge", harness.flag(trace)]
    return argv


def write_start_checkpoint(cfg, flat_weights: dict) -> None:
    """The benchmark's weights as a step-0 checkpoint in the program's
    own format, for ``--train.resume true`` to pick up."""
    import jax

    from milnce_tpu.train.checkpoint import CheckpointManager
    from milnce_tpu.train.schedule import build_schedule_total
    from milnce_tpu.train.state import build_optimizer, create_train_state

    variables = {"params": weights.nest(flat_weights),
                 "batch_stats": weights.nest(
                     weights.batch_stats_for(flat_weights))}
    optimizer = build_optimizer(cfg.optim,
                                build_schedule_total(cfg.optim, 1))
    state = jax.jit(lambda v: create_train_state(v, optimizer))(variables)
    manager = CheckpointManager(os.path.join(cfg.train.checkpoint_root,
                                             cfg.train.checkpoint_dir))
    manager.save(0, state)
    manager.wait()
    manager.close()


class StepObserver:
    """While installed, ``train.loop.make_train_step`` returns the real
    jitted step behind a wrapper that keeps host copies of what the first
    ``follow`` calls ate and produced.  It changes nothing; after the
    followed steps it is one Python call deep."""

    def __init__(self, follow: int, fault=None, trace_dir=None,
                 trace_steps=(0, 0)):
        self.follow, self.fault = follow, fault
        self.trace_dir, self.trace_steps = trace_dir, trace_steps
        self.trace_span = None
        self.seen = 0           # calls of the step, followed or not
        self.calls = 0
        self.batches, self.losses = [], []
        self.first_moment, self.params_after = None, None
        self.skipped = 0

    def __enter__(self):
        from milnce_tpu.train import loop

        self._loop, self._real = loop, loop.make_train_step

        def make(*args, **kwargs):
            jitted = self._real(*args, **kwargs)
            if self.fault is not None:      # tests only: the timed path,
                jitted = self.fault(jitted)  # broken underneath

            def step(state, video, text, start):
                self.seen += 1
                if self.trace_dir is not None:
                    self._trace_control()
                if self.calls >= self.follow:
                    return jitted(state, video, text, start)
                return self._observed(jitted, state, video, text, start)

            return step

        loop.make_train_step = make
        return self

    def __exit__(self, *exc):
        self._loop.make_train_step = self._real
        if self.trace_span is not None:         # the run ended mid-trace
            self._stop_trace()

    def _trace_control(self):
        """In a traced run: the profiler runs from the dispatch of step
        ``first`` to the dispatch of step ``last``, started and stopped
        here, in the loop's own thread, between two dispatches."""
        import jax

        first, last = self.trace_steps
        if self.seen == first:
            trace_reduce.start_trace(self.trace_dir)
            self.trace_span = jax.profiler.TraceAnnotation(
                trace_reduce.WINDOW_SPAN)
            self.trace_span.__enter__()
        elif self.seen == last and self.trace_span is not None:
            self._stop_trace()

    def _stop_trace(self):
        import jax

        self.trace_span.__exit__(None, None, None)
        self.trace_span = None
        jax.profiler.stop_trace()

    def _observed(self, jitted, state, video, text, start):
        import jax

        self.batches.append((np.asarray(jax.device_get(video)),
                             np.asarray(jax.device_get(text))))
        out = jitted(state, video, text, start)
        self.calls += 1
        self.losses.append(float(jax.device_get(out[1])))
        if len(out) > 2:
            self.skipped += int(jax.device_get(out[2]))
        if self.calls == 1:
            adam = [s for s in jax.tree_util.tree_leaves(
                out[0].opt_state, is_leaf=lambda x: hasattr(x, "mu"))
                if hasattr(s, "mu")]
            self.first_moment = _arrays(jax.device_get(adam[0].mu))
        if self.calls == self.follow:
            self.params_after = _arrays(jax.device_get(out[0].params))
        return out


def _arrays(tree) -> dict:
    """Nested dicts -> flat ``a/b/c`` names, array leaves only (optax's
    masked nodes for frozen leaves fall out)."""
    return {k: np.asarray(v) for k, v in weights.flatten(
        _as_dict(tree)).items() if hasattr(v, "shape")}


def _as_dict(tree):
    if hasattr(tree, "items"):
        return {k: _as_dict(v) for k, v in tree.items()}
    return tree


class Watcher(threading.Thread):
    """Follows RUN_EVENTS.jsonl: opens the window at the
    ``warmup_displays``-th display record and, once ``seconds`` have
    passed and (in a traced run) the trace is written, asks the loop to
    drain.  ``hold`` keeps the run going while it returns true."""

    def __init__(self, events_path: str, drain_file: str, seconds: float,
                 traffic: dict, hold=lambda: False):
        super().__init__(name="bench-watcher", daemon=True)
        self.events_path, self.drain_file = events_path, drain_file
        self.seconds, self.traffic, self.hold = seconds, traffic, hold
        self.records: list = []
        self.displays: list = []
        self.t_open = None
        self.error = None
        self.stop = threading.Event()

    def run(self):
        try:
            self._follow()
        except Exception as exc:                # surfaced by the driver
            self.error = exc
        finally:
            _touch(self.drain_file)

    def _follow(self):
        warm = self.traffic["warmup_displays"]
        while not os.path.exists(self.events_path):
            if self.stop.wait(0.05):
                return
        with open(self.events_path) as fh:
            while not self.stop.is_set():
                line = fh.readline()
                if not line.endswith("\n"):
                    if line:
                        fh.seek(fh.tell() - len(line))
                    if (self.t_open is not None and not self.hold()
                            and time.monotonic() >= self.t_open + self.seconds):
                        return
                    self.stop.wait(0.02)
                    continue
                rec = json.loads(line)
                self.records.append(rec)
                if rec.get("name") == "display":
                    self.displays.append(rec)
                    if len(self.displays) == warm:
                        self.t_open = rec["mono"]


def _touch(path: str) -> None:
    with open(path, "a"):
        pass


def follow_reference(cell, seed: int, batches, precision="float32",
                     keep_rows=None) -> dict:
    """The plain reference over the followed steps, from the seed's
    weights: losses, first gradient, change of the parameters."""
    import jax

    cfg = cell.config
    shapes = weights.weight_shapes(cfg["model"])
    w = weights.make_weights(seed, shapes)
    w0 = {n: np.asarray(v) for n, v in jax.device_get(w).items()}
    step = reference.make_train_step(
        bn_groups=cell.chips, precision=precision,
        blocks=cfg["model"]["inception_blocks"],
        base_lr=cfg["optim"]["lr"], warmup=cfg["optim"]["warmup_steps"],
        keep_rows=keep_rows)
    mu = jax.tree_util.tree_map(lambda x: x * 0, w)
    nu = jax.tree_util.tree_map(lambda x: x * 0, w)
    losses, first_grad = [], None
    with jax.default_matmul_precision("highest"):
        for k, (video, text) in enumerate(batches):
            w, mu, nu, loss, grads = step(w, mu, nu, np.int32(k), video, text)
            losses.append(float(loss))
            if k == 0:
                first_grad = {n: np.asarray(g) for n, g in grads.items()}
            del grads
    delta = {n: np.asarray(w[n]) - w0[n] for n in w0}
    return {"losses": losses, "grad": first_grad, "delta": delta}


def program_numbers(cell, seed: int, obs: StepObserver) -> dict:
    """What the observer kept, in the reference's terms."""
    import jax

    shapes = weights.weight_shapes(cell.config["model"])
    w0 = jax.device_get(weights.make_weights(seed, shapes))
    grad = {n: m / (1.0 - reference.ADAM_B1)
            for n, m in obs.first_moment.items()}
    delta = {n: obs.params_after[n] - np.asarray(w0[n]) for n in w0}
    return {"losses": obs.losses, "grad": grad, "delta": delta}


def run(cell, *, seed: int, seconds: float, trace: bool, work: str,
        platform: str = "", t_start: float | None = None,
        fault=None) -> dict:
    """One run of a training cell.  ``fault`` (tests only): a function of
    the step the loop builds that returns the step the loop then drives:
    the timed path, broken underneath."""
    import jax

    from milnce_tpu.config import parse_cli
    from milnce_tpu.train.loop import run_training
    from milnce_tpu.utils.compile_cache import configure_compile_cache

    t_start = time.monotonic() if t_start is None else t_start
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    configure_compile_cache()
    devices = jax.devices()[:cell.chips]
    traffic = cell.traffic
    argv = build_argv(cell, seed, work, platform, trace)
    cfg = parse_cli(argv)
    batch = cfg.train.batch_size

    shapes = weights.weight_shapes(cell.config["model"])
    w0 = weights.make_weights(seed, shapes)
    write_start_checkpoint(cfg, w0)
    del w0

    trace_dir = os.path.join(work, "trace") if trace else None
    # the traced steps: ``trace_steps`` of them, from just after the
    # display that follows the window's opening
    n_display = traffic["n_display"]
    first = (traffic["warmup_displays"] + 1) * n_display + 1
    last = first + traffic.get("trace_steps", 4)
    obs = StepObserver(traffic["follow_steps"], fault, trace_dir,
                       (first, last))
    watcher = Watcher(os.path.join(work, "log", "RUN_EVENTS.jsonl"),
                      os.path.join(work, "DRAIN"), seconds, traffic,
                      hold=lambda: trace and obs.seen <= last)
    watcher.start()
    try:
        with obs:
            result = run_training(cfg)
    finally:
        watcher.stop.set()
        watcher.join(timeout=120)
    if watcher.error is not None:
        raise watcher.error
    if watcher.t_open is None:
        raise RuntimeError("the run ended before the window opened")
    peak = harness.peak_bytes_in_use(devices)
    steps_run = int(result.steps)
    skipped = int(result.skipped_steps)
    del result
    gc.collect()
    jax.clear_caches()

    # ---- the window, from the display records --------------------------
    t0 = watcher.t_open
    inside = [d for d in watcher.displays if t0 <= d["mono"] <= t0 + seconds]
    if len(inside) < 2:
        raise RuntimeError(f"{len(inside)} display records inside the "
                           "window: it is too short for this cell")
    t1 = inside[-1]["mono"]
    window_s = t1 - t0
    steps = (len(inside) - 1) * traffic["n_display"]
    clips = steps * batch
    events = [r for r in watcher.records if t0 < r.get("mono", 0.0) <= t1]
    metrics = {
        "train_clips_per_s_per_chip": clips / window_s / cell.chips,
        "setup_s": t0 - t_start,
    }
    record = harness.RunRecord(
        cell=cell, peaks=None, events=events, window_s=window_s,
        extra={"batch": batch, "steps": steps,
               "n_display": traffic["n_display"],
               "work_per_item_flops": flops.train_step_flops(
                   batch, cfg.data.num_frames, cfg.data.video_size,
                   cfg.data.num_candidates, cfg.data.max_words,
                   cfg.model.inception_blocks, cfg.model.embedding_dim,
                   cfg.model.word_embedding_dim,
                   cfg.model.text_hidden_dim) / batch})
    if trace:
        red = trace_reduce.reduce_trace(
            trace_reduce.find_xplane(trace_dir),
            layout=(trace_reduce.TPU_LAYOUT if devices[0].platform == "tpu"
                    else trace_reduce.CPU_LAYOUT),
            span_names=SPAN_NAMES,
            chips=cell.chips if devices[0].platform == "tpu" else None)
        record.trace = red
        # the steps that finished inside the traced window: executions of
        # the program that took most of the device's time
        if red.module_seconds:
            top = max(red.module_seconds, key=lambda n: sum(
                red.module_seconds[n]))
            traced_steps = red.module_inside[top] / cell.chips
        else:
            traced_steps = last - first
        record.traced_work = traced_steps * batch
        record.extra["traced_steps"] = traced_steps

    # ---- correct: the followed steps against the reference -------------
    prog = program_numbers(cell, seed, obs)
    ref = follow_reference(cell, seed, obs.batches)
    numbers = compare.training_numbers(prog, ref,
                                       frozen=reference.FROZEN)
    compared = {k: {"value": numbers[k], "limit": cell.limits[k]}
                for k in ("loss_gap", "grad_norm_gap", "step_norm_gap")}
    compared["skipped_steps"] = {"value": float(skipped), "limit": 0.0}
    return {"metrics": metrics, "attempted": steps_run,
            "failed": skipped, "record": record, "compared": compared,
            "peak_bytes": peak, "numbers": numbers}
