"""Chaos tests: every recovery path driven under an injected fault
(ISSUE 3).  Each fault site in resilience/faults.py has a tier-1 test
proving the run SURVIVES, the response matches the ROBUSTNESS.md matrix,
and training reaches max_steps with the fault armed — plus unit coverage
of the registry, the watchdog, the finite guard, the circuit breaker,
the checkpoint retry, the failure-rate abort, and the orphan reaper.

Pinned tier-1 (never @slow) by tests/test_suite_hygiene.py: these ARE
the permanent regression harness for the failure paths, including PRs
1-2's hot-path guarantees holding *under* faults (run_training's
transfer guard stays armed throughout; the guarded step's collective
counts are pinned with injection enabled)."""

import json
import os
import threading
import time

import numpy as np
import pytest

from milnce_tpu.config import tiny_preset
from milnce_tpu.resilience import faults
from milnce_tpu.resilience.faults import FaultRegistry, InjectedFault


# --------------------------------------------------------------------------
# fault registry
# --------------------------------------------------------------------------

class TestFaultRegistry:
    def test_spec_grammar(self):
        reg = FaultRegistry("decode.raise@1,3;decode.hang@%2:x=0.5;"
                            "grad.nonfinite@*")
        assert reg.sites["decode.raise"].at == (1, 3)
        assert reg.sites["decode.hang"].every == 2
        assert reg.sites["decode.hang"].x == 0.5
        assert reg.sites["grad.nonfinite"].mode == "all"

    @pytest.mark.parametrize("bad", [
        "decode.raise",                  # missing @sched
        "no.such.site@1",                # unknown site
        "decode.raise@0",                # 0-based index
        "decode.raise@%0",               # every-0
        "decode.hang@1:y=3",             # unknown parameter
    ])
    def test_bad_specs_fail_loudly(self, bad):
        with pytest.raises(ValueError):
            FaultRegistry(bad)

    def test_hit_scheduling_is_deterministic(self):
        reg = FaultRegistry("decode.raise@2,4")
        fired = [reg.fire("decode.raise") is not None for _ in range(5)]
        assert fired == [False, True, False, True, False]
        # unarmed site never fires and costs nothing
        assert reg.fire("ckpt.save_ioerror") is None

    def test_disarmed_sites_are_noops(self):
        faults.disarm()
        faults.maybe_raise("decode.raise")      # must not raise
        faults.maybe_hang("decode.hang")        # must not sleep
        assert faults.device_schedule("grad.nonfinite") is None

    def test_armed_context_raises_and_disarms(self):
        with faults.armed("decode.raise@1"):
            with pytest.raises(InjectedFault):
                faults.maybe_raise("decode.raise")
            faults.maybe_raise("decode.raise")  # occurrence 2: clean
        faults.maybe_raise("decode.raise")      # disarmed again

    def test_env_arming(self, monkeypatch):
        monkeypatch.setattr(faults, "_registry", None)
        monkeypatch.setattr(faults, "_env_checked", False)
        monkeypatch.setenv(faults.ENV_VAR, "decode.raise@1")
        with pytest.raises(InjectedFault):
            faults.maybe_raise("decode.raise")
        faults.disarm()

    def test_exception_class_is_callers_choice(self):
        with faults.armed("ckpt.save_ioerror@1"):
            with pytest.raises(OSError):
                faults.maybe_raise("ckpt.save_ioerror", OSError)


# --------------------------------------------------------------------------
# decode watchdog (loader level)
# --------------------------------------------------------------------------

class _HangingSource:
    """Synthetic-shaped source whose chosen draws sleep: a stand-in for a
    wedged decode pipe, below the fault-site layer so the watchdog can be
    unit-tested without a manifest.  The first ``hang_first_n`` draws
    hang, and EVERY draw of ``hang_idx``."""

    def __init__(self, cfg, hang_first_n=0, hang_idx=None, sleep=2.0):
        from milnce_tpu.data.synthetic import SyntheticVideoTextSource

        self.inner = SyntheticVideoTextSource(cfg, num_samples=32)
        self.hang_first_n = hang_first_n
        self.hang_idx = hang_idx
        self.sleep = sleep
        self._lock = threading.Lock()
        self._calls = 0
        self._active = 0
        self._released = threading.Event()

    def __len__(self):
        return len(self.inner)

    def fallback_sample(self):
        return self.inner.fallback_sample()

    def sample(self, idx, rng):
        with self._lock:
            self._calls += 1
            n = self._calls
            self._active += 1
        try:
            if n <= self.hang_first_n or idx == self.hang_idx:
                self._released.wait(self.sleep)
            return self.inner.sample(idx, rng)
        finally:
            with self._lock:
                self._active -= 1

    def release(self):
        """Wake the sleepers and wait them out.  The watchdog abandons a
        hung reader thread, and one that outlives its test goes on to
        ``inner.sample``, whose ``decode.raise`` site then counts against
        whatever registry a LATER test has armed (the lost occurrence of
        ``test_chaos_host_sites_combined_run_survives`` under six
        workers: ROADMAP Queue 3 item 11)."""
        self._released.set()
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            with self._lock:
                if not self._active:
                    return
            time.sleep(0.005)
        raise AssertionError("a hung reader thread outlived its test")


def test_watchdog_retry_recovers_from_one_hang():
    from milnce_tpu.data.pipeline import ShardedLoader

    cfg = tiny_preset()
    src = _HangingSource(cfg.data, hang_first_n=1, sleep=2.0)
    loader = ShardedLoader(src, 4, seed=0, num_threads=2, process_index=0,
                           process_count=1, sample_timeout=0.2,
                           timeout_retries=2)
    gen = loader.epoch(0)
    try:
        batch = next(gen)
    finally:
        gen.close()
        src.release()
    assert batch["video"].shape[0] == 4
    assert loader.decode_timeouts >= 1
    # the retried decode succeeded: no black-frame fallback needed
    assert all(batch["video"][i].sum() > 0 for i in range(4))


def test_watchdog_escalates_to_black_frame_fallback():
    """An index whose EVERY decode attempt hangs is unrecoverable: after
    the retries, the watchdog escalates to the source's black-frame
    fallback and the batch still comes out full."""
    from milnce_tpu.data.pipeline import ShardedLoader

    cfg = tiny_preset()
    order = np.arange(32)
    np.random.RandomState(0 + 0).shuffle(order)      # seed + epoch
    src = _HangingSource(cfg.data, hang_idx=int(order[1]), sleep=4.0)
    loader = ShardedLoader(src, 4, seed=0, num_threads=2, process_index=0,
                           process_count=1, sample_timeout=0.1,
                           timeout_retries=1)
    gen = loader.epoch(0)
    try:
        batch = next(gen)
    finally:
        gen.close()
        src.release()
    assert batch["video"].shape[0] == 4
    assert loader.decode_timeouts >= 2  # initial + retry both timed out
    # exactly the wedged row fell back to black frames
    assert any(batch["video"][i].sum() == 0 for i in range(4))
    assert sum(batch["video"][i].sum() > 0 for i in range(4)) == 3


def test_watchdog_off_by_default_in_direct_loader_use():
    from milnce_tpu.data.pipeline import ShardedLoader
    from milnce_tpu.data.synthetic import SyntheticVideoTextSource

    cfg = tiny_preset()
    loader = ShardedLoader(SyntheticVideoTextSource(cfg.data), 4)
    assert loader.sample_timeout == 0.0


# --------------------------------------------------------------------------
# orphaned decoder subprocesses
# --------------------------------------------------------------------------

def test_kill_inflight_decoders_reaps_registered_children():
    import subprocess

    from milnce_tpu.data import video as video_mod

    proc = subprocess.Popen(["sleep", "30"])
    video_mod._register_inflight(proc)
    try:
        assert video_mod.kill_inflight_decoders() >= 1
        assert proc.wait(timeout=5) != 0    # terminated, not completed
    finally:
        video_mod._unregister_inflight(proc)


def test_ffmpeg_decode_child_registered_while_pumping(tmp_path):
    """A decode() in flight must be reapable: its child is in the
    registry for the duration of the pipe read, so a mid-epoch generator
    close kills it instead of orphaning a full decode."""
    from milnce_tpu.data import video as video_mod

    stub = tmp_path / "ffmpeg"
    # exec: the Popen child IS the sleeping process (like real ffmpeg),
    # not an sh wrapper whose orphan would keep the stdout pipe open
    stub.write_text("#!/bin/sh\nexec sleep 30\n")
    stub.chmod(0o755)
    dec = video_mod.FFmpegDecoder(binary=str(stub))
    result = {}

    def run():
        try:
            dec.decode("x.mp4", 0.0, 1.0, 10, 8)
        except Exception as exc:
            result["exc"] = exc

    t = threading.Thread(target=run)
    t.start()
    deadline = time.time() + 5.0
    while time.time() < deadline:
        with video_mod._INFLIGHT_LOCK:
            if video_mod._INFLIGHT:
                break
        time.sleep(0.02)
    assert video_mod.kill_inflight_decoders() >= 1
    t.join(timeout=5)
    assert not t.is_alive(), "decode survived the reaper"
    assert isinstance(result.get("exc"), Exception)


def test_loader_close_reaps_inflight_children(monkeypatch):
    """The generator's finally must call the reaper (the satellite fix:
    cancel_futures drops queued work but not already-spawned children)."""
    from milnce_tpu.data import pipeline as pipeline_mod
    from milnce_tpu.data import video as video_mod
    from milnce_tpu.data.synthetic import SyntheticVideoTextSource

    calls = {"n": 0}
    real = video_mod.kill_inflight_decoders
    monkeypatch.setattr(video_mod, "kill_inflight_decoders",
                        lambda *a, **k: (calls.__setitem__("n", calls["n"] + 1)
                                         or real(*a, **k)))
    cfg = tiny_preset()
    loader = pipeline_mod.ShardedLoader(
        SyntheticVideoTextSource(cfg.data, num_samples=16), 4, num_threads=2)
    gen = loader.epoch(0)
    next(gen)
    gen.close()
    assert calls["n"] == 1


# --------------------------------------------------------------------------
# dataset health: max_failure_rate + failure logging
# --------------------------------------------------------------------------

def _howto_fixture(tmp_path, n_rows=16):
    rows = ["video_path"] + [f"vid{i}.mp4" for i in range(n_rows)]
    (tmp_path / "train.csv").write_text("\n".join(rows) + "\n")
    (tmp_path / "captions").mkdir(exist_ok=True)
    for i in range(n_rows):
        (tmp_path / "captions" / f"vid{i}.json").write_text(json.dumps(
            {"start": [0.0, 6.0], "end": [5.0, 12.0],
             "text": ["pour the batter", "flip the pancake"]}))
    cfg = tiny_preset()
    cfg.data.train_csv = str(tmp_path / "train.csv")
    cfg.data.video_root = str(tmp_path)
    cfg.data.caption_root = str(tmp_path / "captions")
    cfg.data.synthetic = False
    cfg.data.decoder_backend = "fake"
    return cfg


def test_max_failure_rate_aborts_broken_dataset(tmp_path):
    from milnce_tpu.data.datasets import DataHealthError, HowTo100MSource
    from milnce_tpu.data.video import FakeDecoder

    class AlwaysBad(FakeDecoder):
        def decode(self, *a, **kw):
            raise RuntimeError("corrupt")

    cfg = _howto_fixture(tmp_path)
    cfg.data.max_failure_rate = 0.5
    src = HowTo100MSource(cfg.data, cfg.model, decoder=AlwaysBad())
    rng = np.random.RandomState(0)
    with pytest.raises(DataHealthError, match="max_failure_rate"):
        for i in range(16):
            src.sample(i % len(src), rng)
    # and the default black-frame behavior survives when DISABLED
    cfg.data.max_failure_rate = 1.0
    src2 = HowTo100MSource(cfg.data, cfg.model, decoder=AlwaysBad())
    for i in range(8):
        s = src2.sample(i, rng)
    assert s["video"].sum() == 0


def test_failure_details_route_through_log_fn(tmp_path):
    from milnce_tpu.data.datasets import HowTo100MSource
    from milnce_tpu.data.video import FakeDecoder

    class BadOnce(FakeDecoder):
        def __init__(self):
            super().__init__()
            self.raised = False

        def decode(self, *a, **kw):
            if not self.raised:
                self.raised = True
                raise RuntimeError("corrupt")
            return super().decode(*a, **kw)

    cfg = _howto_fixture(tmp_path)
    lines = []
    src = HowTo100MSource(cfg.data, cfg.model, decoder=BadOnce(),
                          log_fn=lines.append)
    src.sample(0, np.random.RandomState(0))
    assert src.decode_failures == 1
    assert any("resampling" in ln for ln in lines), lines


# --------------------------------------------------------------------------
# chaos: the four fault sites through run_training (the acceptance gate)
# --------------------------------------------------------------------------

def _run_cfg(tmp_path, name):
    cfg = tiny_preset()
    cfg.model.inception_blocks = 1
    cfg.train.batch_size = 8
    cfg.data.synthetic_num_samples = 32
    cfg.data.num_reader_threads = 2
    cfg.train.checkpoint_root = str(tmp_path / f"ckpt_{name}")
    cfg.train.log_root = str(tmp_path / f"log_{name}")
    return cfg


def test_chaos_host_sites_combined_run_survives(tmp_path, capsys):
    """decode.raise + decode.hang + ckpt.save_ioerror armed TOGETHER in
    one production run over the real HowTo100M source stack (fake
    decoder backend): the source resamples the corrupt decodes (counted,
    surfaced in the display line — satellite), the watchdog times the
    wedged decode out and retries, the exit checkpoint save survives its
    first-attempt IOError via retry, and training reaches max_steps.
    One run, three fault sites — each with its own evidence."""
    from milnce_tpu.train.checkpoint import CheckpointManager
    from milnce_tpu.train.loop import run_training

    cfg = _run_cfg(tmp_path, "hostsites")
    hcfg = _howto_fixture(tmp_path)
    cfg.data = hcfg.data
    cfg.data.num_reader_threads = 2
    cfg.data.sample_timeout = 0.3
    cfg.data.sample_timeout_retries = 2
    cfg.train.faults = ("decode.raise@1,2;decode.hang@3:x=3.0;"
                        "ckpt.save_ioerror@1")
    res = run_training(cfg, max_steps=2)
    assert res.steps == 2 and np.isfinite(res.last_loss)
    out = capsys.readouterr().out
    assert "Decode failures: 2" in out, out       # decode.raise resampled
    assert "Decode timeouts:" in out, out         # decode.hang watchdogged
    assert faults._active() is None               # config arming disarmed
    mgr = CheckpointManager(str(tmp_path / "ckpt_hostsites" / "run"),
                            create=False)
    assert mgr.latest_epoch() is not None         # retried save committed
    mgr.close()


def test_chaos_grad_nonfinite_guard_skips_and_run_survives(tmp_path, capsys):
    """grad.nonfinite armed at step 2: the finite guard skips exactly
    that update (device-side, under the steady-state transfer guard —
    a smuggled host sync would raise) and training reaches max_steps."""
    from milnce_tpu.train.loop import run_training

    cfg = _run_cfg(tmp_path, "gnan")
    cfg.train.faults = "grad.nonfinite@2"
    res = run_training(cfg, max_steps=3)
    assert res.steps == 3 and np.isfinite(res.last_loss)
    assert res.skipped_steps == 1
    assert res.rollbacks == 0
    assert "Skipped steps: 1" in capsys.readouterr().out


def test_ckpt_save_retry_exhaustion_reraises(tmp_path):
    import jax.numpy as jnp
    import optax

    from milnce_tpu.train.checkpoint import CheckpointManager
    from milnce_tpu.train.state import create_train_state

    variables = {"params": {"w": np.ones((4,), np.float32)}}
    state = create_train_state(variables, optax.sgd(1e-2))
    mgr = CheckpointManager(str(tmp_path / "run"), keep=2,
                            save_retries=1, retry_backoff=0.01)
    with faults.armed("ckpt.save_ioerror@*"):
        with pytest.raises(OSError):
            mgr.save(1, state)
    # transient single failure: retried and committed
    with faults.armed("ckpt.save_ioerror@1"):
        mgr.save(1, state)
    mgr.wait()
    assert mgr.latest_epoch() == 1
    mgr.close()


def test_chaos_circuit_breaker_rolls_back_and_resumes(tmp_path, capsys):
    """Every step non-finite: after K consecutive skips the breaker
    restores the rotation checkpoint and resumes PAST the poisoned
    window (instead of halting); the run still reaches max_steps."""
    from milnce_tpu.train.loop import run_training

    cfg = _run_cfg(tmp_path, "breaker")
    cfg.optim.epochs = 2
    first = run_training(cfg, max_steps=2)          # clean run: rotation ckpt
    assert first.steps == 2 and first.rollbacks == 0

    cfg.train.resume = True
    cfg.train.faults = "grad.nonfinite@*"
    cfg.train.skip_rollback_after = 2
    cfg.train.n_display = 2
    res = run_training(cfg, max_steps=3)
    assert res.steps == 3
    assert res.skipped_steps == 3                   # every update skipped
    assert res.rollbacks >= 1
    assert "circuit breaker" in capsys.readouterr().out


def test_breaker_halts_after_fruitless_rollback(tmp_path):
    """Persistent non-finite gradients (every step, forever) must
    TERMINATE: a second breaker trip with zero applied updates since the
    previous rollback proves the failure isn't a data window — halt
    instead of looping rollback-skip-rollback for the rest of the pod
    budget."""
    from milnce_tpu.train.loop import run_training

    cfg = _run_cfg(tmp_path, "fruitless")
    cfg.optim.epochs = 4
    first = run_training(cfg, max_steps=2)          # rotation checkpoint
    assert first.rollbacks == 0
    cfg.train.resume = True
    cfg.train.faults = "grad.nonfinite@*"
    cfg.train.skip_rollback_after = 2
    cfg.train.n_display = 2
    with pytest.raises(FloatingPointError, match="persistent"):
        run_training(cfg, max_steps=50)


def test_breaker_without_checkpoint_halts(tmp_path):
    """Poisoned from step 1 with nothing to roll back to: the breaker
    must halt loudly, not spin forever."""
    from milnce_tpu.train.loop import run_training

    cfg = _run_cfg(tmp_path, "nockpt")
    cfg.train.faults = "grad.nonfinite@*"
    cfg.train.skip_rollback_after = 2
    cfg.train.n_display = 2
    with pytest.raises(FloatingPointError, match="no rotation checkpoint"):
        run_training(cfg, max_steps=8)


# --------------------------------------------------------------------------
# finite guard: step-level semantics + trace invariants under injection
# --------------------------------------------------------------------------

def _tiny_step_setup():
    import jax
    import jax.numpy as jnp

    from milnce_tpu.config import OptimConfig, ParallelConfig
    from milnce_tpu.models import S3D
    from milnce_tpu.parallel.mesh import build_mesh
    from milnce_tpu.train.schedule import build_schedule
    from milnce_tpu.train.state import build_optimizer, create_train_state

    model = S3D(num_classes=16, vocab_size=32, word_embedding_dim=8,
                text_hidden_dim=16, inception_blocks=1)
    video = np.random.default_rng(0).integers(
        0, 255, (8, 4, 32, 32, 3), dtype=np.uint8)
    text = np.zeros((8, 5), np.int32)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((2,) + video.shape[1:], jnp.float32),
                           text[:2])
    ocfg = OptimConfig(name="adam", warmup_steps=1)
    opt = build_optimizer(ocfg, build_schedule(ocfg, 10))
    state = create_train_state(variables, opt)
    mesh = build_mesh(ParallelConfig())
    return model, opt, mesh, state, video, text


def test_finite_guard_skips_poisoned_update_keeps_clean_ones():
    import jax

    from milnce_tpu.train.step import make_train_step

    model, opt, mesh, state, video, text = _tiny_step_setup()
    zeros = np.zeros((8,), np.float32)
    with faults.armed("grad.nonfinite@2"):
        step = make_train_step(model, opt, mesh, donate=False,
                               finite_guard=True)
        s1, loss1, sk1 = step(state, video, text, zeros)    # occurrence 1
        s2, loss2, sk2 = step(s1, video, text, zeros)       # occurrence 2: hit
        s3, loss3, sk3 = step(s2, video, text, zeros)       # occurrence 3
    assert (int(sk1), int(sk2), int(sk3)) == (0, 1, 0)
    # the poisoned step kept params bit-identical and still advanced step
    for a, b in zip(jax.tree_util.tree_leaves(s1.params),
                    jax.tree_util.tree_leaves(s2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (int(s1.step), int(s2.step), int(s3.step)) == (1, 2, 3)
    # the clean step after the skip really updated
    changed = [not np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree_util.tree_leaves(s2.params),
                               jax.tree_util.tree_leaves(s3.params))]
    assert any(changed)
    assert all(np.isfinite(float(l)) for l in (loss1, loss2, loss3))


def test_guarded_step_collectives_unchanged_under_injection():
    """The acceptance pin: arming grad.nonfinite must not change the
    step's communication structure (no new collectives, hence no new
    sync points) — the injection is pure jnp on state.step."""
    import jax

    from milnce_tpu.analysis.trace_invariants import (EXPECTED_COLLECTIVES,
                                                      collective_counts,
                                                      f64_sites, _setup)
    from milnce_tpu.train.step import make_train_step

    model, opt, mesh, state, batch = _setup()
    with faults.armed("grad.nonfinite@*"):
        step = make_train_step(model, opt, mesh, donate=False,
                               finite_guard=True)
        jaxpr = jax.make_jaxpr(step)(state, *batch()).jaxpr
    assert (collective_counts(jaxpr)
            == EXPECTED_COLLECTIVES["train_step_milnce_guarded"])
    assert f64_sites(jaxpr) == []


# --------------------------------------------------------------------------
# checkpoint fallback branches + nan_postmortem isolation (satellite)
# --------------------------------------------------------------------------

def test_restore_fallback_reinit_vs_reraise_fast(tmp_path):
    """Tier-1 (model-free) pin of restore_latest's discrimination: an
    optimizer-structure evolution falls back to weights-only restore; a
    params mismatch re-raises (the slow tier covers the full-model
    variants in test_train.py)."""
    import jax
    import jax.numpy as jnp
    import optax

    from milnce_tpu.train.checkpoint import CheckpointManager
    from milnce_tpu.train.state import create_train_state

    variables = {"params": {"w": np.ones((4,), np.float32),
                            "b": np.zeros((2,), np.float32)}}
    old_state = create_train_state(variables, optax.adam(1e-3)).replace(
        step=jnp.asarray(5, jnp.int32))
    mgr = CheckpointManager(str(tmp_path / "run"), keep=2)
    mgr.save(2, old_state)
    mgr.close()

    # optimizer tree evolved (chain wrapper): weights-only fallback
    new_opt = optax.chain(optax.clip_by_global_norm(1.0), optax.adam(1e-3))
    template = create_train_state(variables, new_opt)
    mgr2 = CheckpointManager(str(tmp_path / "run"), keep=2, create=False)
    epoch, restored = mgr2.restore_latest(template)
    assert epoch == 2 and int(restored.step) == 5
    assert (jax.tree_util.tree_structure(restored.opt_state)
            == jax.tree_util.tree_structure(template.opt_state))

    # params tree changed (model evolved): NOT rescuable — re-raise
    bad_vars = {"params": {"w": np.ones((4,), np.float32)}}
    bad_template = create_train_state(bad_vars, new_opt)
    mgr3 = CheckpointManager(str(tmp_path / "run"), keep=2, create=False)
    with pytest.raises((ValueError, KeyError, TypeError)):
        mgr3.restore_latest(bad_template)


def test_nan_postmortem_isolated_from_rotation(tmp_path):
    """finite_guard OFF + halt_on_nan: the legacy divergence guard still
    halts, snapshotting into nan_postmortem/ WITHOUT touching the
    rotation directory — a later --resume must not restore NaN params."""
    from milnce_tpu.train.loop import run_training

    cfg = _run_cfg(tmp_path, "postmortem")
    cfg.train.finite_guard = False
    cfg.train.faults = "grad.nonfinite@1"
    with pytest.raises(FloatingPointError, match="non-finite"):
        run_training(cfg, max_steps=4)
    run_dir = tmp_path / "ckpt_postmortem" / "run"
    pm = run_dir / "nan_postmortem"
    assert pm.is_dir() and any(p.name.isdigit() for p in pm.iterdir())
    rotation = [p for p in run_dir.iterdir() if p.name.isdigit()]
    assert not rotation, f"NaN state leaked into the rotation: {rotation}"


def test_resume_and_stop_label_math():
    """The epoch-boundary edge cases of the mid-epoch resume math
    (satellite): offsets and checkpoint labels, as pure functions."""
    from milnce_tpu.train.loop import resume_batch_offset, stop_save_label

    assert resume_batch_offset(0, 4) == 0
    assert resume_batch_offset(3, 4) == 3
    assert resume_batch_offset(4, 4) == 0        # boundary: nothing to skip
    assert resume_batch_offset(9, 4) == 1
    # mid-epoch stop: current epoch, forced (label collides with the
    # previous boundary save)
    assert stop_save_label(0, 2, 4) == (0, True)
    assert stop_save_label(1, 6, 4) == (1, True)
    # stop ON the boundary: epoch+1, ordinary save
    assert stop_save_label(0, 4, 4) == (1, False)
    assert stop_save_label(1, 8, 4) == (2, False)
