"""Curriculum training (ISSUE 16): staged (frames, resolution, batch)
schedule with pre-flighted re-trace and checkpoint-compatible
transitions.

Covers the four layers the tentpole touches:

- the ``train.curriculum`` grammar and the step-level plan simulator
  (train/curriculum.py), including the pinned equivalence of the flat
  plan to the historical ``resume_batch_offset`` / ``stop_save_label``
  modulo helpers and the satellite-4 schedule-total audit;
- the goodput ledger's ``stage_switch`` attribution (obs/goodput.py);
- the mem_plan pre-flight refusing an over-budget stage BEFORE any
  stage traces;
- the two-stage tiny-CPU acceptance run: loss-trajectory continuity,
  ledger summing to measured wall within 5% with a nonzero
  ``stage_switch`` bucket, the stage stamp, and the three
  checkpoint/resume scenarios (mid-stage, boundary, schedule removed).

Pinned tier-1 (never @slow) by tests/test_suite_hygiene.py.
"""

import json
import os
import sys
import time

import numpy as np
import pytest

from milnce_tpu.config import tiny_preset
from milnce_tpu.train import curriculum as curr
from milnce_tpu.train.curriculum import CurriculumStage

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# the acceptance schedule: 4f until global step 3, then 8f to the end.
# Shapes deliberately reuse the rig's cached 4f@32 batch-8 program and
# add exactly ONE new shape (8f@32) — tier-1 compile budget.
TWO_STAGE = ("num_frames=4,resolution=32,until_step=3;"
             "num_frames=8,resolution=32")


def _tiny_cfg(tmp_path, samples=48, epochs=1):
    cfg = tiny_preset()
    cfg.model.inception_blocks = 1      # 1-block S3D: tier-1 compile time
    cfg.train.batch_size = 8
    cfg.data.synthetic_num_samples = samples
    cfg.data.num_reader_threads = 2
    cfg.optim.epochs = epochs
    cfg.train.checkpoint_root = str(tmp_path / "ckpt")
    cfg.train.log_root = str(tmp_path / "log")
    return cfg


def _read_events(cfg):
    path = os.path.join(cfg.train.log_root, "RUN_EVENTS.jsonl")
    assert os.path.exists(path)
    return [json.loads(line) for line in open(path)]


# --------------------------------------------------------------------------
# grammar
# --------------------------------------------------------------------------

class TestParseCurriculum:
    def test_empty_spec_is_flat(self):
        assert curr.parse_curriculum("") == []

    def test_inline_grammar_with_inherited_batch(self):
        stages = curr.parse_curriculum(TWO_STAGE, default_batch_size=8)
        assert [s.num_frames for s in stages] == [4, 8]
        assert [s.resolution for s in stages] == [32, 32]
        assert [s.batch_size for s in stages] == [8, 8]
        assert stages[0].until_step == 3 and stages[1].until_step is None
        assert stages[0].label() == "4f@32 batch 8"

    def test_json_artifact_path(self, tmp_path):
        art = tmp_path / "sched.json"
        art.write_text(json.dumps({"curriculum": [
            {"num_frames": 4, "resolution": 64, "batch_size": 16,
             "until_epoch": 1},
            {"num_frames": 8, "resolution": 112, "batch_size": 8},
        ]}))
        stages = curr.parse_curriculum(str(art))
        assert stages[0].until_epoch == 1
        assert stages[1].batch_size == 8

    @pytest.mark.parametrize("bad,match", [
        ("num_frames=4,fps=2;num_frames=8,resolution=32",
         "unknown key"),                          # unknown key
        ("num_frames=x,resolution=32", "not an integer"),
        ("num_frames=0,resolution=32", "must be > 0"),
        ("num_frames=4,resolution=32,until_step=2,until_epoch=1;"
         "num_frames=8,resolution=32", "BOTH"),   # both bounds
        ("num_frames=4,resolution=32,until_step=2", "open-ended"),
        ("num_frames=4,resolution=32;num_frames=8,resolution=32",
         "needs until_step or until_epoch"),      # unbounded middle
        ("num_frames=4", "resolution"),           # missing required
        ("/no/such/artifact.json", "no such file"),
    ])
    def test_malformed_specs_fail_loudly(self, bad, match):
        with pytest.raises(ValueError, match=match):
            curr.parse_curriculum(bad, default_batch_size=8)

    def test_missing_batch_without_default_fails(self):
        with pytest.raises(ValueError, match="no batch_size"):
            curr.parse_curriculum("num_frames=4,resolution=32")


# --------------------------------------------------------------------------
# plan simulator
# --------------------------------------------------------------------------

class TestPlanCurriculum:
    def test_flat_plan_matches_modulo_helpers(self):
        """The flat run is a single-stage plan through the SAME
        machinery; its locate()/epoch math must equal the historical
        resume_batch_offset / stop_save_label helpers exactly."""
        from milnce_tpu.train.loop import (resume_batch_offset,
                                           stop_save_label,
                                           stop_save_label_planned)

        plan = curr.plan_curriculum(
            [CurriculumStage(num_frames=4, resolution=32, batch_size=8)],
            num_samples=48, epochs=2)       # spe 6, total 12
        assert plan.total_steps == 12
        for step in range(12):
            seg, off = plan.locate(step)
            assert seg.skip_batches + off == resume_batch_offset(step, 6)
        for epoch, opt_step in [(0, 2), (0, 6), (1, 8), (1, 12)]:
            assert (stop_save_label_planned(epoch, opt_step, plan)
                    == stop_save_label(epoch, opt_step, 6))

    def test_mid_epoch_switch_segments(self):
        stages = curr.parse_curriculum(TWO_STAGE, default_batch_size=8)
        plan = curr.plan_curriculum(stages, num_samples=48, epochs=1)
        assert plan.total_steps == 6
        segs = plan.segments
        assert [(s.stage, s.epoch, s.skip_batches, s.start_step, s.n_steps)
                for s in segs] == [(0, 0, 0, 0, 3), (1, 0, 3, 3, 3)]
        assert plan.stage_at(2) == 0 and plan.stage_at(3) == 1
        seg, off = plan.locate(4)
        assert seg.stage == 1 and off == 1
        # a finished run resumes to a no-op at the end of the last seg
        seg, off = plan.locate(plan.total_steps)
        assert seg is segs[-1] and off == seg.n_steps

    def test_batch_change_reskips_consumed_samples(self):
        # stage 0 consumes 3*4=12 samples; stage 1 at batch 8 must skip
        # ceil(12/8)=2 batches so no sample trains twice in the epoch
        stages = [
            CurriculumStage(num_frames=4, resolution=32, batch_size=4,
                            until_step=3),
            CurriculumStage(num_frames=4, resolution=32, batch_size=8)]
        plan = curr.plan_curriculum(stages, num_samples=48, epochs=1)
        seg1 = plan.segments[1]
        assert seg1.skip_batches == 2
        assert seg1.n_steps == 48 // 8 - 2
        assert plan.total_steps == 3 + 4

    def test_until_epoch_switches_at_epoch_entry(self):
        stages = [
            CurriculumStage(num_frames=4, resolution=32, batch_size=8,
                            until_epoch=1),
            CurriculumStage(num_frames=8, resolution=32, batch_size=8)]
        plan = curr.plan_curriculum(stages, num_samples=48, epochs=2)
        assert [(s.stage, s.epoch) for s in plan.segments] == [(0, 0),
                                                               (1, 1)]
        assert plan.epoch_start_step(1) == 6 and plan.total_steps == 12

    def test_unreachable_stage_refused(self):
        stages = [
            CurriculumStage(num_frames=4, resolution=32, batch_size=8,
                            until_step=100),
            CurriculumStage(num_frames=8, resolution=32, batch_size=8)]
        with pytest.raises(ValueError, match="unreachable"):
            curr.plan_curriculum(stages, num_samples=48, epochs=1)

    def test_oversized_stage_batch_refused(self):
        with pytest.raises(ValueError, match="exceeds the dataset"):
            curr.plan_curriculum(
                [CurriculumStage(num_frames=4, resolution=32,
                                 batch_size=64)],
                num_samples=48, epochs=1)

    def test_schedule_totals_follow_the_plan_not_flat_spe(self):
        """Satellite 4: warmup/cosine totals must come from the plan's
        simulated step count.  With per-stage batch sizes the naive
        ``steps_per_epoch(flat) * epochs`` is simply wrong — pin both
        the divergence and the flat-case equivalence."""
        from milnce_tpu.config import OptimConfig
        from milnce_tpu.train.schedule import (build_host_schedule,
                                               build_host_schedule_total)

        mixed = curr.plan_curriculum(
            [CurriculumStage(num_frames=4, resolution=32, batch_size=4,
                             until_step=3),
             CurriculumStage(num_frames=4, resolution=32, batch_size=8)],
            num_samples=48, epochs=1)
        assert mixed.total_steps == 7       # != 48//8 and != 48//4
        assert mixed.total_steps != 48 // 8 * 1

        ocfg = OptimConfig()
        ocfg.epochs = 2
        flat = curr.plan_curriculum(
            [CurriculumStage(num_frames=4, resolution=32, batch_size=8)],
            num_samples=48, epochs=2)
        by_total = build_host_schedule_total(ocfg, flat.total_steps)
        by_spe = build_host_schedule(ocfg, 6)
        for step in range(flat.total_steps + 1):
            assert by_total(step) == pytest.approx(by_spe(step), rel=1e-12)


# --------------------------------------------------------------------------
# goodput: stage_switch attribution (pure ledger unit)
# --------------------------------------------------------------------------

def test_ledger_attributes_stage_switch_and_retrace():
    """The stage.switch span AND the first step dispatched after it (the
    new stage's trace+compile) land in ``stage_switch``, excluded from
    the compute pool — curriculum overhead is measured, not guessed."""
    from milnce_tpu.obs.goodput import compute_ledger

    recs = [
        {"kind": "event", "name": "run.start", "ts": 0.0},
        {"kind": "span", "name": "step", "ts": 1.0, "dur_ms": 2000.0},
        {"kind": "span", "name": "step", "ts": 3.0, "dur_ms": 500.0},
        {"kind": "span", "name": "stage.switch", "ts": 3.6,
         "dur_ms": 400.0},
        {"kind": "span", "name": "step", "ts": 4.0, "dur_ms": 1500.0},
        {"kind": "span", "name": "step", "ts": 5.5, "dur_ms": 500.0},
        {"kind": "event", "name": "run.end", "ts": 7.0},
    ]
    led = compute_ledger(recs)
    assert led.stage_switches == 1
    assert led.categories["compile"] == pytest.approx(2.0)
    assert led.categories["stage_switch"] == pytest.approx(0.4 + 1.5)
    assert led.categories["compute"] == pytest.approx(1.0)
    assert led.to_extra()["stage_switches"] == 1
    assert sum(led.categories.values()) == pytest.approx(led.wall_s)


# --------------------------------------------------------------------------
# pre-flight
# --------------------------------------------------------------------------

def test_hbm_budget_env_wins(monkeypatch):
    monkeypatch.setenv("MILNCE_HBM_GIB", "2.0")
    assert curr.hbm_budget_bytes() == 2 * 2 ** 30


def test_preflight_refuses_over_budget_stage_before_trace(tmp_path,
                                                          monkeypatch):
    """An impossible per-chip budget must refuse the run AT STARTUP with
    the stage named — before any stage compiles (the refusal arrives in
    well under a compile's time because the plan traces abstractly)."""
    from milnce_tpu.train.loop import run_training

    cfg = _tiny_cfg(tmp_path)
    cfg.train.curriculum = TWO_STAGE
    monkeypatch.setenv("MILNCE_HBM_GIB", "0.0001")
    with pytest.raises(ValueError) as exc_info:
        run_training(cfg, max_steps=6)
    msg = str(exc_info.value)
    assert "curriculum pre-flight refused" in msg
    assert "curriculum stage 0 (4f@32 batch 8)" in msg
    assert "EXCEEDS" in msg
    # top contributors are named so the refusal is actionable
    assert "top contributors" in msg


# --------------------------------------------------------------------------
# acceptance: the two-stage tiny-CPU run (ISSUE 16 acceptance criteria)
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def curriculum_run(tmp_path_factory):
    """ONE two-stage run (4f@32 -> 8f@32, switch at step 3) shared by
    the acceptance pins below — training runs are the expensive part of
    this file, so the ledger/stamp/events assertions share it."""
    from milnce_tpu.train.loop import run_training

    tmp = tmp_path_factory.mktemp("curr_accept")
    cfg = _tiny_cfg(tmp)
    cfg.train.curriculum = TWO_STAGE
    cfg.train.run_id = "curr-accept"
    t0 = time.monotonic()
    res = run_training(cfg, max_steps=6)
    return {"cfg": cfg, "res": res, "wall": time.monotonic() - t0}


def test_two_stage_run_finishes_in_final_stage(curriculum_run):
    res = curriculum_run["res"]
    assert res.steps == 6
    assert res.stage == 1
    assert np.isfinite(res.last_loss)


def test_stage_switch_events_and_plan_event(curriculum_run):
    events = _read_events(curriculum_run["cfg"])
    plans = [e for e in events if e.get("name") == "curriculum.plan"]
    assert len(plans) == 1
    assert plans[0]["total_steps"] == 6 and len(plans[0]["stages"]) == 2
    switches = [e for e in events if e.get("name") == "stage.switch"]
    assert len(switches) == 1
    sw = switches[0]
    assert sw["stage"] == 1 and sw["prev_stage"] == 0 and sw["step"] == 3
    assert sw["num_frames"] == 8 and sw["resolution"] == 32
    # the display line tracks the live stage (n_display=1: every step)
    displays = [e for e in events if e.get("name") == "display"]
    assert displays and displays[0]["stage"] == 0
    assert displays[-1]["stage"] == 1
    # checkpoint spans carry the stage they saved under
    saves = [e for e in events if e.get("name") == "ckpt.save"]
    assert saves and saves[-1]["stage"] == 1


def test_ledger_sums_to_wall_with_nonzero_stage_switch(curriculum_run):
    cfg, wall = curriculum_run["cfg"], curriculum_run["wall"]
    doc = json.load(open(os.path.join(cfg.train.log_root, "GOODPUT.json")))
    assert doc["stage_switches"] == 1
    assert doc["categories_s"]["stage_switch"] > 0.0
    total = sum(doc["categories_s"].values())
    assert total == pytest.approx(wall, rel=0.05), (
        f"ledger sum {total:.3f}s vs measured {wall:.3f}s "
        f"(categories {doc['categories_s']})")


def test_stage_stamp_written_next_to_rotation(curriculum_run):
    cfg = curriculum_run["cfg"]
    stamp = curr.read_stage_stamp(
        os.path.join(cfg.train.checkpoint_root, "run"))
    assert stamp is not None
    assert stamp["schema"] == "milnce.curriculum/v1"
    assert stamp["curriculum"] == TWO_STAGE
    assert stamp["stage"] == 1
    assert stamp["num_frames"] == 8 and stamp["resolution"] == 32
    assert stamp["step"] == 6


def test_loss_continuity_vs_flat_run_at_final_shape(curriculum_run,
                                                    tmp_path):
    """Post-switch, the curriculum run trains at the flat 8f config's
    shape from a 3-step head start; its post-switch window mean must sit
    in the same regime as a flat 8f run of the same seed/data (synthetic
    losses are volatile step to step, so the band is generous — the
    failure mode this guards is a divergence/garbage state after the
    transition, which lands orders of magnitude away)."""
    from milnce_tpu.train.loop import run_training

    flat_cfg = _tiny_cfg(tmp_path)
    flat_cfg.data.num_frames = 8
    flat_cfg.data.video_size = 32
    flat_res = run_training(flat_cfg, max_steps=6)
    assert np.isfinite(flat_res.last_loss)

    disp_c = [e for e in _read_events(curriculum_run["cfg"])
              if e.get("name") == "display"]
    disp_f = [e for e in _read_events(flat_cfg)
              if e.get("name") == "display"]
    post = [e["loss"] for e in disp_c if e["stage"] == 1]
    ref = [e["loss"] for e in disp_f][-len(post):]
    assert post and all(np.isfinite(v) for v in post)
    ratio = np.mean(post) / np.mean(ref)
    assert 0.25 <= ratio <= 4.0, (
        f"post-switch window mean {np.mean(post):.3f} vs flat "
        f"{np.mean(ref):.3f} (ratio {ratio:.2f})")


# --------------------------------------------------------------------------
# checkpoint-compatible transitions (satellite 3)
# --------------------------------------------------------------------------

def test_resume_mid_stage_lands_at_right_offset(tmp_path, capsys):
    from milnce_tpu.train.loop import run_training

    cfg = _tiny_cfg(tmp_path)
    cfg.train.curriculum = TWO_STAGE
    r1 = run_training(cfg, max_steps=5)     # stops mid-stage-1 at step 5
    assert r1.stage == 1

    cfg.train.resume = True
    r2 = run_training(cfg, max_steps=1)
    out = capsys.readouterr().out
    assert r2.steps == 1 and r2.stage == 1
    assert int(r2.state.step) == 6          # optimizer counter carried
    # the resume log pins the batch offset (stage-1 skip 3 + 2 done) and
    # the stage the plan located
    assert "at batch 5" in out, out
    assert "curriculum stage 1" in out, out


def test_resume_at_boundary_enters_next_stage(tmp_path):
    from milnce_tpu.train.loop import run_training

    cfg = _tiny_cfg(tmp_path)
    cfg.train.curriculum = TWO_STAGE
    r1 = run_training(cfg, max_steps=3)     # stops ON the stage boundary
    assert r1.stage == 0                    # saved while still in stage 0
    stamp = curr.read_stage_stamp(
        os.path.join(cfg.train.checkpoint_root, "run"))
    assert stamp["stage"] == 0 and stamp["step"] == 3

    cfg.train.resume = True
    r2 = run_training(cfg, max_steps=1)     # plan.locate(3) -> stage 1
    assert r2.stage == 1
    assert int(r2.state.step) == 4


def test_resume_with_curriculum_removed_fails_loudly(tmp_path):
    from milnce_tpu.train.loop import run_training

    cfg = _tiny_cfg(tmp_path)
    cfg.train.curriculum = TWO_STAGE
    run_training(cfg, max_steps=3)

    cfg.train.curriculum = ""
    cfg.train.resume = True
    with pytest.raises(ValueError) as exc_info:
        run_training(cfg, max_steps=1)
    msg = str(exc_info.value)
    assert "train.curriculum is unset" in msg
    assert "4f@32" in msg                   # the saved stage's shape named


# --------------------------------------------------------------------------
# bench curriculum axis (satellite 1) — sweep logic with a fake child
# --------------------------------------------------------------------------

def _fake_bench_row(timeout_s=None, **kw):
    f = kw["frames"]
    return {"platform": "tpu", "device_kind": "TPU v5 lite", "n_chips": 1,
            "dtype": kw["dtype"], "batch": kw["batch"],
            "remat": kw["remat"], "s2d": kw["s2d"],
            "conv_impl": kw["conv_impl"], "loss": kw.get("loss", "milnce"),
            "loss_impl": None, "grad_accum": kw.get("grad_accum", 1),
            "inner": kw["inner"], "step_ms": 100.0 * f,
            "clips_per_sec_per_chip": 240.0 / f,
            "flops_per_step": None, "flops_source": None,
            "flops_per_sec": None}


def test_bench_curriculum_axis_composes_schedule_rate(monkeypatch):
    """MILNCE_BENCH_CURRICULUM measures each stage at its own shape and
    reports the whole-schedule rate vs a flat full-res run of the same
    total clip count; stage rows never displace the headline."""
    import bench

    recs, notes = [], {}
    monkeypatch.setattr(bench, "_run_config", _fake_bench_row)
    monkeypatch.setattr(bench, "_emit", recs.append)
    monkeypatch.setattr(bench, "_write_notes",
                        lambda *a, **k: notes.update(k))
    monkeypatch.setenv(
        "MILNCE_BENCH_CURRICULUM",
        "num_frames=2,resolution=32,batch_size=8,until_step=100;"
        "num_frames=4,resolution=64,batch_size=8")
    rec = bench.run_bench()

    # headline = the sweep's 16f row (240/16), untouched by stage rows
    assert rec["value"] == pytest.approx(15.0)
    cur = rec["curriculum"]
    assert [s["label"] for s in cur["stages"]] == ["2f@32 batch 8",
                                                   "4f@64 batch 8"]
    # final stage defaults to the bounded stages' total steps
    assert [s["steps"] for s in cur["stages"]] == [100, 100]
    assert cur["total_clips"] == 1600
    # 800 clips @120 + 800 @60 -> 20s vs flat 1600 @60 -> 26.67s
    assert cur["schedule_clips_per_sec_per_chip"] == pytest.approx(80.0)
    assert cur["flat_clips_per_sec_per_chip"] == pytest.approx(60.0)
    assert cur["speedup_vs_flat"] == pytest.approx(4.0 / 3.0, abs=1e-3)
    # BENCH_NOTES gets the same summary (the stage column's source)
    assert notes["curriculum"]["speedup_vs_flat"] == cur["speedup_vs_flat"]


def test_bench_curriculum_axis_requires_step_bounds(monkeypatch):
    """Epoch-bounded stages need a dataset size a synthetic bench does
    not have — the axis fails softly (sweep results kept, no curriculum
    key) rather than fabricating a schedule rate."""
    import bench

    monkeypatch.setattr(bench, "_run_config", _fake_bench_row)
    monkeypatch.setattr(bench, "_emit", lambda r: None)
    monkeypatch.setattr(bench, "_write_notes", lambda *a, **k: None)
    monkeypatch.setenv(
        "MILNCE_BENCH_CURRICULUM",
        "num_frames=2,resolution=32,batch_size=8,until_epoch=1;"
        "num_frames=4,resolution=64,batch_size=8")
    rec = bench.run_bench()
    assert "curriculum" not in rec
    assert rec["value"] == pytest.approx(15.0)
