"""Curriculum schedule: staged (frames, resolution, batch) training.

The paper's pretraining burns most of its FLOPs on full-rate clips from
step 0; a curriculum runs early training at low fps/resolution and only
graduates to the full operating point late (PAPERS.md: Arachne).  This
module is the pure-host half of that: parse ``train.curriculum``, turn
it into an exact step-level plan, and pre-flight every stage's memory
footprint before anything traces.  train/loop.py consumes the plan; the
module itself touches no devices (the pre-flight traces abstractly).

Grammar (``train.curriculum``, same loud-fail style as
``parse_conv_impl_map`` / the serving tier specs): stages separated by
``;``, each a comma list of ``key=value`` with keys ``num_frames``,
``resolution``, ``batch_size`` (optional — defaults to
``train.batch_size``), and exactly one of ``until_step`` /
``until_epoch`` on every stage but the last (the final stage is
open-ended and runs to the end of training)::

    num_frames=4,resolution=64,until_step=1000;\
    num_frames=8,resolution=112,until_step=3000;\
    num_frames=32,resolution=224

A spec containing no ``=`` is read as a JSON artifact path holding the
stage list (optionally under a ``"curriculum"`` key).  Unknown keys,
non-positive values, a bounded final stage, an unbounded middle stage,
or boundaries that leave a stage unreachable all raise ``ValueError``
naming the stage — never a silent fallback.

Plan semantics (:func:`plan_curriculum`): the plan simulates the epoch
loop exactly — ``until_epoch=E`` ends a stage when the epoch counter
reaches E; ``until_step=S`` ends it when the global optimizer step
reaches S (mid-epoch allowed).  A mid-epoch switch re-arms the loader
with ``skip_batches = ceil(samples_consumed / new_batch)`` so no sample
is trained twice in an epoch (a partial batch of samples may be dropped
at the boundary — the same drop-remainder semantics as the epoch tail).
The flat (no-curriculum) path is the SAME machinery with a single
open-ended stage built from the run config, so the loop has one code
path and the flat math (resume offsets, epoch progress, warmup totals)
is pinned equal to the historical helpers by tests/test_curriculum.py.
"""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass
from typing import Optional

_STAGE_KEYS = ("num_frames", "resolution", "batch_size",
               "until_step", "until_epoch")

#: checkpoint sidecar (train/loop.py writes it next to the Orbax
#: rotation at every save) — Orbax's CheckpointManager carries no
#: metadata channel, and the resume guard needs the writing run's stage
#: shape to refuse a curriculum checkpoint resumed with the schedule
#: silently removed.
STAMP_NAME = "CURRICULUM_STAMP.json"


@dataclass(frozen=True)
class CurriculumStage:
    num_frames: int
    resolution: int
    batch_size: int
    until_step: Optional[int] = None    # stage ends when the global
    #                                     optimizer step reaches this
    until_epoch: Optional[int] = None   # stage ends entering this epoch

    def label(self) -> str:
        return (f"{self.num_frames}f@{self.resolution} "
                f"batch {self.batch_size}")


@dataclass(frozen=True)
class StageSegment:
    """One contiguous run of steps of one stage inside one epoch."""
    stage: int          # index into the plan's stages
    epoch: int
    skip_batches: int   # loader.epoch(epoch, skip_batches=...) offset
    start_step: int     # global optimizer step of the segment's first step
    n_steps: int

    @property
    def end_step(self) -> int:
        return self.start_step + self.n_steps


def parse_curriculum(spec: str, *,
                     default_batch_size: Optional[int] = None) -> list:
    """``train.curriculum`` -> ordered ``CurriculumStage`` list ('' ->
    []).  Inline grammar or a JSON artifact path — see module docstring.
    Every malformed input names its stage and raises; nothing falls back
    silently."""
    if not spec:
        return []
    if "=" in spec:
        raw = []
        for part in spec.split(";"):
            if not part.strip():
                continue
            d: dict = {}
            for item in part.split(","):
                if not item.strip():
                    continue
                if "=" not in item:
                    raise ValueError(
                        f"curriculum stage {len(raw)}: item {item!r} is "
                        "not key=value (keys: "
                        f"{', '.join(_STAGE_KEYS)})")
                k, v = item.split("=", 1)
                d[k.strip()] = v.strip()
            raw.append(d)
    else:
        if not os.path.exists(spec):
            raise ValueError(
                f"train.curriculum={spec!r}: no '=' so it must be a JSON "
                "artifact path, but no such file exists")
        with open(spec) as fh:
            payload = json.load(fh)
        raw = (payload.get("curriculum", payload)
               if isinstance(payload, dict) else payload)
        if not isinstance(raw, list):
            raise ValueError(
                f"curriculum artifact {spec}: expected a JSON list of "
                "stage objects (or {'curriculum': [...]}), got "
                f"{type(raw).__name__}")
    if not raw:
        return []
    stages = []
    for i, d in enumerate(raw):
        if not isinstance(d, dict):
            raise ValueError(f"curriculum stage {i}: expected an object "
                             f"of stage keys, got {type(d).__name__}")
        unknown = sorted(set(d) - set(_STAGE_KEYS))
        if unknown:
            raise ValueError(
                f"curriculum stage {i}: unknown key(s) "
                f"{', '.join(unknown)} (valid: {', '.join(_STAGE_KEYS)})")
        vals = {}
        for k, v in d.items():
            try:
                vals[k] = int(v)
            except (TypeError, ValueError):
                raise ValueError(
                    f"curriculum stage {i}: {k}={v!r} is not an integer")
            if vals[k] <= 0:
                raise ValueError(
                    f"curriculum stage {i}: {k}={vals[k]} must be > 0")
        for req in ("num_frames", "resolution"):
            if req not in vals:
                raise ValueError(
                    f"curriculum stage {i}: missing required key {req!r}")
        if "batch_size" not in vals:
            if default_batch_size is None:
                raise ValueError(
                    f"curriculum stage {i}: no batch_size and no default "
                    "to inherit")
            vals["batch_size"] = int(default_batch_size)
        has_s = "until_step" in vals
        has_e = "until_epoch" in vals
        last = i == len(raw) - 1
        if has_s and has_e:
            raise ValueError(
                f"curriculum stage {i}: sets BOTH until_step and "
                "until_epoch — exactly one bounds a non-final stage")
        if last and (has_s or has_e):
            raise ValueError(
                f"curriculum stage {i}: the final stage must be "
                "open-ended (it runs to the end of training) but sets "
                f"until_{'step' if has_s else 'epoch'}")
        if not last and not (has_s or has_e):
            raise ValueError(
                f"curriculum stage {i}: needs until_step or until_epoch "
                "(only the final stage is open-ended)")
        stages.append(CurriculumStage(**vals))
    return stages


def flat_stages(data_cfg, batch_size: int) -> list:
    """The no-curriculum run as a single open-ended stage — the loop's
    one code path covers both."""
    return [CurriculumStage(num_frames=data_cfg.num_frames,
                            resolution=data_cfg.video_size,
                            batch_size=int(batch_size))]


def stage_data_config(data_cfg, stage: CurriculumStage):
    """Per-stage DataConfig: only the decode shapes change; everything
    else (candidates, words, decode policy) rides the run config."""
    return dataclasses.replace(data_cfg, num_frames=stage.num_frames,
                               video_size=stage.resolution)


def stage_config(cfg, stage: CurriculumStage):
    """Full Config with the data shapes swapped to ``stage``'s — what
    build_source consumes when the loop re-arms the pipeline at a
    boundary."""
    return dataclasses.replace(cfg, data=stage_data_config(cfg.data, stage))


@dataclass
class CurriculumPlan:
    stages: tuple
    segments: tuple     # StageSegment, ordered by start_step
    num_samples: int
    epochs: int
    total_steps: int

    def segments_for_epoch(self, epoch: int) -> list:
        return [s for s in self.segments if s.epoch == epoch]

    def locate(self, step: int):
        """(segment, offset) containing global step ``step`` — the NEXT
        step to run, so a resume from a restored counter lands exactly
        where the saving run stopped.  ``step >= total_steps`` pins to
        the end of the final segment (a finished run resumes to no-op)."""
        for seg in self.segments:
            if seg.start_step <= step < seg.end_step:
                return seg, step - seg.start_step
        if step >= self.total_steps and self.segments:
            last = self.segments[-1]
            return last, last.n_steps
        raise ValueError(f"step {step} outside the plan "
                         f"(total_steps={self.total_steps})")

    def stage_at(self, step: int) -> int:
        return self.locate(step)[0].stage

    def epoch_start_step(self, epoch: int) -> int:
        segs = self.segments_for_epoch(epoch)
        return segs[0].start_step if segs else self.total_steps

    def epoch_end_step(self, epoch: int) -> int:
        segs = self.segments_for_epoch(epoch)
        return segs[-1].end_step if segs else self.total_steps

    def epoch_steps(self, epoch: int) -> int:
        return self.epoch_end_step(epoch) - self.epoch_start_step(epoch)


def plan_curriculum(stages, num_samples: int, epochs: int) -> CurriculumPlan:
    """Simulate the epoch loop over ``stages`` into an exact step-level
    plan.  Raises when a stage can never run (its predecessor's boundary
    lies past the end of training, or boundaries are non-monotone) —
    a schedule that silently never reaches full resolution is the worst
    possible failure mode of a curriculum."""
    stages = tuple(stages)
    if not stages:
        raise ValueError("plan_curriculum needs at least one stage")
    segments = []
    step = 0
    si = 0
    n = len(stages)
    for epoch in range(epochs):
        consumed = 0            # samples this epoch has trained on
        while True:
            # epoch-counter boundaries resolve at epoch entry
            while (si + 1 < n and stages[si].until_epoch is not None
                   and epoch >= stages[si].until_epoch):
                si += 1
            st = stages[si]
            spe = num_samples // st.batch_size
            if spe <= 0:
                raise ValueError(
                    f"curriculum stage {si} ({st.label()}): batch_size "
                    f"exceeds the dataset ({num_samples} samples)")
            bounded = si + 1 < n and st.until_step is not None
            if bounded and st.until_step <= step:
                si += 1         # boundary already passed (non-monotone
                continue        # specs drain here into "unreachable")
            skip = -(-consumed // st.batch_size)    # ceil div
            avail = spe - skip
            if bounded:
                avail = min(avail, st.until_step - step)
            if avail > 0:
                segments.append(StageSegment(si, epoch, skip, step, avail))
                step += avail
                consumed += avail * st.batch_size
            if bounded and step >= st.until_step:
                si += 1         # mid-epoch switch: stay in this epoch
                continue
            break               # epoch exhausted at the current stage
    reached = {seg.stage for seg in segments}
    for i, st in enumerate(stages):
        if i not in reached:
            raise ValueError(
                f"curriculum stage {i} ({st.label()}) is unreachable — "
                f"earlier boundaries consume the whole run ({step} steps "
                f"over {epochs} epoch(s)); lower until_step/until_epoch "
                "or raise optim.epochs")
    return CurriculumPlan(stages=stages, segments=tuple(segments),
                          num_samples=num_samples, epochs=epochs,
                          total_steps=step)


# ---------------------------------------------------------------------
# mem_plan pre-flight: refuse an over-budget stage BEFORE it traces
# ---------------------------------------------------------------------

def hbm_budget_bytes() -> Optional[int]:
    """Per-chip HBM budget the stage pre-flight gates against:
    ``MILNCE_HBM_GIB`` (explicit, wins — also how CPU runs arm the gate)
    else the device's reported ``bytes_limit``.  On the ``tpu`` platform
    a device that does not report one is an error — a pre-flight
    disarmed in silence is no pre-flight; anywhere else ``None`` disarms
    it (the CPU reports no memory stats)."""
    env = os.environ.get("MILNCE_HBM_GIB")
    if env:
        return int(float(env) * 2 ** 30)
    import jax

    dev = jax.local_devices()[0]
    if dev.platform != "tpu":
        return None
    stats = dev.memory_stats()
    if not stats or "bytes_limit" not in stats:
        raise RuntimeError(
            f"{dev.device_kind!r} reports no memory_stats()['bytes_limit'] "
            "— set MILNCE_HBM_GIB to arm the curriculum HBM pre-flight")
    return int(stats["bytes_limit"])


def preflight_stages(step_fn, state, stages, *, num_candidates: int,
                     max_words: int, budget_bytes: int,
                     guard_on: bool = True) -> list:
    """Static-plan every stage's step (analysis/memplan.py, the PR 8
    autotune pre-flight) against ``budget_bytes`` and REFUSE the run if
    any stage's predicted per-chip peak doesn't fit — at startup, with
    the stage and top-3 contributors named, never an OOM mid-run.

    Traces abstractly (``jax.make_jaxpr`` over ShapeDtypeStructs): no
    device bytes move and the jitted step's executable cache stays
    empty, so refusal genuinely happens *before* any stage compiles.
    A planner crash (vs. an over-budget verdict) downgrades to an
    advisory note — the gate must not turn an analyzable-but-odd config
    into a false refusal.  Returns the per-stage verdict strings for
    the run log."""
    import jax
    import jax.numpy as jnp

    from milnce_tpu.analysis import memplan
    from milnce_tpu.train.step import STATE_DONATION_ARGNUMS

    del guard_on    # signature symmetry with the loop; the plan traces
    #                 whatever step_fn the run built (guarded or not)
    notes = []
    for i, st in enumerate(stages):
        b = st.batch_size
        args = (state,
                jax.ShapeDtypeStruct(
                    (b, st.num_frames, st.resolution, st.resolution, 3),
                    jnp.uint8),
                jax.ShapeDtypeStruct((b * num_candidates, max_words),
                                     jnp.int32),
                jax.ShapeDtypeStruct((b,), jnp.float32))
        entry = f"curriculum stage {i} ({st.label()})"
        try:
            plan = memplan.plan_fn(
                step_fn, args, argnames=("state", "video", "text", "start"),
                donate_argnums=STATE_DONATION_ARGNUMS, entry=entry)
        except Exception as exc:        # planner limitation, not verdict
            notes.append(f"{entry}: pre-flight planner failed "
                         f"({type(exc).__name__}: {exc}) — advisory only")
            continue
        fits, msg = memplan.budget_verdict(plan, budget_bytes / 2 ** 30)
        notes.append(msg)
        if not fits:
            raise ValueError(
                f"curriculum pre-flight refused {entry}: {msg} — shrink "
                "the stage's batch/resolution, enable remat/grad_accum, "
                "or raise the budget (MILNCE_HBM_GIB)")
    return notes


# ---------------------------------------------------------------------
# checkpoint stage stamp: the resume-compatibility guard's source of
# truth (satellite 3 — a curriculum checkpoint resumed with the
# schedule removed must fail LOUDLY, naming shapes, not silently train
# at full res)
# ---------------------------------------------------------------------

def write_stage_stamp(ckpt_dir: str, *, spec: str, stage_index: int,
                      stage: CurriculumStage, step: int) -> None:
    """Atomic sidecar write next to the Orbax rotation (process 0 only —
    the caller gates).  Overwritten at every save: the stamp describes
    the LATEST saved state, which is exactly what restore_latest hands
    back."""
    payload = {
        "schema": "milnce.curriculum/v1",
        "curriculum": spec,
        "stage": int(stage_index),
        "num_frames": int(stage.num_frames),
        "resolution": int(stage.resolution),
        "batch_size": int(stage.batch_size),
        "step": int(step),
    }
    path = os.path.join(ckpt_dir, STAMP_NAME)
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(payload, fh, indent=1)
    os.replace(tmp, path)


def read_stage_stamp(ckpt_dir: str) -> Optional[dict]:
    path = os.path.join(ckpt_dir, STAMP_NAME)
    if not os.path.exists(path):
        return None         # pre-curriculum checkpoint: no guard to run
    with open(path) as fh:
        return json.load(fh)


def check_resume_compatible(stamp: Optional[dict], *, curriculum_spec: str,
                            flat_frames: int, flat_resolution: int,
                            flat_batch: int) -> None:
    """Refuse resuming a curriculum checkpoint with ``train.curriculum``
    removed.  The TrainState is shape-invariant across stages, so
    NOTHING else would fail — the run would silently continue at the
    flat config's full shape with the schedule's intent discarded."""
    if not stamp or not stamp.get("curriculum"):
        return      # flat checkpoint (or pre-curriculum): any config ok
    if curriculum_spec:
        return      # schedule present; the plan's locate() places us
    saved = (f"{stamp.get('num_frames')}f@{stamp.get('resolution')} "
             f"batch {stamp.get('batch_size')}")
    flat = f"{flat_frames}f@{flat_resolution} batch {flat_batch}"
    raise ValueError(
        "checkpoint was written by a curriculum run (stage "
        f"{stamp.get('stage')}: {saved}, schedule "
        f"{stamp.get('curriculum')!r}, step {stamp.get('step')}) but "
        "train.curriculum is unset — resuming would silently train at "
        f"the flat shape {flat} instead of the schedule's; restore with "
        "the original train.curriculum (or a deliberate replacement)")
