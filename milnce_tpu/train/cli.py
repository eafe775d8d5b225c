"""Training entry point: ``python -m milnce_tpu.train.cli --preset small``.

Replaces all three reference launchers (main_distributed.py, train.py,
train_small.py — the latter two being near-duplicate clones, one of them
import-broken, SURVEY.md §2.4) with one CLI over the typed config.

Exit status: 0 on completion; ``DRAINED_EXIT_CODE`` (75, EX_TEMPFAIL)
when the run drained on a preemption signal — the checkpoint + stamps
are already on disk through the atomic tmp+rename discipline, and the
orchestrator's contract is to rerun with ``--train.resume true`` (on
any mesh shape whose batches divide; MIGRATING.md "Checkpoint
resharding")."""

from __future__ import annotations

from milnce_tpu.config import parse_cli
from milnce_tpu.elastic import DRAINED_EXIT_CODE
from milnce_tpu.train.loop import run_training
from milnce_tpu.utils.compile_cache import configure_compile_cache


def main(argv=None):
    cfg = parse_cli(argv, description="milnce-tpu trainer")
    configure_compile_cache()
    result = run_training(cfg)
    if result.drained:
        print(f"drained: {result.steps} steps, final loss "
              f"{result.last_loss:.4f} — checkpoint saved, resume with "
              f"--train.resume true (exit {DRAINED_EXIT_CODE})")
        raise SystemExit(DRAINED_EXIT_CODE)
    print(f"done: {result.steps} steps, final loss {result.last_loss:.4f}")


if __name__ == "__main__":
    main()
