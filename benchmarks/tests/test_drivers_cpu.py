"""The drivers end to end at the tiny preset on the CPU: what run.py does
after its look for a chip.  One train cell, a ``chips: 4`` train cell on
four virtual devices, the query cell with a few thousand rows; then the
timed path broken underneath, once for each fault a cell can have, and
``correct`` must come out false."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness
from benchmarks.tests.conftest import run_cell

TRAIN_LAYERS = {"data_wait_share.train", "step_dispatch_ms.train",
                "step_device_ms.train", "train_step_mfu",
                "device_idle_share.train"}


def test_train_cell_runs_and_is_correct(bench, bench_dir, tmp_path):
    result, out = run_cell(bench, bench_dir, "tiny-train", tmp_path)
    assert result["correct"], result["compared"]
    assert set(result["metrics"]) == {"train_clips_per_s_per_chip",
                                      "setup_s"}
    assert result["metrics"]["train_clips_per_s_per_chip"]["value"] > 0
    assert result["attempted"] >= 6 and result["failed"] == 0
    # float32 on both sides here: the reference is the program's equal
    assert out["numbers"]["loss_gap"] < 1e-4
    assert out["numbers"]["grad_norm_gap"] < 1e-3
    assert out["numbers"]["step_norm_gap"] < 1e-3
    # the rate is whole steps of the window over the window
    rec = out["record"]
    assert rec.extra["steps"] % rec.extra["n_display"] == 0
    assert result["metrics"]["train_clips_per_s_per_chip"]["value"] == \
        pytest.approx(rec.extra["steps"] * rec.extra["batch"]
                      / rec.window_s)


def test_four_chip_train_cell_traced(bench, bench_dir, tmp_path):
    result, out = run_cell(bench, bench_dir, "tiny-train-dp4", tmp_path,
                           trace=True)
    assert result["correct"], result["compared"]
    assert result["device"]["count"] == 4
    assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"]
    assert TRAIN_LAYERS <= set(result["metrics"])
    assert 0 < result["metrics"]["train_step_mfu"]["value"] < 100
    assert len(result["breakdown"]["device_ops"]) <= 10
    assert out["record"].extra["batch"] == 32       # 8 a chip, 4 chips


def _state_unchanged(step):
    def broken(state, video, text, start):
        keep = jax.tree_util.tree_map(jnp.copy, state)
        out = step(state, video, text, start)
        return (keep,) + tuple(out[1:])
    return broken


def _half_batch(step):
    def broken(state, video, text, start):
        b = video.shape[0] // 2
        k = text.shape[0] // video.shape[0]
        with jax.transfer_guard("allow"):       # the slices' bounds
            video, text, start = video[:b], text[:b * k], start[:b]
        return step(state, video, text, start)
    return broken


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch_left_out"])
def test_train_faults_come_out_not_correct(bench, bench_dir, tmp_path,
                                           fault):
    result, out = run_cell(bench, bench_dir, "tiny-train", tmp_path,
                           fault=fault)
    assert not result["correct"], out["numbers"]


def test_query_cell_runs_traced_and_is_correct(bench, bench_dir, tmp_path):
    result, out = run_cell(bench, bench_dir, "tiny-query", tmp_path,
                           trace=True)
    assert result["correct"], result["compared"]
    assert {"flush_rows_mean.serve", "flush_ms_p50.serve", "query_mfu",
            "device_idle_share.serve"} <= set(result["metrics"])
    assert result["end_to_end"]["queries_per_s"]["value"] > 0
    assert result["end_to_end"]["query_p95_ms"]["value"] > 0
    assert result["failed"] == 0 and result["attempted"] > 20
    assert out["notes"]["recompiles"] == {"engine": 0, "index": 0}
    assert out["numbers"]["score_err"] < 1e-4


def _answer_altered(service):
    real = service.index.topk

    def topk(queries):
        scores, idx = real(queries)
        idx = np.array(idx)
        idx[:, 0] = (idx[:, 0] + 1) % service.index.size
        return scores, idx

    service.index.topk = topk


def _query_token_altered(service):
    real = service.embed_text_ids

    def embed(token_ids, *args, **kwargs):
        rows = np.array(token_ids)
        rows[:, 0] = rows[:, 0] % 100 + 1
        return real(rows, *args, **kwargs)

    service.embed_text_ids = embed


@pytest.mark.parametrize("fault", [_answer_altered, _query_token_altered],
                         ids=["answer_altered", "token_altered"])
def test_query_faults_come_out_not_correct(bench, bench_dir, tmp_path,
                                           fault):
    result, out = run_cell(bench, bench_dir, "tiny-query", tmp_path,
                           fault=fault)
    assert not result["correct"], out["numbers"]


def test_controls_come_out_not_correct(bench, bench_dir, tmp_path):
    """The control is the reference put in the program's place, computed
    one precision step below what the configuration states (float8 for
    bfloat16): held to the cell's limits it is not correct.  On the chip
    it is read at the cells' own sizes (PERF.md); here at the rehearsal
    cells' sizes and limits."""
    from benchmarks import compare, traffic_gen
    from benchmarks.reference import s3dg_milnce as reference

    cell = harness.load_cell(bench, "tiny-train", bench_dir=bench_dir)
    train = harness.load_driver("train", bench_dir)
    cfg = cell.config
    rng = np.random.RandomState(5)
    batch = cfg["train"]["batch_per_chip"]
    batches = [(rng.randint(0, 255, (batch, cfg["data"]["num_frames"],
                                     cfg["data"]["video_size"],
                                     cfg["data"]["video_size"], 3),
                            np.uint8),
                rng.randint(0, cfg["model"]["vocab_size"],
                            (batch * cfg["data"]["num_candidates"],
                             cfg["data"]["max_words"])).astype(np.int32))
               for _ in range(3)]
    ref = train.follow_reference(cell, 5, batches)
    ctl = train.follow_reference(cell, 5, batches, precision="float8")
    numbers = compare.training_numbers(ctl, ref, frozen=reference.FROZEN)
    assert not harness.judge({k: {"value": numbers[k], "limit": v}
                              for k, v in cell.limits.items()}), numbers
    # the look of PERF.md (bfloat16 kept between layers and in the loss):
    # a reading, never a verdict
    look = train.follow_reference(cell, 5, batches,
                                  precision="bfloat16_stored")
    assert 0 < compare.training_numbers(
        look, ref, frozen=reference.FROZEN)["loss_gap"] < 1
    half = train.follow_reference(cell, 5, batches, keep_rows=batch // 2)
    numbers = compare.training_numbers(half, ref, frozen=reference.FROZEN)
    assert not harness.judge({k: {"value": numbers[k], "limit": v}
                              for k, v in cell.limits.items()}), numbers

    cell = harness.load_cell(bench, "tiny-query", bench_dir=bench_dir)
    serve = harness.load_driver("serve", bench_dir)
    pool = traffic_gen.query_pool(5, cell.traffic,
                                  cfg["model"]["vocab_size"],
                                  cfg["data"]["max_words"])[:16]
    empty = np.zeros((16, 5), np.int64)
    low = serve.reference_numbers(cell, 5, pool, empty, empty, "float8")
    held = serve.reference_numbers(cell, 5, pool, low["top_idx"],
                                   low["top_scores"])["numbers"]
    assert not harness.judge({k: {"value": held[k], "limit": v}
                              for k, v in cell.limits.items()}), held
