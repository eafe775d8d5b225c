"""benchmarks/flops.py and peaks.py are copies of the arithmetic in
milnce_tpu/utils/roofline.py: today they agree.  The index scan's work
and the bytes/s peak are the benchmark's own."""

import json
import os

import pytest

from benchmarks import flops, harness, peaks
from benchmarks.tests.conftest import ROOT


@pytest.mark.parametrize("config", ["s3dg-milnce-16f224",
                                    "s3dg-milnce-32f224"])
def test_flops_agree_with_the_programs_roofline_arithmetic(config):
    from milnce_tpu.utils import roofline

    cfg = harness.load_json(os.path.join(ROOT, "benchmarks", "configs",
                                         f"{config}.json"))
    m, d = cfg["model"], cfg["data"]
    batch = cfg["train"]["batch_per_chip"]
    mine = flops.train_step_flops(
        batch, d["num_frames"], d["video_size"], d["num_candidates"],
        d["max_words"], m["inception_blocks"], m["embedding_dim"],
        m["word_embedding_dim"], m["text_hidden_dim"])
    theirs = roofline.train_step_flops(
        batch, d["num_frames"], d["video_size"], d["num_candidates"],
        d["max_words"], space_to_depth=m["space_to_depth"],
        inception_blocks=m["inception_blocks"],
        embedding_dim=m["embedding_dim"],
        word_dim=m["word_embedding_dim"], hidden=m["text_hidden_dim"])
    assert mine == pytest.approx(theirs, rel=1e-9)
    assert flops.video_fwd_flops(2, d["num_frames"], d["video_size"]) \
        == pytest.approx(roofline.video_fwd_flops(
            2, d["num_frames"], d["video_size"]), rel=1e-9)
    assert flops.text_fwd_flops(10, d["max_words"]) == pytest.approx(
        roofline.text_fwd_flops(10, d["max_words"]), rel=1e-9)


def test_peaks_agree_and_an_unknown_kind_is_an_error():
    from milnce_tpu.utils import roofline

    assert roofline.device_peak_flops("TPU v5 lite") \
        == peaks.PEAKS["TPU v5 lite"]["flops_per_s"]
    assert peaks.peaks_for("TPU v5 lite")["bytes_per_s"] == 819e9
    with pytest.raises(ValueError):
        peaks.peaks_for("TPU v9 imaginary")


def test_index_scan_is_bound_by_bytes_at_the_cells_sizes():
    work = flops.index_scan_work(3_000_000, 512, 16)
    assert work["bytes"] == 3_000_000 * 512 * 4
    assert work["flops"] == 2.0 * 16 * 3_000_000 * 512
    least, bound = flops.least_time_s(work, peaks.PEAKS["TPU v5 lite"])
    assert bound == "bytes" and least == pytest.approx(6.144e9 / 819e9)
    many = flops.index_scan_work(3_000_000, 512, 4096)
    assert flops.least_time_s(many, peaks.PEAKS["TPU v5 lite"])[1] == "flops"
