"""Config presets + CLI overrides (replaces args.py/args_small.py)."""

import json

import pytest

from milnce_tpu.config import (CONV_STAGES, parse_cli, parse_conv_impl_map,
                               small_preset, tiny_preset)


def test_full_defaults_match_reference_args():
    """Every behavioral default of /root/reference/args.py:3-52, pinned
    (path-like defaults excluded — environment leaks, SURVEY §2.4)."""
    cfg = parse_cli([])
    expected = {
        "optim.name": "adam",               # args.py:12
        "model.weight_init": "uniform",     # args.py:13
        "data.num_reader_threads": 20,      # args.py:14
        "model.embedding_dim": 512,         # args.py:15 --num_class
        "data.num_candidates": 5,           # args.py:16
        "train.batch_size": 128,            # args.py:17
        "train.num_windows_test": 4,        # args.py:18
        "train.batch_size_val": 32,         # args.py:19
        "optim.momentum": 0.9,              # args.py:20 (the typo'd --momemtum)
        "train.n_display": 400,             # args.py:21
        "data.num_frames": 32,              # args.py:22
        "data.video_size": 224,             # args.py:23
        "data.crop_only": True,             # args.py:24
        "data.center_crop": False,          # args.py:25
        "data.random_flip": True,           # args.py:26
        "train.verbose": True,              # args.py:27
        "optim.warmup_steps": 50_000,       # args.py:28
        "data.min_time": 5.0,               # args.py:29
        "data.fps": 10,                     # args.py:32
        "optim.epochs": 300,                # args.py:34
        "optim.lr": 1e-3,                   # args.py:36
        "train.resume": False,              # args.py:38
        "train.evaluate": False,            # args.py:39
        "train.seed": 1,                    # args.py:47
    }
    for key, want in expected.items():
        section, field = key.split(".")
        got = getattr(getattr(cfg, section), field)
        assert got == want, f"{key}: {got!r} != reference default {want!r}"


def test_small_preset_deltas():
    """Exactly the args_small.py deltas (diff vs args.py); everything
    else — input shapes included — stays at the full-run defaults."""
    cfg = small_preset()
    assert cfg.train.batch_size == 12          # args_small.py:17
    assert cfg.train.n_display == 100          # args_small.py:21
    assert cfg.optim.warmup_steps == 1000      # args_small.py:28
    assert cfg.optim.epochs == 100             # args_small.py:34
    assert cfg.data.num_frames == 32           # unchanged by args_small
    assert cfg.data.num_candidates == 5        # unchanged by args_small


def test_cli_overrides():
    cfg = parse_cli(["--preset", "small", "--optim.lr", "0.01",
                     "--train.batch_size", "64", "--data.random_flip", "false"])
    assert cfg.optim.lr == 0.01
    assert cfg.train.batch_size == 64
    assert cfg.data.random_flip is False
    assert cfg.optim.warmup_steps == 1000  # preserved from preset


def test_optional_int_fields_parse_as_int():
    cfg = parse_cli(["--parallel.num_processes", "4",
                     "--parallel.process_id", "0",
                     "--parallel.coordinator_address", "10.0.0.1:8476"])
    assert cfg.parallel.num_processes == 4 and isinstance(cfg.parallel.num_processes, int)
    assert cfg.parallel.process_id == 0 and isinstance(cfg.parallel.process_id, int)
    assert cfg.parallel.coordinator_address == "10.0.0.1:8476"


def test_tiny_preset_is_hermetic():
    cfg = tiny_preset()
    assert cfg.data.synthetic
    assert cfg.train.batch_size <= 8


class TestConvImplMap:
    """ModelConfig.conv_impl_map parsing: inline specs, autotune
    artifacts, and the typo-fails-at-config-time contract."""

    def test_empty_spec_is_empty_map(self):
        assert parse_conv_impl_map("") == {}

    def test_inline_spec(self):
        got = parse_conv_impl_map("conv1=im2col,mixed_3b=fold2d")
        assert got == {"conv1": "im2col", "mixed_3b": "fold2d"}

    def test_artifact_path(self, tmp_path):
        # the shape scripts/stage_probe.py --autotune writes
        art = {"generator": "scripts/stage_probe.py --autotune",
               "device": "TPU v5 lite",
               "impl_map": {"conv1": "im2col"},
               "stage_ms": {"conv1": {"native": {"fwdbwd": 266.0},
                                      "im2col": {"fwdbwd": 9.0}}}}
        path = tmp_path / "impl_map.json"
        path.write_text(json.dumps(art))
        assert parse_conv_impl_map(str(path)) == {"conv1": "im2col"}

    def test_raw_json_map_also_accepted(self, tmp_path):
        path = tmp_path / "map.json"
        path.write_text(json.dumps({"conv_2c": "fold2d"}))
        assert parse_conv_impl_map(str(path)) == {"conv_2c": "fold2d"}

    def test_unknown_stage_rejected(self):
        with pytest.raises(ValueError, match="unknown stage"):
            parse_conv_impl_map("conv9000=native")

    def test_unknown_impl_rejected(self):
        with pytest.raises(ValueError, match="unknown impl"):
            parse_conv_impl_map("conv1=winograd")

    def test_cli_override_reaches_model_config(self):
        cfg = parse_cli(["--model.conv_impl_map", "conv1=im2col"])
        assert cfg.model.conv_impl_map == "conv1=im2col"

    def test_stage_names_cover_the_probe_walk(self):
        # the map grain must match what scripts/stage_probe.py measures
        assert CONV_STAGES[:3] == ("conv1", "conv_2b", "conv_2c")
        assert len([s for s in CONV_STAGES if s.startswith("mixed_")]) == 9

    def test_artifact_round_trip_through_build_model(self, tmp_path):
        """config -> model -> autotune artifact -> reload: the emitted
        artifact drives build_model and the per-stage resolution."""
        from milnce_tpu.models.build import build_model

        art = {"generator": "scripts/stage_probe.py --autotune",
               "impl_map": {"conv1": "im2col", "mixed_5c": "fold2d"}}
        path = tmp_path / "impl_map.json"
        path.write_text(json.dumps(art))
        cfg = small_preset().model
        cfg.conv_impl_map = str(path)
        model = build_model(cfg)
        assert model.conv_impl_map == (("conv1", "im2col"),
                                       ("mixed_5c", "fold2d"))


@pytest.mark.parametrize("flag", [["--serve.max_delay_ms", "5"],
                                  ["--serve.continuous_batching", "true"]])
def test_the_batching_window_options_are_gone(flag, capsys):
    """ISSUE 30: one batching policy (take what waits when the device is
    free), so the two options that chose another are refused like any
    unknown field and a stale launch script fails loudly."""
    with pytest.raises(SystemExit) as exc:
        parse_cli(flag)
    assert exc.value.code == 2
    assert f"unrecognized arguments: {' '.join(flag)}" in capsys.readouterr().err
    assert parse_cli(["--serve.max_batch", "8"]).serve.max_batch == 8
