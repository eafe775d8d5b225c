"""Measurement-script machinery: the xla_flag_probe launcher/grid, the
stage_probe autotuner, and bench's impl-map/cliff plumbing.

The round-5 flag probe shipped a table where every non-baseline row
died ``rc=1, no record`` (XLA_FLAGS_PROBE.md) — an instrument that
errors on every interesting row and ships anyway settles nothing, so
its pure logic is pinned here.
"""

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import bench  # noqa: E402

sys.path.insert(0, os.path.join(_REPO, "scripts"))
import xla_flag_probe  # noqa: E402


class TestSplitFlags:
    """--xla_tpu_* knobs are libtpu flags; the CLIENT's XLA_FLAGS parser
    hard-aborts on them (observed: rc=-6 'Unknown flags in XLA_FLAGS'
    — the round-5 row killer), so the router must keep the two apart."""

    def test_tpu_flags_routed_to_libtpu(self):
        xla, libtpu = xla_flag_probe.split_flags(
            "--xla_tpu_scoped_vmem_limit_kib=65536")
        assert xla == ""
        assert libtpu == "--xla_tpu_scoped_vmem_limit_kib=65536"

    def test_generic_flags_stay_in_xla_flags(self):
        xla, libtpu = xla_flag_probe.split_flags(
            "--xla_force_host_platform_device_count=2")
        assert xla == "--xla_force_host_platform_device_count=2"
        assert libtpu == ""

    def test_mixed_set_splits(self):
        xla, libtpu = xla_flag_probe.split_flags(
            "--xla_tpu_enable_latency_hiding_scheduler=true "
            "--xla_dump_to=/tmp/d")
        assert xla == "--xla_dump_to=/tmp/d"
        assert libtpu == "--xla_tpu_enable_latency_hiding_scheduler=true"

    def test_every_tpu_candidate_routes_clear_of_xla_flags(self):
        for _, flags in xla_flag_probe.CANDIDATES:
            xla, _ = xla_flag_probe.split_flags(flags)
            assert "--xla_tpu_" not in xla, (
                f"candidate {flags!r} would abort the client flag parser")


class TestBuildGrid:
    def test_stem_map_is_crossed_with_flags_on_tpu(self):
        grid = xla_flag_probe.build_grid("conv1=im2col")
        tuned = [(name, flags, kw) for name, flags, kw in grid
                 if kw.get("conv_impl_map")]
        assert len(tuned) >= 3           # bare + vmem + lhs crossings
        assert any(flags for _, flags, _ in tuned)
        assert all(kw["conv_impl_map"] == "conv1=im2col"
                   for _, _, kw in tuned)

    def test_no_map_no_tuned_rows(self):
        grid = xla_flag_probe.build_grid("")
        assert all(not kw for _, _, kw in grid)


class TestResolveImplMap:
    @staticmethod
    def _write_artifact(tmp_path, **kw):
        art = tmp_path / "build" / "impl_map.json"
        art.parent.mkdir(exist_ok=True)
        payload = {"impl_map": {"conv1": "im2col"}}
        payload.update(kw)
        art.write_text(json.dumps(payload))
        return art

    def test_inline_spec_passes_through(self):
        assert xla_flag_probe.resolve_impl_map("conv1=im2col") == "conv1=im2col"

    def test_missing_default_artifact_means_no_map(self, monkeypatch, tmp_path):
        monkeypatch.setattr(xla_flag_probe, "_REPO", str(tmp_path))
        assert xla_flag_probe.resolve_impl_map("") == ""

    def test_default_artifact_picked_up_when_trustworthy(self, monkeypatch,
                                                         tmp_path):
        monkeypatch.setattr(xla_flag_probe, "_REPO", str(tmp_path))
        art = self._write_artifact(tmp_path, complete=True,
                                   device="TPU v5 lite")
        assert xla_flag_probe.resolve_impl_map("") == str(art)

    def test_incomplete_default_artifact_rejected(self, monkeypatch,
                                                  tmp_path):
        # a partial autotune must not silently steer the grid
        monkeypatch.setattr(xla_flag_probe, "_REPO", str(tmp_path))
        self._write_artifact(tmp_path, complete=False, device="TPU v5 lite")
        assert xla_flag_probe.resolve_impl_map("") == ""

    def test_cpu_tuned_default_rejected(self, monkeypatch, tmp_path):
        # a stage_probe sanity run on the CPU writes the same default
        # path; a TPU probe crossing its grid with CPU-chosen winners
        # would publish wrong rows
        monkeypatch.setattr(xla_flag_probe, "_REPO", str(tmp_path))
        self._write_artifact(tmp_path, complete=True, device="cpu")
        assert xla_flag_probe.resolve_impl_map("") == ""

    def test_explicit_path_obeyed_as_given(self, monkeypatch, tmp_path):
        monkeypatch.setattr(xla_flag_probe, "_REPO", str(tmp_path))
        got = xla_flag_probe.resolve_impl_map("build/other.json")
        assert got == str(tmp_path / "build" / "other.json")


def test_autotune_stage_filter_typo_fails_fast():
    """--stages conv_1 (typo) must raise before any backend work, not
    autotune zero stages and ship an empty map marked complete."""
    import stage_probe

    with pytest.raises(ValueError, match="unknown conv stage"):
        stage_probe._validate_stage_filter("conv_1")
    assert stage_probe._validate_stage_filter("conv1,mixed_3b") == {
        "conv1", "mixed_3b"}
    assert stage_probe._validate_stage_filter("") == set()


def test_run_config_no_record_carries_stderr(monkeypatch):
    """A config child that dies before emitting its record must raise
    with the child's stderr tail — not the bare 'no record' the round-5
    probe table was full of."""

    class FakeProc:
        returncode = -6

        def communicate(self, timeout=None):
            return b"", b"F0803 xla: Unknown flags in XLA_FLAGS: --boom\n"

    monkeypatch.setattr(bench.subprocess, "Popen",
                        lambda *a, **kw: FakeProc())
    with pytest.raises(RuntimeError) as exc_info:
        bench._run_config(timeout_s=5, dtype="float32",
                          batch=1, frames=2, size=8, words=4, k=2,
                          remat=False, inner=1, s2d=False,
                          conv_impl="native", flops_hint=None)
    msg = str(exc_info.value)
    assert "rc=-6" in msg
    assert "Unknown flags in XLA_FLAGS" in msg


def test_bench_flags_batch_cliff(monkeypatch):
    """A row regressing >10% clips/s vs a SMALLER batch (the observed
    281-vs-393 drop at batch 192) must be flagged as a cliff on the
    result row, not silently averaged into the table."""
    base = {"platform": "tpu", "device_kind": "TPU v5 lite", "n_chips": 1,
            "dtype": "bfloat16", "remat": False, "s2d": False,
            "conv_impl": "native", "impl_map": "", "loss": "milnce",
            "grad_accum": 1, "inner": 4, "flops_per_step": None,
            "flops_source": None, "flops_per_sec": None}
    ladder = {64: 330.0, 128: 393.0, 192: 281.0}   # BENCH_NOTES r5 shape

    def fake_run_config(timeout_s=None, **kw):
        b = kw["batch"]
        if b not in ladder:
            raise RuntimeError(f"config timeout>{timeout_s}s: {kw}")
        return dict(base, batch=b, step_ms=1.0,
                    clips_per_sec_per_chip=ladder[b])

    notes = {}
    monkeypatch.setattr(bench, "_run_config", fake_run_config)
    monkeypatch.setattr(bench, "_emit", lambda rec: None)
    monkeypatch.setattr(bench, "_write_notes",
                        lambda results, *a, **k: notes.setdefault(
                            "results", list(results)))

    bench.run_bench()
    by_batch = {r["batch"]: r for r in notes["results"]}
    assert "cliff_vs_smaller_batch" not in by_batch[128]
    assert by_batch[192]["cliff_vs_smaller_batch"] == pytest.approx(
        1 - 281.0 / 393.0, abs=1e-3)


def test_write_notes_marks_cliff_and_preserves_hand_notes(tmp_path,
                                                          monkeypatch):
    """BENCH_NOTES.md must carry the cliff marker on flagged rows and
    keep the '## Hand notes' section across auto-rewrites (the r5
    rewrite silently dropped the hand-written methodology caveats)."""
    monkeypatch.setattr(bench, "_REPO", str(tmp_path))
    notes = tmp_path / "BENCH_NOTES.md"
    notes.write_text("# BENCH notes (auto-written by bench.py)\n\n"
                     "- device: TPU v5 lite x1 (platform=tpu)\n\n"
                     "## Hand notes\n\nanchor predates differenced timing.\n")
    rows = [{"platform": "tpu", "device_kind": "TPU v5 lite", "n_chips": 1,
             "dtype": "bfloat16", "batch": 128, "remat": False,
             "step_ms": 325.0, "clips_per_sec_per_chip": 393.0},
            {"dtype": "bfloat16", "batch": 192, "remat": False,
             "step_ms": 682.0, "clips_per_sec_per_chip": 281.0,
             "cliff_vs_smaller_batch": 0.285, "impl_map": "conv1=im2col"}]
    bench._write_notes(rows, rows[0])
    text = notes.read_text()
    assert "cliff: -28% vs smaller batch" in text
    assert "## Hand notes" in text
    assert "anchor predates differenced timing." in text
    assert "conv1=im2col" in text


def test_flag_probe_without_a_tpu_writes_no_row(tmp_path):
    """The whole probe as a real subprocess where JAX finds no TPU: the
    first measurement child says so, the probe exits nonzero, and no row
    — measured or error — is printed under a candidate's name."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "scripts", "xla_flag_probe.py"),
         "--timeout", "300"],
        env=env, cwd=_REPO, capture_output=True, timeout=600)
    assert proc.returncode != 0
    rows = [json.loads(line) for line in proc.stdout.decode().splitlines()
            if line.strip().startswith("{")]
    assert rows and all("name" not in r for r in rows), rows
    assert "no TPU" in rows[-1]["error"]


@pytest.mark.slow
def test_stage_probe_autotune_cpu_smoke(tmp_path):
    """--autotune end-to-end on CPU: emits the per-stage impl-map
    artifact, and the artifact round-trips into build_model (the exact
    path bench.py / train cli consume)."""
    out = tmp_path / "impl_map.json"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "scripts", "stage_probe.py"),
         "--autotune", "--batch", "2", "--frames", "4", "--size", "32",
         "--stages", "conv1", "--iters", "2",
         "--impls", "native,im2col", "--out", str(out)],
        env=env, cwd=_REPO, capture_output=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr.decode()[-500:]
    art = json.loads(out.read_text())
    assert art["generator"].startswith("scripts/stage_probe.py")
    assert art["complete"] is True
    assert set(art["impl_map"]) <= {"conv1"}
    timings = art["stage_ms"]["conv1"]
    assert set(timings) == {"native", "im2col"}
    for impl in timings:
        assert timings[impl]["fwd"] > 0 and timings[impl]["fwdbwd"] > 0

    from milnce_tpu.config import small_preset
    from milnce_tpu.models.build import build_model

    cfg = small_preset().model
    cfg.conv_impl_map = str(out)
    model = build_model(cfg)             # consumes without error
    assert dict(model.conv_impl_map or ()) == art["impl_map"]
