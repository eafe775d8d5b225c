"""bench.py machinery: record schema, the no-TPU refusal, the
JAX-free parent, the compile-cache rule, and ``_bench_config`` driven
in-process at a tiny size (slow tier).

Nothing bench.py prints may come from a CPU run under the device
metric's name, so the refusal is exercised as a real subprocess.
"""

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import bench  # noqa: E402


class TestLastJson:
    def test_picks_last_record(self):
        raw = (b'{"metric": "m", "value": 1.0, "unit": "u"}\n'
               b'{"metric": "m", "value": 2.0, "unit": "u"}\n')
        assert bench._last_json(raw)["value"] == 2.0

    def test_skips_non_record_json(self):
        # stray JSON-shaped log lines after the record must not win
        raw = (b'{"metric": "m", "value": 3.0}\n'
               b'{"event": "shutdown"}\n'
               b'not json at all\n')
        assert bench._last_json(raw)["value"] == 3.0

    def test_unparsable_tail_then_record(self):
        raw = b'garbage\n{"metric": "m", "value": 4.0}\n{"broken\n'
        assert bench._last_json(raw)["value"] == 4.0

    def test_no_record(self):
        assert bench._last_json(b"") is None
        assert bench._last_json(b"warning: something\n") is None


class TestMakeRecord:
    BEST = {"platform": "tpu", "device_kind": "TPU v5 lite", "n_chips": 1,
            "dtype": "bfloat16", "batch": 256, "remat": False, "s2d": False,
            "clips_per_sec_per_chip": 100.0, "mfu": 0.05}

    def test_schema_and_anchor(self):
        rec = bench._make_record(self.BEST, 16, 224)
        # ISSUE 5: the record is a milnce.obs/v1 document (diffable by
        # scripts/obs_report.py alongside serve benches)
        from milnce_tpu.obs.export import SNAPSHOT_SCHEMA
        assert rec["schema"] == SNAPSHOT_SCHEMA
        assert rec["kind"] == "train_bench"
        assert rec["unit"] == "clips/sec/chip"
        assert rec["value"] == 100.0
        # every record names the device as JAX reported it
        assert rec["platform"] == "tpu"
        assert rec["device_kind"] == "TPU v5 lite" and rec["n_chips"] == 1
        assert rec["mfu"] == 0.05
        assert rec["vs_baseline"] == round(100.0 / bench.BASELINE_THROUGHPUT, 3)
        assert "16f@224" in rec["metric"] and "bfloat16" in rec["metric"]

    def test_a_cpu_row_never_becomes_a_record(self):
        # a CPU number is never written under the device metric's name
        cpu_row = dict(self.BEST, platform="cpu", device_kind="cpu")
        with pytest.raises(ValueError, match="did not run on a TPU"):
            bench._make_record(cpu_row, 4, 64)

    def test_s2d_flagged_in_metric(self):
        best = dict(self.BEST, s2d=True)
        rec = bench._make_record(best, 16, 224)
        assert "s2d stem" in rec["metric"]

    def test_predicted_peak_rides_into_the_obs_record(self):
        # ISSUE 8: the static HBM plan is a gate metric — obs_report
        # flags memory drift only if the record carries it (and a row
        # whose planner errored ships WITHOUT the field, never with 0)
        best = dict(self.BEST, predicted_peak_bytes_per_chip=123456789)
        rec = bench._make_record(best, 16, 224)
        assert rec["predicted_peak_bytes_per_chip"] == 123456789
        rec = bench._make_record(self.BEST, 16, 224)
        assert "predicted_peak_bytes_per_chip" not in rec

    def test_dtype_census_hash_rides_into_the_obs_record(self):
        # Pass 5: the precision fingerprint is how obs_report tells a
        # dtype change from a speedup — best-effort, so an errored
        # audit ships without the field, never with a fake hash
        best = dict(self.BEST, dtype_census_hash="abc123def456")
        rec = bench._make_record(best, 16, 224)
        assert rec["dtype_census_hash"] == "abc123def456"
        rec = bench._make_record(self.BEST, 16, 224)
        assert "dtype_census_hash" not in rec


def _run_bench_script(extra_env):
    env = dict(os.environ)
    env.update(extra_env)
    return subprocess.run([sys.executable, os.path.join(_REPO, "bench.py")],
                          env=env, cwd=_REPO, capture_output=True,
                          timeout=600)


def test_bench_without_a_tpu_exits_nonzero_with_no_record():
    """`JAX_PLATFORMS=cpu python bench.py`: one line on stderr saying
    that there is no TPU, a nonzero exit code, and nothing on stdout —
    no throughput record, no placeholder, no CPU re-run."""
    proc = _run_bench_script({"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == b"", proc.stdout
    err = [ln for ln in proc.stderr.decode().splitlines()
           if ln.startswith("bench:")]
    assert len(err) == 1 and "no TPU" in err[0], proc.stderr.decode()[-800:]


def test_sweep_parent_never_imports_jax():
    """The sweep orchestrator holds no backend: a parent that touched
    JAX would hold the chip its measuring children need.  Run the whole
    sweep in a fresh interpreter with the children faked and look at
    sys.modules afterwards."""
    code = """
import json, sys
sys.path.insert(0, %r)
import bench

ROW = {"platform": "tpu", "device_kind": "TPU v5 lite", "n_chips": 1,
       "dtype": "bfloat16", "remat": False, "s2d": False,
       "conv_impl": "native", "impl_map": "", "loss": "milnce",
       "grad_accum": 1, "inner": 4, "step_ms": 1.0,
       "flops_per_step": 1e12, "flops_source": "xla",
       "flops_per_sec": None}
def fake_run_config(timeout_s=None, **kw):
    return dict(ROW, batch=kw["batch"], loss=kw.get("loss", "milnce"),
                grad_accum=kw.get("grad_accum", 1),
                clips_per_sec_per_chip=100.0 + kw["batch"] / 64.0)

bench._run_config = fake_run_config
bench._write_notes = lambda *a, **k: None
bench._emit = lambda rec: None
rec = bench.run_bench()
assert rec["platform"] == "tpu" and rec["value"] > 0
bad = sorted(m for m in sys.modules
             if m == "jax" or m.startswith(("jax.", "jaxlib")))
print(json.dumps(bad))
""" % _REPO
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          cwd=_REPO, timeout=300)
    assert proc.returncode == 0, proc.stderr.decode()[-800:]
    assert json.loads(proc.stdout.decode().splitlines()[-1]) == []


def test_no_tpu_in_the_first_child_ends_the_sweep(monkeypatch):
    """NoTpuError from the first measuring child propagates out of the
    sweep — it is not one more failed config to step over."""
    calls = []

    def no_tpu(timeout_s=None, **kw):
        calls.append(kw)
        raise bench.NoTpuError("bench: no TPU (jax found platform='cpu')")

    monkeypatch.setattr(bench, "_run_config", no_tpu)
    monkeypatch.setattr(bench, "_emit", lambda rec: None)
    with pytest.raises(bench.NoTpuError):
        bench.run_bench()
    assert len(calls) == 1


@pytest.mark.parametrize("env_dir", [None, "from_env"],
                         ids=["unset", "JAX_COMPILATION_CACHE_DIR"])
def test_compile_cache_rule(env_dir, tmp_path):
    """One rule (utils/compile_cache.py): where the variable is set JAX
    reads it and the code sets no directory; where it is not, the
    directory is the fixed default.  A fresh interpreter each, because
    the setting is process-wide."""
    code = """
import json, os, sys
sys.path.insert(0, %r)
import jax
from milnce_tpu.utils.compile_cache import (DEFAULT_CACHE_DIR,
                                            configure_compile_cache)
got = configure_compile_cache()
jax.jit(lambda x: x * 2 + 1)(jax.numpy.arange(8.0)).block_until_ready()
print(json.dumps({"returned": got, "default": DEFAULT_CACHE_DIR,
                  "config": jax.config.jax_compilation_cache_dir}))
""" % _REPO
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          cwd=_REPO, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr.decode()[-800:]
    out = json.loads(proc.stdout.decode().splitlines()[-1])
    assert out["default"] == os.path.join(_REPO, "build", "jax_cache")
    if env_dir:
        want = str(tmp_path / env_dir)
        assert out["returned"] == out["config"] == want
        assert os.listdir(want), "no cache entry written where the variable says"
    else:
        assert out["returned"] == out["config"] == out["default"]


def test_peak_flops_lookup():
    from milnce_tpu.utils.roofline import device_peak_flops

    assert device_peak_flops("TPU v5 lite") == 197e12
    assert device_peak_flops("TPU v4") == 275e12
    assert device_peak_flops("cpu") is None


def test_parse_mesh_spec_grammar():
    # '' = 1-D data mesh; 'data,model[=N]' = 2-D grid (default width 2);
    # anything else fails at parse time like the config grammars
    assert bench._parse_mesh_spec("") == (None, 1)
    assert bench._parse_mesh_spec("data,model") == ("model", 2)
    assert bench._parse_mesh_spec("data,model=4") == ("model", 4)
    for bad in ("model,data", "data", "data,model,extra"):
        with pytest.raises(ValueError, match="mesh spec"):
            bench._parse_mesh_spec(bad)


_TINY = dict(dtype="float32", batch=16, frames=4, size=32, words=4, k=2,
             remat=False, inner=1, s2d=False, conv_impl="native",
             flops_hint=None)


@pytest.mark.slow
class TestBenchConfigInProcess:
    """``_bench_config`` — what a measuring child runs — driven
    in-process at a tiny size on the CPU mesh: the row's plumbing, never
    its numbers (a CPU row cannot become a record, TestMakeRecord)."""

    def test_row_names_its_device(self):
        r = bench._bench_config(**_TINY)
        assert r["platform"] == "cpu" and r["n_chips"] >= 1
        assert "mfu" not in r           # no peak off the TPU
        with pytest.raises(ValueError, match="did not run on a TPU"):
            bench._make_record(r, 4, 32)

    def test_dtw_row_serializes_with_loss_tag(self):
        # the sdtw_3 comparison row: result must round-trip through the
        # tagged-JSON protocol (regression: the warmup loss scalar once
        # shadowed the loss-name arg -> ArrayImpl in the record) and
        # carry no MFU/FLOPs (the analytic model doesn't count the DP)
        r = bench._bench_config(**dict(_TINY, loss="sdtw_3"))
        assert r["loss"] == "sdtw_3"
        assert r["flops_per_step"] is None and "mfu" not in r
        assert r["clips_per_sec_per_chip"] > 0
        json.dumps(r)

    def test_grad_accum_row_measures_embedding_cache_step(self):
        # the north-star recipe row: grad_accum>1 routes the measurement
        # through make_grad_cache_step; FLOPs/MFU are suppressed (the
        # plain-step model doesn't describe the two-pass program) and the
        # record carries the grad_accum tag for BENCH_NOTES
        r = bench._bench_config(**dict(_TINY, grad_accum=2))
        assert r["grad_accum"] == 2
        assert r["flops_per_step"] is None and "mfu" not in r
        assert r["clips_per_sec_per_chip"] > 0
        json.dumps(r)

    def test_mesh_2d_row_carries_layout_identity(self, monkeypatch):
        # the ISSUE 6 sweep axis: a 2-D row must record which layout and
        # which sharding map produced the number (mesh shape + map hash),
        # so obs_report compares like with like
        monkeypatch.setenv("MILNCE_BENCH_FSDP_MIN", "256")
        r = bench._bench_config(**dict(_TINY, mesh_spec="data,model"))
        assert r["mesh"] == "4x2 (data,model)"
        assert r["params_sharded"] > 0
        assert len(r["sharding_map_hash"]) == 12
        assert r["clips_per_sec_per_chip"] > 0
        # ISSUE 8: every measured row carries its static HBM plan, and
        # the 2-D row's per-chip prediction reflects the FSDP sharding
        assert r["predicted_peak_bytes_per_chip"] > 0
        # Pass 5: and its precision fingerprint, so obs_report can flag
        # cross-precision compares
        assert len(r["dtype_census_hash"]) == 12
        json.dumps(r)

    def test_mesh_2d_row_refuses_pure_replication(self, monkeypatch):
        # a map that shards nothing must be REFUSED, not measured: paying
        # model-axis collectives for replication is not an FSDP data point
        monkeypatch.setenv("MILNCE_BENCH_FSDP_MIN", str(10 ** 9))
        with pytest.raises(RuntimeError, match="shards NOTHING"):
            bench._bench_config(**dict(_TINY, mesh_spec="data,model"))


def test_run_config_child_refuses_off_the_tpu():
    """The measuring child as a real subprocess where JAX finds no TPU:
    ``_run_config`` raises NoTpuError carrying the child's one line."""
    with pytest.raises(bench.NoTpuError, match="no TPU"):
        bench._run_config(timeout_s=300, **_TINY)
