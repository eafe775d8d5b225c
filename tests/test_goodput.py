"""Attribution-tier gates (ISSUE 9): goodput ledger, live MFU, anomaly
-> bounded profiler capture, run-identity tagging, pod aggregation.

The acceptance pins that live here:

- the goodput ledger on a 2-step instrumented CPU run AND on a chaos
  run (injected decode-timeout + nonfinite-grad faults) sums to the
  externally measured wall time within 5%, attributing nonzero badput
  to the injected sites;
- the live ``milnce_train_mfu`` gauge agrees with bench.py's
  roofline-derived MFU within 2% on the same steps (shared
  ``utils/roofline.py`` formula + table);
- a planted step-time spike fires the anomaly event and EXACTLY ONE
  profiler capture; a clean run captures zero times;
- ``obs_report --merge`` over >= 2 process-local snapshots produces a
  pod view ``--check`` can gate; mixed-run streams error loudly.

All tier-1 (suite-hygiene obs gate); the training runs share the
1-block tiny S3D jit cache with tests/test_obs.py.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from milnce_tpu.obs import aggregate
from milnce_tpu.obs import runctx
from milnce_tpu.obs.anomaly import EwmaSpikeDetector
from milnce_tpu.obs.capture import ProfilerCapture
from milnce_tpu.obs.export import SNAPSHOT_SCHEMA, snapshot
from milnce_tpu.obs.goodput import (CATEGORIES, compute_ledger,
                                    ledger_to_registry, select_run,
                                    split_runs)
from milnce_tpu.obs.metrics import MetricsRegistry
from milnce_tpu.obs.spans import SpanRecorder

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_OBS_REPORT = os.path.join(_REPO, "scripts", "obs_report.py")


def _span(name, ts, dur_s, **attrs):
    return {"kind": "span", "name": name, "ts": ts,
            "dur_ms": dur_s * 1e3, **attrs}


def _event(name, ts, **attrs):
    return {"kind": "event", "name": name, "ts": ts, **attrs}


# ---------------------------------------------------------------------------
# ledger unit behavior
# ---------------------------------------------------------------------------

class TestLedger:
    def _stream(self):
        recs = [_event("run.start", 0.0)]
        recs.append(_span("step", 1.0, 5.0, step=1))       # compile
        for i in range(4):                                  # 4 x 1s steps
            recs.append(_span("step", 6.0 + i, 1.0, step=i + 2))
        recs.append(_span("data.wait", 10.0, 0.5))
        recs.append(_span("data.wait", 10.5, 0.5))
        recs.append(_span("ckpt.save", 11.0, 1.0))
        recs.append(_span("sync", 12.0, 0.5, cause="display"))
        recs.append(_event("run.end", 20.0))
        return recs

    def test_categories_partition_and_sum_to_wall(self):
        led = compute_ledger(self._stream())
        assert led.wall_s == 20.0
        cats = led.categories
        assert cats["compile"] == 5.0
        assert cats["compute"] == pytest.approx(4.5)    # 4 steps + sync
        assert cats["data_wait"] == pytest.approx(1.0)
        assert cats["checkpoint"] == pytest.approx(1.0)
        assert cats["skipped"] == 0.0
        assert sum(cats.values()) == pytest.approx(led.wall_s)
        assert set(cats) == set(CATEGORIES)
        assert led.steps == 5
        assert 0 < led.goodput_fraction < 1

    def test_elastic_drain_and_reshard_categories(self):
        """ISSUE 20 satellite: elastic.drain / elastic.resume spans land
        in their own drain / reshard buckets (used INSTEAD of ckpt.save /
        ckpt.restore on the drain path — never alongside, which would
        double-count), and the partition still sums to wall."""
        recs = [_event("run.start", 0.0),
                _span("elastic.resume", 0.5, 1.5, label="latest",
                      from_mesh="{'data': 8}", to_mesh="{'data': 4}"),
                _span("step", 2.0, 5.0, step=1),            # compile
                _span("step", 7.0, 1.0, step=2),
                _span("step", 8.0, 1.0, step=3),
                _span("elastic.drain", 9.0, 2.0, label=1, forced=True,
                      source="host.preempt"),
                _event("run.end", 12.0)]
        led = compute_ledger(recs)
        cats = led.categories
        assert cats["reshard"] == pytest.approx(1.5)
        assert cats["drain"] == pytest.approx(2.0)
        assert cats["checkpoint"] == 0.0
        assert cats["compute"] == pytest.approx(2.0)
        assert sum(cats.values()) == pytest.approx(led.wall_s)
        assert set(cats) == set(CATEGORIES)

    def test_skipped_steps_reattributed_out_of_compute(self):
        recs = self._stream()
        recs.insert(-1, _event("display", 12.5, skipped_total=2))
        led = compute_ledger(recs)
        # 2 of 4 post-compile steps skipped -> half the compute moved
        assert led.skipped_steps == 2
        assert led.categories["skipped"] == pytest.approx(4.5 / 2)
        assert led.categories["compute"] == pytest.approx(4.5 / 2)
        assert sum(led.categories.values()) == pytest.approx(led.wall_s)

    def test_rollback_lost_uses_mean_step_time(self):
        recs = self._stream()
        recs.insert(-1, _event("rollback", 13.0, lost_updates=2,
                               consecutive_skips=1))
        led = compute_ledger(recs)
        assert led.rollbacks == 1 and led.lost_updates == 2
        # mean post-compile step = 1s -> 2s moved out of compute
        assert led.categories["rollback_lost"] == pytest.approx(2.0)
        assert led.categories["compute"] == pytest.approx(2.5)
        assert sum(led.categories.values()) == pytest.approx(led.wall_s)

    def test_overlapping_spans_exceed_wall_not_hidden(self):
        # double-counted attribution must SHOW (sum > wall), never be
        # silently clamped — the 5% acceptance pin relies on this
        recs = [_event("run.start", 0.0),
                _span("step", 0.0, 8.0, step=1),
                _span("step", 0.0, 8.0, step=2),
                _event("run.end", 10.0)]
        led = compute_ledger(recs)
        assert sum(led.categories.values()) > led.wall_s

    def test_resumed_run_same_id_window_covers_both_sessions(self):
        # review fix: a crashed run re-launched under the same explicit
        # run_id appends a second marker pair into the same stream; the
        # window must span FIRST start -> LAST end or the categories
        # (summed over both sessions) exceed wall and the gated
        # goodput_fraction inflates past 1.0
        recs = [_event("run.start", 0.0),
                _span("step", 1.0, 5.0, step=1),
                _span("step", 6.0, 5.0, step=2)]     # crash: no run.end
        recs += [_event("run.start", 100.0),
                 _span("step", 101.0, 5.0, step=1),
                 _span("step", 106.0, 5.0, step=2),
                 _event("run.end", 112.0)]
        led = compute_ledger(recs)
        assert led.wall_s == 112.0
        assert sum(led.categories.values()) == pytest.approx(112.0)
        assert led.goodput_fraction <= 1.0

    def test_mixed_run_stream_is_loud(self):
        recs = [dict(r, run_id="a") for r in self._stream()]
        recs += [dict(r, run_id="b") for r in self._stream()]
        with pytest.raises(ValueError, match="mixed-run"):
            compute_ledger(recs)
        led = compute_ledger(recs, run_id="a")
        assert led.run_id == "a" and led.wall_s == 20.0
        assert sorted(split_runs(recs)) == ["a", "b"]
        with pytest.raises(ValueError, match="not in stream"):
            select_run(recs, "c")

    def test_ledger_exports_gauges(self):
        reg = MetricsRegistry()
        ledger_to_registry(compute_ledger(self._stream()), reg)
        fam = reg.gauge("milnce_goodput_seconds", labels=("category",))
        vals = {k[0]: ch.value for k, ch in fam.items()}
        assert vals["compile"] == 5.0
        assert reg.gauge("milnce_goodput_wall_seconds").value == 20.0
        assert 0 < reg.gauge("milnce_goodput_fraction").value < 1


# ---------------------------------------------------------------------------
# EWMA spike detector
# ---------------------------------------------------------------------------

class TestDetector:
    def test_spike_fires_once_then_cooldown(self):
        clock = {"t": 0.0}
        rec = SpanRecorder()
        fired = []
        det = EwmaSpikeDetector("t.ms", ratio=2.0, warmup=3,
                                cooldown_s=100.0, recorder=rec,
                                on_anomaly=lambda v, e: fired.append(v),
                                time_fn=lambda: clock["t"])
        for _ in range(5):
            assert not det.observe(10.0)
        assert det.observe(50.0)                 # the spike
        assert not det.observe(50.0)             # cooldown suppresses
        clock["t"] = 200.0
        assert det.observe(50.0)                 # cooldown elapsed
        assert fired == [50.0, 50.0]
        events = [r for r in rec.tail() if r["name"] == "anomaly"]
        assert len(events) == 2
        assert events[0]["detector"] == "t.ms"
        assert events[0]["value"] == 50.0

    def test_warmup_suppresses_and_baseline_not_poisoned(self):
        det = EwmaSpikeDetector("t.ms", ratio=2.0, warmup=2,
                                cooldown_s=0.0, recorder=SpanRecorder())
        assert not det.observe(100.0)            # warmup: huge first value
        assert not det.observe(10.0)
        # anomalous samples must not be folded into the EWMA
        ewma_before = det.stats()["ewma"]
        det.observe(1000.0)
        assert det.stats()["ewma"] == ewma_before

    def test_bad_ratio_rejected(self):
        with pytest.raises(ValueError, match="ratio"):
            EwmaSpikeDetector("t", ratio=1.0)


# ---------------------------------------------------------------------------
# bounded one-shot capture
# ---------------------------------------------------------------------------

class _FakeProfiler:
    def __init__(self):
        self.starts, self.stops = [], []

    def start(self, d):
        self.starts.append(d)

    def stop(self):
        self.stops.append(True)


class TestCapture:
    def test_one_shot_budget_and_cooldown(self, tmp_path):
        clock = {"t": 0.0}
        prof = _FakeProfiler()
        rec = SpanRecorder()
        cap = ProfilerCapture(str(tmp_path), duration_s=1000.0,
                              cooldown_s=50.0, max_captures=2,
                              recorder=rec, start_fn=prof.start,
                              stop_fn=prof.stop,
                              time_fn=lambda: clock["t"])
        v = cap.arm(reason="spike")
        assert v["armed"] and "capture_001-spike" in v["trace_dir"]
        assert os.path.isdir(v["trace_dir"])
        # active: a second arm is refused, not double-started
        assert not cap.arm(reason="again")["armed"]
        assert cap.stop()
        assert not cap.stop()                    # idempotent
        # cooldown refuses, then a later arm succeeds
        assert "cooldown" in cap.arm()["reason"]
        clock["t"] = 60.0
        assert cap.arm(reason="second")["armed"]
        cap.stop()
        # budget exhausted at max_captures
        clock["t"] = 200.0
        assert "exhausted" in cap.arm()["reason"]
        assert prof.starts and len(prof.starts) == 2 == len(prof.stops)
        names = [r["name"] for r in rec.tail()]
        assert names.count("capture.start") == 2
        assert names.count("capture.stop") == 2

    def test_timer_auto_stops(self, tmp_path):
        prof = _FakeProfiler()
        rec = SpanRecorder()
        cap = ProfilerCapture(str(tmp_path), duration_s=0.05,
                              max_captures=1, recorder=rec,
                              start_fn=prof.start, stop_fn=prof.stop)
        assert cap.arm()["armed"]
        deadline = time.time() + 5.0
        while not prof.stops and time.time() < deadline:
            time.sleep(0.01)
        assert prof.stops, "duration timer never stopped the capture"
        stop_ev = [r for r in rec.tail() if r["name"] == "capture.stop"]
        assert stop_ev and stop_ev[0]["cause"] == "duration"
        assert cap.stats()["state"] == "idle"

    def test_http_reason_cannot_escape_out_dir(self, tmp_path):
        # review fix: the reason string arrives from the NETWORK (POST
        # /obs/capture) — path separators/.. must not steer the trace
        # write outside the capture root
        root = tmp_path / "caps"
        cap = ProfilerCapture(str(root), start_fn=lambda d: None,
                              stop_fn=lambda: None,
                              recorder=SpanRecorder())
        v = cap.arm(reason="../../../tmp/evil")
        assert v["armed"]
        inside = os.path.realpath(v["trace_dir"])
        assert inside.startswith(os.path.realpath(str(root)) + os.sep)
        assert ".." not in os.path.relpath(inside, str(root))

    def test_stop_during_starting_still_flushes(self, tmp_path):
        # review fix: close() landing while arm() is inside start_fn on
        # another thread must still stop the trace (a daemon timer dies
        # with the process and the capture would be lost)
        started = threading.Event()
        release = threading.Event()
        calls = {"stop": 0}

        def slow_start(d):
            started.set()
            assert release.wait(10)

        rec = SpanRecorder()
        cap = ProfilerCapture(str(tmp_path), duration_s=1000.0,
                              start_fn=slow_start,
                              stop_fn=lambda: calls.__setitem__(
                                  "stop", calls["stop"] + 1),
                              recorder=rec)
        result = {}
        t = threading.Thread(target=lambda: result.update(cap.arm()))
        t.start()
        assert started.wait(10)
        assert not cap.stop()           # lands in 'starting': flagged
        release.set()
        t.join(timeout=10)
        assert not result["armed"]
        assert "stop requested" in result["reason"]
        assert calls["stop"] == 1
        assert cap.stats()["state"] == "idle"
        stops = [r for r in rec.tail() if r["name"] == "capture.stop"]
        assert stops and stops[0]["cause"] == "stopped-during-start"

    def test_start_failure_returns_to_idle(self, tmp_path):
        def boom(d):
            raise RuntimeError("no profiler here")

        rec = SpanRecorder()
        cap = ProfilerCapture(str(tmp_path), start_fn=boom,
                              stop_fn=lambda: None, recorder=rec)
        v = cap.arm()
        assert not v["armed"] and "no profiler here" in v["reason"]
        assert cap.stats()["state"] == "idle"
        assert [r for r in rec.tail() if r["name"] == "capture.error"]


# ---------------------------------------------------------------------------
# run identity tagging
# ---------------------------------------------------------------------------

class TestRunIdentity:
    def test_records_and_snapshots_stamped(self):
        prev = runctx.set_run_context("runX", 3)
        try:
            rec = SpanRecorder()
            rec.event("e")
            with rec.span("s"):
                pass
            for r in rec.tail():
                assert r["run_id"] == "runX"
                assert r["process_index"] == 3
                assert "mono" in r
            doc = snapshot(MetricsRegistry())
            assert doc["run_id"] == "runX" and doc["process_index"] == 3
            # explicit args override the context
            doc2 = snapshot(MetricsRegistry(), run_id="other",
                            process_index=7)
            assert doc2["run_id"] == "other" and doc2["process_index"] == 7
        finally:
            runctx.set_run_context(*prev)

    def test_mono_is_append_ordered(self):
        rec = SpanRecorder()
        for i in range(5):
            rec.event("e", i=i)
        monos = [r["mono"] for r in rec.tail()]
        assert monos == sorted(monos)
        # since= filter returns only newer records
        newer = rec.tail(since=monos[2])
        assert [r["i"] for r in newer] == [3, 4]

    def test_mono_strictly_increasing_under_bursts(self):
        # review fix: back-to-back records rounding to the same
        # microsecond would let a poller whose cursor lands between
        # them miss the second forever (tail's filter is a strict '>')
        rec = SpanRecorder()
        for i in range(500):
            rec.event("burst", i=i)
        monos = [r["mono"] for r in rec.tail()]
        assert all(b > a for a, b in zip(monos, monos[1:]))
        # every cursor position yields exactly the records after it
        assert len(rec.tail(since=monos[249])) == 250


# ---------------------------------------------------------------------------
# pod aggregation
# ---------------------------------------------------------------------------

def _proc_snapshot(pi, qps, run_id="podrun"):
    reg = MetricsRegistry()
    reg.counter("req_total", "h").inc(10 * (pi + 1))
    reg.gauge("load", "h").set(float(pi))
    h = reg.histogram("lat", "h", buckets=(1.0, 2.0))
    h.observe(0.5)
    return snapshot(reg, kind="serve_bench", extra={"qps": qps},
                    run_id=run_id, process_index=pi)


class TestAggregate:
    def test_merge_snapshots_sum_and_spread(self):
        docs = [_proc_snapshot(0, 100.0), _proc_snapshot(1, 200.0),
                _proc_snapshot(2, 400.0)]
        pod = aggregate.merge_snapshots(docs)
        assert pod["kind"] == "pod_serve_bench"
        assert pod["processes"] == 3 and pod["run_id"] == "podrun"
        m = pod["metrics"]
        assert m["req_total"]["values"][0]["value"] == 60     # summed
        g = m["load"]["values"][0]
        assert (g["min"], g["value"], g["max"]) == (0.0, 1.0, 2.0)
        assert m["lat"]["values"][0]["count"] == 3            # summed
        assert pod["qps"] == 200.0                            # median
        assert pod["spread"]["qps"]["max"] == 400.0

    def test_merge_refuses_mixed_runs_and_dup_processes(self):
        with pytest.raises(ValueError, match="mixed-run"):
            aggregate.merge_snapshots(
                [_proc_snapshot(0, 1.0, "a"), _proc_snapshot(1, 1.0, "b")])
        with pytest.raises(ValueError, match="duplicate process_index"):
            aggregate.merge_snapshots(
                [_proc_snapshot(0, 1.0), _proc_snapshot(0, 2.0)])
        with pytest.raises(ValueError, match=">= 2"):
            aggregate.merge_snapshots([_proc_snapshot(0, 1.0)])
        with pytest.raises(ValueError, match="run_id"):
            aggregate.merge_snapshots([
                {"schema": SNAPSHOT_SCHEMA, "kind": "metrics",
                 "metrics": {}, "process_index": i} for i in range(2)])

    def test_event_stream_merge_flags_straggler(self):
        def stream(pi, step_ms):
            return [dict(_span("step", float(i), step_ms / 1e3, step=i),
                         run_id="podrun", process_index=pi)
                    for i in range(10)]

        view = aggregate.merge_event_streams(
            [stream(0, 10.0), stream(1, 10.5), stream(2, 20.0)])
        assert view["step_p50_skew"] == pytest.approx(2.0)
        assert view["stragglers"] == [2]
        assert view["per_process"][0]["step_ms_p50"] == pytest.approx(10.0)


# ---------------------------------------------------------------------------
# obs_report CLI: merge / latest-baseline / run-id split
# ---------------------------------------------------------------------------

def _run_report(*args):
    proc = subprocess.run([sys.executable, _OBS_REPORT, *args],
                          capture_output=True, text=True, timeout=120)
    return proc.returncode, proc.stdout + proc.stderr


def _goodput_doc(pi, frac, run_id="podrun"):
    return {"schema": SNAPSHOT_SCHEMA, "kind": "goodput",
            "run_id": run_id, "process_index": pi, "metrics": {},
            "goodput_fraction": frac, "mfu": 0.3,
            "wall_s": 100.0, "categories_s": {"compute": frac * 100.0}}


class TestObsReportCli:
    def test_mixed_run_stream_errors_and_run_id_selects(self, tmp_path):
        path = tmp_path / "ev.jsonl"
        with open(path, "w") as fh:
            for rid in ("a", "b"):
                for i in range(3):
                    fh.write(json.dumps(dict(
                        _span("step", float(i), 0.01, step=i),
                        run_id=rid)) + "\n")
        code, out = _run_report(str(path))
        assert code == 2 and "mixed-run stream" in out
        code, out = _run_report(str(path), "--run-id", "a")
        assert code == 0 and "step" in out

    def test_merge_produces_gateable_pod_view(self, tmp_path):
        for pi, frac in enumerate((0.5, 0.6)):
            (tmp_path / f"g{pi}.json").write_text(
                json.dumps(_goodput_doc(pi, frac)))
        pod = tmp_path / "POD.json"
        code, out = _run_report("--merge", str(tmp_path / "g0.json"),
                                str(tmp_path / "g1.json"),
                                "--out", str(pod))
        assert code == 0, out
        assert "pod_goodput" in out and "spread" in out.lower()
        doc = json.load(open(pod))
        assert doc["kind"] == "pod_goodput"
        assert doc["goodput_fraction"] == pytest.approx(0.55)
        # the merged view gates like any artifact: a baseline pod with
        # better goodput fails the check, a worse one passes
        better = tmp_path / "base.json"
        better.write_text(json.dumps(dict(doc, goodput_fraction=0.9)))
        code, out = _run_report("--check", str(pod),
                                "--baseline", str(better))
        assert code == 1 and "[FAIL] goodput_fraction" in out
        worse = tmp_path / "worse.json"
        worse.write_text(json.dumps(dict(doc, goodput_fraction=0.4)))
        code, out = _run_report("--check", str(pod),
                                "--baseline", str(worse))
        assert code == 0, out

    def test_merge_event_streams_reports_straggler(self, tmp_path):
        for pi, ms in ((0, 10.0), (1, 25.0)):
            with open(tmp_path / f"ev{pi}.jsonl", "w") as fh:
                for i in range(8):
                    fh.write(json.dumps(dict(
                        _span("step", float(i), ms / 1e3, step=i),
                        run_id="podrun", process_index=pi)) + "\n")
        code, out = _run_report("--merge", str(tmp_path / "ev0.jsonl"),
                                str(tmp_path / "ev1.jsonl"))
        assert code == 0, out
        assert "STRAGGLER" in out and "skew" in out

    def test_baseline_latest_picks_newest_same_kind(self, tmp_path):
        old = tmp_path / "old.json"
        old.write_text(json.dumps(_goodput_doc(0, 0.9, "r-old")))
        new = tmp_path / "new.json"
        new.write_text(json.dumps(_goodput_doc(0, 0.5, "r-new")))
        os.utime(old, (time.time() - 1000, time.time() - 1000))
        cur = tmp_path / "cur.json"
        cur.write_text(json.dumps(_goodput_doc(0, 0.52, "r-cur")))
        # newest same-kind is new.json (0.5): 0.52 vs 0.5 passes; had it
        # picked old.json (0.9) this would FAIL — the pass proves the pick
        code, out = _run_report("--check", str(cur), "--baseline",
                                "latest")
        assert code == 0, out
        assert "new.json" in out

    def test_merge_check_latest_resolves_in_inputs_dir(self, tmp_path):
        # review fix: --merge has a placeholder path ("<merged:N>") —
        # --baseline latest must scan the INPUT artifacts' directory,
        # not the cwd, even without --out
        for pi, frac in enumerate((0.5, 0.6)):
            (tmp_path / f"g{pi}.json").write_text(
                json.dumps(_goodput_doc(pi, frac)))
        pod_base = tmp_path / "POD_baseline.json"
        base = aggregate.merge_snapshots(
            [_goodput_doc(0, 0.5, "old"), _goodput_doc(1, 0.6, "old")])
        pod_base.write_text(json.dumps(base))
        code, out = _run_report("--merge", str(tmp_path / "g0.json"),
                                str(tmp_path / "g1.json"),
                                "--check", "--baseline", "latest",
                                "--tolerance", "0.5")
        assert code == 0, out
        assert "POD_baseline.json" in out

    def test_baseline_latest_refuses_kind_mismatch(self, tmp_path):
        cur = tmp_path / "cur.json"
        cur.write_text(json.dumps(_goodput_doc(0, 0.5)))
        other = tmp_path / "serve.json"
        other.write_text(json.dumps(
            {"schema": SNAPSHOT_SCHEMA, "kind": "serve_bench",
             "metrics": {}, "qps": 1.0}))
        code, out = _run_report("--check", str(cur), "--baseline",
                                "latest")
        assert code == 2
        assert "no other goodput artifact" in out
        assert "serve_bench" in out


# ---------------------------------------------------------------------------
# MFU: one formula, two consumers
# ---------------------------------------------------------------------------

def test_mfu_helper_matches_bench_formula():
    """bench.py computes flops_per_sec / (peak * n_chips); the loop's
    live gauge calls roofline.mfu — given the same measured throughput
    they must agree exactly (well inside the 2% acceptance bound)."""
    from milnce_tpu.utils.roofline import mfu

    flops, dt, inner, peak, chips = 3.2e9, 0.25, 4, 1.0e12, 8
    bench_style = (flops * inner / dt) / (peak * chips)
    assert mfu(flops, inner / dt, peak, chips) == pytest.approx(
        bench_style, rel=1e-12)


def test_peak_flops_table_or_error_never_a_default(monkeypatch):
    """The MFU denominator comes from the table alone: off the TPU the
    gauge is off (None), on it an unknown device_kind is an error, and
    no environment variable overrides either."""
    from types import SimpleNamespace as Dev

    from milnce_tpu.utils.roofline import chip_peak_flops, device_peak_flops

    assert device_peak_flops("cpu") is None
    assert device_peak_flops("TPU v5e") == 197e12
    monkeypatch.setenv("MILNCE_PEAK_FLOPS", "2.5e12")
    assert device_peak_flops("cpu") is None
    assert chip_peak_flops(Dev(platform="cpu", device_kind="cpu")) is None
    assert chip_peak_flops(Dev(platform="tpu",
                               device_kind="TPU v5 lite")) == 197e12
    with pytest.raises(ValueError, match="TPU v9000"):
        chip_peak_flops(Dev(platform="tpu", device_kind="TPU v9000"))


# ---------------------------------------------------------------------------
# end to end: instrumented CPU runs (the ISSUE 9 acceptance)
# ---------------------------------------------------------------------------

def _tiny_cfg(tmp_path, samples=16, epochs=1):
    from milnce_tpu.config import tiny_preset

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
    return path, [json.loads(l) for l in open(path)]


@pytest.fixture(scope="module")
def two_step_run(tmp_path_factory):
    """ONE instrumented 2-step CPU run shared by the ledger-sum and
    pod-merge tests (each training run pays model init + a stop-save;
    the artifacts are read-only afterwards)."""
    from milnce_tpu.train.loop import run_training

    tmp = tmp_path_factory.mktemp("goodput_two_step")
    cfg = _tiny_cfg(tmp)
    cfg.train.run_id = "goodput-2step"
    t0 = time.monotonic()
    res = run_training(cfg, max_steps=2)
    return {"cfg": cfg, "res": res, "wall": time.monotonic() - t0}


def test_two_step_run_ledger_sums_to_measured_wall(two_step_run):
    """ISSUE 9 acceptance: ledger categories on the 2-step instrumented
    run sum to the externally measured wall time within 5%; every event
    line and the GOODPUT snapshot carry run_id + process_index."""
    cfg, res = two_step_run["cfg"], two_step_run["res"]
    measured_wall = two_step_run["wall"]
    assert res.steps == 2 and np.isfinite(res.last_loss)

    path, records = _read_events(cfg)
    for r in records:
        assert r["run_id"] == "goodput-2step", r
        assert r["process_index"] == 0
        assert "mono" in r
    assert [r["name"] for r in records].count("data.wait") >= 2

    gp_path = os.path.join(cfg.train.log_root, "GOODPUT.json")
    assert os.path.exists(gp_path), "run wrote no goodput ledger"
    doc = json.load(open(gp_path))
    assert doc["schema"] == SNAPSHOT_SCHEMA and doc["kind"] == "goodput"
    assert doc["run_id"] == "goodput-2step"
    assert doc["process_index"] == 0
    total = sum(doc["categories_s"].values())
    assert total == pytest.approx(measured_wall, rel=0.05), (
        f"ledger sum {total:.3f}s vs measured {measured_wall:.3f}s "
        f"(categories {doc['categories_s']})")
    assert doc["steps"] == 2
    assert 0.0 <= doc["goodput_fraction"] <= 1.0
    # obs_report summarizes + gates the artifact end to end
    code, out = _run_report(gp_path)
    assert code == 0 and "wall-time attribution" in out


def test_chaos_run_ledger_attributes_injected_badput(tmp_path):
    """ISSUE 9 acceptance: injected decode-timeout + nonfinite-grad
    faults produce a ledger that (a) sums to measured wall within 5%
    and (b) shows nonzero badput at BOTH injected sites.  The same run
    also pins the SIGUSR1 manual-capture path (detector disabled so the
    one capture is attributable to the signal alone) — training runs
    are the expensive part of this file, so acceptance pins share them."""
    from milnce_tpu.train.loop import run_training

    cfg = _tiny_cfg(tmp_path, samples=64, epochs=2)
    cfg.train.run_id = "goodput-chaos"
    cfg.train.capture_dir = str(tmp_path / "captures")
    cfg.train.capture_ms = 100.0
    cfg.train.anomaly_detect = False    # isolate the signal path
    # sample 20 hangs 1.5s -> watchdog timeout at 0.3s -> retry decodes
    # clean; optimizer step 3's gradients are poisoned -> finite guard
    # skips the update.  Lookahead/prefetch pinned to 0 so the hang
    # sits on the consumer's critical path deterministically — with
    # decode-ahead, a slow (loaded) run finishes the hung decode before
    # the consumer awaits it and the timeout never fires (flake).
    cfg.train.faults = "decode.hang@20:x=1.5;grad.nonfinite@3"
    cfg.data.sample_timeout = 0.3
    cfg.data.sample_timeout_retries = 1
    cfg.data.decode_lookahead = 0
    cfg.data.prefetch_depth = 0
    events_path = os.path.join(cfg.train.log_root, "RUN_EVENTS.jsonl")

    def send_after_first_display():
        deadline = time.time() + 60.0
        while time.time() < deadline:
            if os.path.exists(events_path):
                with open(events_path) as fh:
                    if any('"display"' in line for line in fh):
                        os.kill(os.getpid(), signal.SIGUSR1)
                        return
            time.sleep(0.01)

    t = threading.Thread(target=send_after_first_display, daemon=True)
    t.start()
    t0 = time.monotonic()
    res = run_training(cfg, max_steps=6)
    measured_wall = time.monotonic() - t0
    t.join(timeout=5)
    assert res.steps == 6
    assert res.skipped_steps == 1

    doc = json.load(open(os.path.join(cfg.train.log_root,
                                      "GOODPUT.json")))
    cats = doc["categories_s"]
    total = sum(cats.values())
    assert total == pytest.approx(measured_wall, rel=0.05), (
        f"ledger sum {total:.3f}s vs measured {measured_wall:.3f}s "
        f"({cats})")
    # the injected sites show up as attributed badput
    assert doc["skipped_steps"] == 1
    assert cats["skipped"] > 0.0, cats
    assert doc["decode_timeouts"] >= 1
    assert cats["data_wait"] > 0.0, cats
    # SIGUSR1 armed exactly one manual capture (detector was off)
    _, records = _read_events(cfg)
    starts = [r for r in records if r["name"] == "capture.start"]
    assert len(starts) == 1 and starts[0]["reason"] == "sigusr1"
    assert doc["captures"] == 1 and doc["anomalies"] == 0


def test_live_mfu_gauge_agrees_with_bench_formula(tmp_path, monkeypatch):
    """ISSUE 9 acceptance: the live gauge and bench.py's roofline MFU
    agree within 2% on the same steps — same FLOPs model, same peak
    table, same formula, same displayed throughput."""
    from milnce_tpu.obs import metrics as obs_metrics
    from milnce_tpu.train.loop import run_training
    from milnce_tpu.train import loop as train_loop
    from milnce_tpu.utils.roofline import mfu, train_step_flops

    # the CPU has no peak (gauge off): the test, not an option of the
    # program, gives this run a denominator
    monkeypatch.setattr(train_loop, "roofline_peak", lambda dev: 1e12)
    cfg = _tiny_cfg(tmp_path, samples=32)
    cfg.train.run_id = "goodput-mfu"
    # capture configured but the run is clean: doubles as the
    # zero-captures half of the anomaly acceptance (below)
    cfg.train.capture_dir = str(tmp_path / "captures")
    res = run_training(cfg, max_steps=3)
    assert res.steps == 3

    reg = obs_metrics.registry()
    live_mfu = reg.gauge("milnce_train_mfu").value
    clips_per_sec = reg.gauge("milnce_train_clips_per_sec").value
    assert live_mfu > 0 and clips_per_sec > 0
    flops = train_step_flops(
        cfg.train.batch_size, cfg.data.num_frames, cfg.data.video_size,
        cfg.data.num_candidates, cfg.data.max_words,
        inception_blocks=cfg.model.inception_blocks)
    import jax

    expected = mfu(flops, clips_per_sec / cfg.train.batch_size,
                   1e12, len(jax.devices()))
    assert live_mfu == pytest.approx(expected, rel=0.02), (
        f"live {live_mfu} vs bench-formula {expected}")
    # the display events carry mfu, and the ledger snapshot exposes it
    # at top level for the obs_report gate
    _, records = _read_events(cfg)
    displays = [r for r in records if r["name"] == "display"]
    assert displays and all("mfu" in r for r in displays)
    doc = json.load(open(os.path.join(cfg.train.log_root,
                                      "GOODPUT.json")))
    assert doc["mfu"] > 0
    # clean run: zero anomalies, zero captures (ISSUE 9 acceptance —
    # the detector's warmup + ratio gates stay quiet on a healthy run)
    names = [r["name"] for r in records]
    assert names.count("anomaly") == 0
    assert names.count("capture.start") == 0
    assert doc["captures"] == 0


def test_planted_spike_fires_one_anomaly_and_one_capture(tmp_path):
    """ISSUE 9 acceptance: a planted step-time spike (a 2s decode hang
    surfacing as data wait in one display window) fires the anomaly
    event and EXACTLY ONE bounded profiler capture."""
    from milnce_tpu.train.loop import run_training

    cfg = _tiny_cfg(tmp_path, samples=64, epochs=1)
    cfg.train.run_id = "goodput-spike"
    cfg.train.capture_dir = str(tmp_path / "captures")
    cfg.train.capture_ms = 100.0
    cfg.train.anomaly_warmup = 3
    cfg.train.anomaly_ratio = 2.0
    # sample 60 (in step 8's batch) hangs 2s with the watchdog off: the
    # consumer waits the full hang -> one window spikes far past 2x
    # EWMA.  Lookahead/prefetch 0 keep the hang on the consumer's
    # critical path (decode-ahead on a slow machine would absorb it
    # before the await and the spike would vanish — observed flake).
    cfg.train.faults = "decode.hang@60:x=2.0"
    cfg.data.sample_timeout = 0.0
    cfg.data.decode_lookahead = 0
    cfg.data.prefetch_depth = 0
    res = run_training(cfg, max_steps=8)
    assert res.steps == 8

    _, records = _read_events(cfg)
    names = [r["name"] for r in records]
    anomalies = [r for r in records if r["name"] == "anomaly"]
    assert len(anomalies) == 1, (
        f"expected exactly 1 anomaly, got {len(anomalies)}: {anomalies}")
    assert anomalies[0]["detector"] == "train.step_ms"
    assert names.count("capture.start") == 1
    assert names.count("capture.stop") == 1
    start = [r for r in records if r["name"] == "capture.start"][0]
    assert start["reason"] == "step_time_spike"
    assert os.path.isdir(start["trace_dir"])
    # the real jax.profiler wrote an actual trace
    trace_files = [f for root, _, fs in os.walk(start["trace_dir"])
                   for f in fs]
    assert trace_files, "capture directory holds no trace"
    doc = json.load(open(os.path.join(cfg.train.log_root,
                                      "GOODPUT.json")))
    assert doc["anomalies"] == 1 and doc["captures"] == 1


def test_pod_merge_of_real_goodput_snapshots(two_step_run, tmp_path):
    """ISSUE 9 acceptance: obs_report --merge over two process-local
    snapshots of one run -> a pod view --check gates.  The second
    process view is synthesized from the real one (one CPU process
    can't host two jax process indices), exercising the REAL merge path
    over a REAL artifact."""
    cfg = two_step_run["cfg"]
    p0 = os.path.join(cfg.train.log_root, "GOODPUT.json")
    doc = json.load(open(p0))
    doc1 = dict(doc, process_index=1,
                goodput_fraction=doc["goodput_fraction"] * 0.8)
    p1 = os.path.join(cfg.train.log_root, "GOODPUT.p1.json")
    json.dump(doc1, open(p1, "w"))
    pod = os.path.join(str(tmp_path), "POD.json")
    code, out = _run_report("--merge", p0, p1, "--out", pod)
    assert code == 0, out
    merged = json.load(open(pod))
    assert merged["kind"] == "pod_goodput"
    assert merged["processes"] == 2
    assert merged["run_id"] == "goodput-2step"
    # gates like any single-process artifact
    code, out = _run_report("--check", pod, "--baseline", p0,
                            "--tolerance", "0.5")
    assert code == 0, out
