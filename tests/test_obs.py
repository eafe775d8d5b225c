"""Unified observability gates (ISSUE 5): metrics registry, span
recorder, Prometheus/JSONL exposition, and the obs_report regression
gate.

The load-bearing invariants pinned here:

- **thread safety with exact counts**: the registry exists to replace
  the unsynchronized ``/healthz`` dict race — N threads hammering one
  counter/histogram must land EXACTLY N*K increments, not "about";
- **host-side only**: recording anything that quacks like a device
  array is a ``TypeError``, never a silent ``float()`` device sync;
- **format stability**: the Prometheus text exposition and the
  ``milnce.obs/v1`` snapshot schema are contracts for scrapers and for
  ``scripts/obs_report.py`` — the goldens pin them byte-for-byte;
- **end to end**: a real 2-step instrumented CPU train run writes
  ``RUN_EVENTS.jsonl`` with step + checkpoint spans (ISSUE 5
  acceptance), and obs_report can summarize and gate it.

All tier-1 (the suite-hygiene obs gate pins this file never-slow);
the train-run test shares the S3D compile cache with
test_transfer_guard.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from milnce_tpu.obs.export import (PROMETHEUS_CONTENT_TYPE, SNAPSHOT_SCHEMA,
                                   snapshot, to_prometheus, write_snapshot)
from milnce_tpu.obs.metrics import MetricsRegistry
from milnce_tpu.obs.spans import SpanRecorder, get_recorder, install

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_OBS_REPORT = os.path.join(_REPO, "scripts", "obs_report.py")
_BASELINE = os.path.join(_REPO, "tests", "fixtures",
                         "obs_baseline_serve.json")


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_thread_hammer_exact_final_counts(self):
        """8 threads x 2000 mixed recordings; every count must be exact
        — this is the /healthz race, fixed."""
        reg = MetricsRegistry()
        c = reg.counter("hammer_total", "t")
        g = reg.gauge("hammer_gauge", "t")
        fam = reg.counter("hammer_labeled_total", "t", ("site",))
        h = reg.histogram("hammer_hist", "t", buckets=(2.0, 5.0))
        n_threads, k = 8, 2000

        def worker(tid):
            child = fam.labels(site=f"s{tid % 2}")
            for i in range(k):
                c.inc()
                g.inc()
                child.inc()
                h.observe(float(i % 10))

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        total = n_threads * k
        assert c.value == total
        assert g.value == total
        assert sum(ch.value for _, ch in fam.items()) == total
        snap = h.snapshot()
        assert snap["count"] == total
        assert sum(snap["counts"]) == total
        # per-thread values 0..9 uniformly: 0,1,2 <= 2.0; 3,4,5 <= 5.0
        assert snap["counts"] == [total * 3 // 10, total * 3 // 10,
                                  total * 4 // 10]

    def test_create_or_get_is_idempotent(self):
        reg = MetricsRegistry()
        a = reg.counter("dup_total", "x")
        b = reg.counter("dup_total", "x")
        assert a is b
        a.inc()
        assert b.value == 1

    def test_conflicting_reregistration_raises(self):
        reg = MetricsRegistry()
        reg.counter("one_name", "x")
        with pytest.raises(ValueError, match="conflicting"):
            reg.gauge("one_name", "x")
        with pytest.raises(ValueError, match="conflicting"):
            reg.counter("one_name", "x", labels=("site",))

    def test_counter_rejects_negative(self):
        with pytest.raises(ValueError):
            MetricsRegistry().counter("neg_total", "x").inc(-1)

    def test_label_names_must_match_declaration(self):
        fam = MetricsRegistry().counter("lbl_total", "x", ("site",))
        with pytest.raises(ValueError):
            fam.labels(zone="a")

    def test_callback_gauge_reads_live_and_rejects_set(self):
        reg = MetricsRegistry()
        box = {"v": 1.0}
        g = reg.gauge("cb_gauge", "x", fn=lambda: box["v"])
        assert g.value == 1.0
        box["v"] = 7.5
        assert g.value == 7.5
        with pytest.raises(ValueError):
            g.set(3.0)

    def test_gauge_bind_races_value_reads_without_tearing(self):
        """ISSUE 7 regression: bind() swapped the callback with no lock
        while scrape threads read (graftlint GL010) — rebinding under
        concurrent reads must never raise and every read resolves to
        SOME bound callback's value."""
        g = MetricsRegistry().gauge("rebind_gauge", "x", fn=lambda: 1.0)
        errors = []
        stop = threading.Event()

        def reader():
            try:
                while not stop.is_set():
                    assert g.value in (1.0, 2.0)
            except Exception as exc:  # pragma: no cover - asserted below
                errors.append(exc)

        threads = [threading.Thread(target=reader) for _ in range(4)]
        for t in threads:
            t.start()
        for _ in range(500):
            g.bind(lambda: 2.0)
            g.bind(lambda: 1.0)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        assert not errors, errors

    def test_callback_gauge_callback_runs_outside_the_gauge_lock(self):
        """The callback is invoked AFTER the gauge lock is released:
        callbacks read other components' stats (the serving pattern:
        recompile gauge -> engine stats lock), and calling through
        while holding this gauge's lock would stack it above every
        callee lock in the order graph — lock-order hygiene (GL011/
        GL012 discipline, ISSUE 7)."""
        reg = MetricsRegistry()
        g = reg.gauge("hygiene_gauge", "x")
        held_during_callback = []
        g.bind(lambda: held_during_callback.append(g._lock.locked()) or 5.0)
        assert g.value == 5.0
        assert held_during_callback == [False]

    def test_device_array_recording_raises(self):
        """The tentpole invariant: float() of a device array is a
        blocking sync — the registry refuses it at the boundary."""
        import jax.numpy as jnp

        reg = MetricsRegistry()
        dev = jnp.ones(())
        with pytest.raises(TypeError, match="host-side only"):
            reg.counter("dev_total", "x").inc(dev)
        with pytest.raises(TypeError, match="host-side only"):
            reg.gauge("dev_gauge", "x").set(dev)
        with pytest.raises(TypeError, match="host-side only"):
            reg.histogram("dev_hist", "x", buckets=(1.0,)).observe(dev)


class TestHistogram:
    def test_bucket_edges_le_convention(self):
        """A value equal to an edge lands in THAT bucket (Prometheus
        cumulative-le semantics)."""
        h = MetricsRegistry().histogram("edges_hist", "x",
                                        buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 1.0, 1.5, 2.0, 4.0, 4.5):
            h.observe(v)
        snap = h.snapshot()
        assert snap["edges"] == [1.0, 2.0, 4.0]
        assert snap["counts"] == [2, 2, 1, 1]   # le1, le2, le4, +Inf
        assert snap["count"] == 6 and snap["sum"] == 13.5

    def test_bad_edges_rejected(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError):
            reg.histogram("no_edges", "x", buckets=())
        with pytest.raises(ValueError):
            reg.histogram("unsorted", "x", buckets=(2.0, 1.0))


# ---------------------------------------------------------------------------
# exposition: Prometheus text + JSON snapshot
# ---------------------------------------------------------------------------

def _golden_registry() -> MetricsRegistry:
    reg = MetricsRegistry()
    reg.counter("requests_total", "requests handled").inc(3)
    reg.gauge("temperature", "current temp").set(1.5)
    reg.counter("by_site_total", "per-site requests",
                ("site",)).labels(site='a"b\\c').inc(2)
    h = reg.histogram("latency_ms", "request latency", buckets=(1.0, 2.0))
    for v in (0.5, 1.0, 5.0):
        h.observe(v)
    return reg


# the byte-exact exposition contract (format 0.0.4): integral values
# print without a decimal point, histogram buckets are cumulative with
# +Inf and _sum/_count, label values escaped per the spec
_GOLDEN_TEXT = """\
# HELP requests_total requests handled
# TYPE requests_total counter
requests_total 3
# HELP temperature current temp
# TYPE temperature gauge
temperature 1.5
# HELP by_site_total per-site requests
# TYPE by_site_total counter
by_site_total{site="a\\"b\\\\c"} 2
# HELP latency_ms request latency
# TYPE latency_ms histogram
latency_ms_bucket{le="1"} 2
latency_ms_bucket{le="2"} 2
latency_ms_bucket{le="+Inf"} 3
latency_ms_sum 6.5
latency_ms_count 3
"""


class TestExposition:
    def test_prometheus_golden(self):
        assert to_prometheus(_golden_registry()) == _GOLDEN_TEXT

    def test_content_type_pinned(self):
        assert PROMETHEUS_CONTENT_TYPE == (
            "text/plain; version=0.0.4; charset=utf-8")

    def test_snapshot_roundtrip(self, tmp_path):
        path = str(tmp_path / "snap.json")
        doc = write_snapshot(path, _golden_registry(), kind="metrics",
                             extra={"run": "r1"})
        back = json.load(open(path))
        assert back == doc
        assert back["schema"] == SNAPSHOT_SCHEMA == "milnce.obs/v1"
        assert back["kind"] == "metrics" and back["run"] == "r1"
        assert back["metrics"]["requests_total"]["values"][0]["value"] == 3
        hist = back["metrics"]["latency_ms"]["values"][0]
        assert hist["counts"] == [2, 0, 1] and hist["sum"] == 6.5

    def test_snapshot_reserved_extra_key_raises(self):
        with pytest.raises(ValueError, match="reserved"):
            snapshot(MetricsRegistry(), extra={"metrics": {}})

    def test_nonfinite_samples_render_not_crash(self):
        # a guarded train window with zero applied updates sets the loss
        # gauge to nan by construction — one non-finite sample must
        # never 500 the whole scrape (NaN/+Inf are legal sample values)
        reg = MetricsRegistry()
        reg.gauge("g_nan").set(float("nan"))
        reg.gauge("g_inf").set(float("inf"))
        reg.gauge("g_ninf").set(float("-inf"))
        text = to_prometheus(reg)
        assert "g_nan NaN" in text
        assert "g_inf +Inf" in text
        assert "g_ninf -Inf" in text


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

class TestSpans:
    def test_jsonl_roundtrip(self, tmp_path):
        path = str(tmp_path / "RUN_EVENTS.jsonl")
        rec = SpanRecorder(path=path)
        with rec.span("step", step=1):
            pass
        rec.event("rollback", step=1, restored_epoch=3)
        with pytest.raises(RuntimeError, match="boom"):
            with rec.span("ckpt.save", label=2):
                raise RuntimeError("boom")
        rec.close()
        lines = [json.loads(l) for l in open(path)]
        assert [r["name"] for r in lines] == ["step", "rollback",
                                              "ckpt.save"]
        assert lines[0]["kind"] == "span" and lines[0]["dur_ms"] >= 0
        assert lines[0]["step"] == 1 and "ts" in lines[0]
        assert lines[1]["kind"] == "event"
        assert lines[1]["restored_epoch"] == 3
        # the failing span still recorded, carrying the exception type
        assert lines[2]["error"] == "RuntimeError"
        # the in-memory ring saw the same records
        assert rec.tail() == lines

    def test_ring_is_bounded_most_recent(self):
        rec = SpanRecorder(ring=4)
        for i in range(10):
            rec.event("e", i=i)
        tail = rec.tail()
        assert [r["i"] for r in tail] == [6, 7, 8, 9]
        assert [r["i"] for r in rec.tail(2)] == [8, 9]

    def test_install_swaps_and_restores(self):
        mine = SpanRecorder()
        prev = install(mine)
        try:
            assert get_recorder() is mine
        finally:
            assert install(prev) is mine
        assert get_recorder() is prev

    def test_spans_are_profiler_annotations_with_no_switch(self):
        """The TraceAnnotation bridge is always on where jax is imported
        (it is, in this process): spans record as ever, and
        ``annotation`` hands out the profiler's own class."""
        import jax

        from milnce_tpu.obs.spans import annotation

        assert isinstance(annotation("step"), jax.profiler.TraceAnnotation)
        rec = SpanRecorder()
        with rec.span("step", step=1):
            pass
        last = rec.tail()[-1]
        assert last["name"] == "step" and last["dur_ms"] >= 0

    def test_span_imports_no_jax_in_a_process_without_it(self):
        """A host-only process (a loader) that records spans stays
        host-only: the bridge reads ``sys.modules`` and imports
        nothing."""
        code = ("import sys\n"
                "from milnce_tpu.obs.spans import SpanRecorder, annotation\n"
                "rec = SpanRecorder()\n"
                "with rec.span('decode', sample=1):\n"
                "    rec.event('decode.retry')\n"
                "assert rec.tail()[-1]['name'] == 'decode'\n"
                "assert type(annotation('x')).__name__ == 'nullcontext'\n"
                "assert not [m for m in sys.modules if m == 'jax' "
                "or m.startswith('jax.')], 'span() imported jax'\n"
                "print('HOST_ONLY_OK')\n")
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True, timeout=120,
                              cwd=_REPO)
        assert proc.returncode == 0, proc.stderr
        assert "HOST_ONLY_OK" in proc.stdout

    def test_close_is_idempotent(self, tmp_path):
        rec = SpanRecorder(path=str(tmp_path / "x.jsonl"))
        rec.event("e")
        rec.close()
        rec.close()
        rec.event("ring_only_after_close")    # must not raise
        assert rec.tail()[-1]["name"] == "ring_only_after_close"


class TestRuntimeWatch:
    def test_only_a_long_collection_is_an_event(self, monkeypatch):
        """The hook itself, on a clock the test moves: 12 ms is an event
        with its generation, count and the pause's own end; 1 ms is
        not; nothing is written from inside the hook."""
        from milnce_tpu.obs import spans

        clock = [100.0]
        monkeypatch.setattr(spans, "_now", lambda: clock[0])
        rec = SpanRecorder()
        pauses = spans.RuntimeWatch(rec)
        for dur_s, gen in ((0.012, 2), (0.001, 0)):
            pauses._hook("start", {"generation": gen})
            clock[0] += dur_s
            pauses._hook("stop", {"generation": gen, "collected": 7})
        assert rec.tail() == []         # the hook takes no lock
        pauses.remove()                 # never installed: drains only
        (ev,) = rec.tail()
        assert ev["name"] == "runtime.gc" and ev["kind"] == "event"
        assert (ev["generation"], ev["collected"]) == (2, 7)
        assert ev["dur_ms"] == pytest.approx(12.0)
        assert ev["end_mono"] == pytest.approx(100.012)
        assert spans.GC_PAUSE_MIN_MS == 5.0

    def test_install_hooks_gc_and_remove_unhooks(self):
        import gc

        from milnce_tpu.obs import spans

        rec = SpanRecorder()
        before = list(gc.callbacks)
        pauses = spans.RuntimeWatch(rec).install()
        try:
            assert len(gc.callbacks) == len(before) + 1
            junk = []
            for _ in range(400_000):    # cycles only a collection frees
                a = []
                a.append(a)
                junk.append(a)
            del junk, a
            gc.collect()

            def mine():     # building the junk sets off passes of its own
                return [e for e in rec.tail() if e["name"] == "runtime.gc"
                        and e["collected"] >= 400_000]

            deadline = time.monotonic() + 5.0
            while not mine() and time.monotonic() < deadline:
                time.sleep(0.01)        # the writer thread's 50 ms
            (ev,) = mine()
        finally:
            pauses.remove()
        assert gc.callbacks == before
        assert ev["generation"] == 2
        assert ev["dur_ms"] >= spans.GC_PAUSE_MIN_MS


# ---------------------------------------------------------------------------
# obs_report: summaries + the CI regression gate
# ---------------------------------------------------------------------------

    def test_a_beat_a_second_says_how_late_the_watcher_woke(self):
        from milnce_tpu.obs import spans

        rec = SpanRecorder()
        watch = spans.RuntimeWatch(rec).install()
        try:
            deadline = time.monotonic() + 5.0
            while (len([e for e in rec.tail() if e["name"] == "runtime.beat"])
                   < 2 and time.monotonic() < deadline):
                time.sleep(0.05)
        finally:
            watch.remove()
        beats = [e for e in rec.tail() if e["name"] == "runtime.beat"]
        assert len(beats) >= 2 and beats[0]["kind"] == "event"
        for e in beats[:2]:
            # one wake-up every 20 ms, less what each came late
            assert 10 <= e["beats"] <= 50 and 1000.0 <= e["dur_ms"] < 2000.0
            assert 0 <= e["late_mean_ms"] <= e["late_max_ms"]
            assert e["late_mean_ms"] * e["beats"] <= e["dur_ms"]
            assert e["proc_cpu_ms"] >= 0
        # a second apart, on the recorder's clock
        assert 0.9 <= beats[1]["mono"] - beats[0]["mono"] <= 2.0
        assert (spans.BEAT_S, spans.BEAT_REPORT_S) == (0.02, 1.0)

    def test_one_late_wake_up_is_a_stall_written_at_once(self, monkeypatch):
        """The recorder's clock jumps 80 ms while the watcher sleeps: it
        woke that late, as far as it can tell."""
        from milnce_tpu.obs import spans

        jump = [0.0]
        monkeypatch.setattr(spans, "_now",
                            lambda: time.monotonic() + jump[0])
        rec = SpanRecorder()
        watch = spans.RuntimeWatch(rec).install()
        try:
            time.sleep(0.1)
            assert not [e for e in rec.tail()
                        if e["name"] == "runtime.stall"]
            jump[0] = 0.08
            deadline = time.monotonic() + 5.0
            while (not [e for e in rec.tail() if e["name"] == "runtime.stall"]
                   and time.monotonic() < deadline):
                time.sleep(0.005)
            (stall,) = [e for e in rec.tail() if e["name"] == "runtime.stall"]
            deadline = time.monotonic() + 5.0
            while (not [e for e in rec.tail() if e["name"] == "runtime.beat"]
                   and time.monotonic() < deadline):
                time.sleep(0.05)
        finally:
            watch.remove()
        assert stall["kind"] == "event" and 60.0 <= stall["late_ms"] <= 140.0
        assert stall["end_mono"] <= stall["mono"]
        assert stall["proc_cpu_ms"] >= 0
        if os.path.exists("/proc/thread-self/schedstat"):
            assert 0 <= stall["runq_ms"] <= stall["late_ms"] + 20.0 + 1.0
        # the beat of that second carries it as its latest wake-up
        beat = [e for e in rec.tail() if e["name"] == "runtime.beat"][0]
        assert beat["late_max_ms"] >= stall["late_ms"]
        assert spans.STALL_MIN_MS == 50.0

    def test_one_watcher_thread_and_none_after_remove(self):
        from milnce_tpu.obs import spans

        def watchers():
            return [t for t in threading.enumerate()
                    if t.name == "obs-runtime-watch"]

        before = len(watchers())
        watch = spans.RuntimeWatch(SpanRecorder()).install()
        assert len(watchers()) == before + 1
        watch.remove()
        assert len(watchers()) == before


def _run_report(*args):
    proc = subprocess.run([sys.executable, _OBS_REPORT, *args],
                          capture_output=True, text=True, timeout=120)
    return proc.returncode, proc.stdout + proc.stderr


def _serve_doc(p99=10.0, p50=4.0, qps=800.0):
    doc = json.load(open(_BASELINE))
    doc["latency_ms"]["p99"] = p99
    doc["latency_ms"]["p50"] = p50
    doc["qps"] = qps
    return doc


def _events_file(tmp_path, name, step_ms):
    path = tmp_path / name
    with open(path, "w") as fh:
        for i, ms in enumerate(step_ms):
            fh.write(json.dumps({"kind": "span", "name": "step",
                                 "ts": 0.0, "step": i,
                                 "dur_ms": ms}) + "\n")
        fh.write(json.dumps({"kind": "event", "name": "display",
                             "ts": 0.0}) + "\n")
    return str(path)


class TestObsReport:
    def test_summarize_snapshot(self):
        code, out = _run_report(_BASELINE)
        assert code == 0
        assert "kind: serve_bench" in out and "latency_ms_p99: 10" in out

    def test_summarize_events(self, tmp_path):
        path = _events_file(tmp_path, "ev.jsonl", [5.0, 6.0, 7.0])
        code, out = _run_report(path)
        assert code == 0
        assert "step" in out and "display=1" in out

    def test_gate_passes_within_tolerance(self, tmp_path):
        cur = tmp_path / "cur.json"
        cur.write_text(json.dumps(_serve_doc(p99=10.5, qps=790.0)))
        code, out = _run_report("--check", str(cur),
                                "--baseline", _BASELINE)
        assert code == 0, out
        assert "FAIL" not in out

    def test_gate_fails_on_15pct_p99_drift(self, tmp_path):
        cur = tmp_path / "cur.json"
        cur.write_text(json.dumps(_serve_doc(p99=11.5)))
        code, out = _run_report("--check", str(cur),
                                "--baseline", _BASELINE)
        assert code == 1
        assert "[FAIL] latency_ms_p99" in out

    def test_gate_fails_on_qps_collapse(self, tmp_path):
        cur = tmp_path / "cur.json"
        cur.write_text(json.dumps(_serve_doc(qps=600.0)))
        code, out = _run_report("--check", str(cur),
                                "--baseline", _BASELINE)
        assert code == 1
        assert "[FAIL] qps" in out

    def test_gate_fails_on_memory_footprint_inflation(self, tmp_path):
        """ISSUE 8: predicted_peak_bytes_per_chip (the static HBM plan
        bench stamps into each record) gates lower-is-better — a row
        that got faster by inflating its footprint is a regression; a
        shrinking footprint never fails (good direction)."""
        base = tmp_path / "base.json"
        base.write_text(json.dumps(
            {**_serve_doc(), "predicted_peak_bytes_per_chip": 10_000_000}))
        cur = tmp_path / "cur.json"
        cur.write_text(json.dumps(
            {**_serve_doc(), "predicted_peak_bytes_per_chip": 12_000_000}))
        code, out = _run_report("--check", str(cur),
                                "--baseline", str(base))
        assert code == 1
        assert "[FAIL] predicted_peak_bytes_per_chip" in out
        slim = tmp_path / "slim.json"
        slim.write_text(json.dumps(
            {**_serve_doc(), "predicted_peak_bytes_per_chip": 8_000_000}))
        code, out = _run_report("--check", str(slim),
                                "--baseline", str(base))
        assert code == 0, out

    def test_gate_all_zero_baseline_never_passes_vacuously(self, tmp_path):
        # an all-zero baseline (e.g. a bench error-path record committed
        # by mistake) skips every shared metric — a gate that compared
        # NOTHING must fail loudly, not exit 0
        base = tmp_path / "base.json"
        base.write_text(json.dumps(_serve_doc(p99=0.0, p50=0.0, qps=0.0)))
        cur = tmp_path / "cur.json"
        cur.write_text(json.dumps(_serve_doc(p99=999.0, qps=1.0)))
        code, out = _run_report("--check", str(cur),
                                "--baseline", str(base))
        assert code == 1
        assert "nothing was compared" in out

    def test_gate_good_direction_drift_never_fails(self, tmp_path):
        cur = tmp_path / "cur.json"
        cur.write_text(json.dumps(_serve_doc(p99=2.0, p50=1.0,
                                             qps=2000.0)))
        code, out = _run_report("--check", str(cur),
                                "--baseline", _BASELINE)
        assert code == 0, out

    def test_gate_step_time_drift_on_event_streams(self, tmp_path):
        base = _events_file(tmp_path, "base.jsonl", [10.0] * 20)
        ok = _events_file(tmp_path, "ok.jsonl", [10.5] * 20)
        bad = _events_file(tmp_path, "bad.jsonl", [11.5] * 20)
        code, out = _run_report("--check", ok, "--baseline", base)
        assert code == 0, out
        code, out = _run_report("--check", bad, "--baseline", base)
        assert code == 1
        assert "[FAIL] step_ms_p50" in out

    def test_gate_notes_cross_layout_compare(self, tmp_path):
        # ISSUE 6: 1-D vs 2-D runs ARE comparable (that IS the point of
        # the mesh/map-hash fields), but the report must attribute the
        # layout difference instead of reading it as a plain regression
        base = tmp_path / "base.json"
        base.write_text(json.dumps({**_serve_doc(), "mesh": "8 (data)"}))
        cur = tmp_path / "cur.json"
        cur.write_text(json.dumps({**_serve_doc(),
                                   "mesh": "4x2 (data,model)",
                                   "sharding_map_hash": "abc123def456"}))
        code, out = _run_report("--check", str(cur),
                                "--baseline", str(base))
        assert code == 0, out
        assert "[note] mesh differs: baseline 8 (data) -> current " \
               "4x2 (data,model)" in out
        assert "[note] sharding_map_hash differs" in out
        # identical layouts stay note-free
        code, out = _run_report("--check", str(base),
                                "--baseline", str(base))
        assert code == 0 and "[note]" not in out

    def test_gate_notes_cross_precision_compare(self, tmp_path):
        # Pass 5: a differing dtype_census_hash means the rows ran
        # different-precision programs — attributable, not a regression
        base = tmp_path / "base.json"
        base.write_text(json.dumps({**_serve_doc(),
                                    "dtype_census_hash": "f33cda64207f"}))
        cur = tmp_path / "cur.json"
        cur.write_text(json.dumps({**_serve_doc(),
                                   "dtype_census_hash": "0123abcd4567"}))
        code, out = _run_report("--check", str(cur),
                                "--baseline", str(base))
        assert code == 0, out
        assert "[note] dtype_census_hash differs" in out

    def test_incomparable_artifacts_fail_loudly(self, tmp_path):
        empty = tmp_path / "empty.jsonl"
        empty.write_text(json.dumps({"kind": "event", "name": "e",
                                     "ts": 0.0}) + "\n")
        code, out = _run_report("--check", str(empty),
                                "--baseline", _BASELINE)
        assert code != 0
        assert "no shared gate metrics" in out

    def test_unversioned_snapshot_rejected(self, tmp_path):
        legacy = tmp_path / "legacy.json"
        legacy.write_text(json.dumps({"qps": 1.0}))
        code, out = _run_report(str(legacy))
        assert code == 2
        assert "schema" in out


# ---------------------------------------------------------------------------
# end to end: the instrumented train loop (ISSUE 5 acceptance)
# ---------------------------------------------------------------------------

def test_two_step_train_run_writes_run_events(tmp_path):
    """A 2-step instrumented CPU train run must write RUN_EVENTS.jsonl
    with step + checkpoint spans — and the whole run already executes
    under the steady-state transfer guard, so this doubles as proof the
    recorder adds no host sync to the hot loop."""
    from milnce_tpu.config import tiny_preset
    from milnce_tpu.train.loop import run_training

    cfg = tiny_preset()
    cfg.model.inception_blocks = 1       # 1-block S3D: tier-1 compile time
    cfg.train.batch_size = 8
    cfg.data.synthetic_num_samples = 16
    cfg.data.num_reader_threads = 2
    cfg.train.checkpoint_root = str(tmp_path / "ckpt")
    cfg.train.log_root = str(tmp_path / "log")
    res = run_training(cfg, max_steps=2)
    assert res.steps == 2 and np.isfinite(res.last_loss)

    path = os.path.join(cfg.train.log_root, "RUN_EVENTS.jsonl")
    assert os.path.exists(path), "instrumented run wrote no event stream"
    records = [json.loads(l) for l in open(path)]
    steps = [r for r in records
             if r["kind"] == "span" and r["name"] == "step"]
    saves = [r for r in records
             if r["kind"] == "span" and r["name"] == "ckpt.save"]
    assert len(steps) == 2, f"expected 2 step spans, got {len(steps)}"
    assert [r["step"] for r in steps] == [1, 2]
    assert all(r["dur_ms"] >= 0 for r in steps)
    assert saves, "stop-save produced no ckpt.save span"
    # the run's stream detached: later library events go to the previous
    # process-default recorder, not the closed file
    assert get_recorder().path != path

    # obs_report summarizes the real artifact end to end
    code, out = _run_report(path)
    assert code == 0 and "ckpt.save" in out
