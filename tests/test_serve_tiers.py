"""Per-tenant SLO classes (ISSUE 14): tier spec parsing, the
starvation-protection cap on the admission controller, tier threading
through the service + HTTP front, the serve_bench knee finder, and the
two-tier chaos bench acceptance (interactive + batch backfill under
``index.swap_raise@%3``, gated via ``obs_report --check``).

The unit layers are jax-free (an engine-shaped fake); the chaos bench
is a subprocess because the acceptance pin IS the real script end to
end (fast-child exemption in test_suite_hygiene.py)."""

import importlib.util
import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from milnce_tpu.obs import metrics as obs_metrics
from milnce_tpu.serving.service import (AdmissionController,
                                        RetrievalService, ShedError,
                                        parse_tier_spec, serve_http)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_serve_bench():
    spec = importlib.util.spec_from_file_location(
        "serve_bench_under_test",
        os.path.join(_REPO, "scripts", "serve_bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class FakeEngine:
    """Engine-shaped stand-in (mirrors test_serve_chaos's): embed is a
    pure function of the rows, with injectable delay."""

    buckets = (4, 8)
    max_batch = 8
    text_words = 4
    embed_dim = 8

    def __init__(self, delay_s: float = 0.0):
        self.delay_s = delay_s

    def bucket_for(self, n):
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(n)

    def embed_text(self, rows):
        if self.delay_s:
            time.sleep(self.delay_s)
        rows = np.asarray(rows)
        return np.tile(rows[:, :1].astype(np.float32),
                       (1, self.embed_dim))

    embed_video = embed_text

    def recompiles(self):
        return 0

    def stats(self):
        return {"buckets": list(self.buckets), "max_batch": self.max_batch,
                "recompiles": 0, "dead": False, "calls": {}}


def _rows(n=1, fill=3):
    return np.full((n, 4), fill, np.int32)


class TestTierSpec:
    def test_parse_ordered_shares(self):
        spec = parse_tier_spec("interactive:1.0,batch:0.5")
        assert list(spec) == ["interactive", "batch"]  # priority order
        assert spec == {"interactive": 1.0, "batch": 0.5}

    @pytest.mark.parametrize("bad", [
        "interactive", "a:0", "a:1.5", "a:1.0,a:0.5", ":0.5"])
    def test_malformed_specs_rejected(self, bad):
        with pytest.raises(ValueError):
            parse_tier_spec(bad)

    def test_empty_spec_is_untiered(self):
        assert parse_tier_spec("") == {}


class TestTierAdmission:
    def _ac(self, max_inflight=4, tiers="interactive:1.0,batch:0.25"):
        return AdmissionController(
            max_inflight, max_batch=4, tiers=tiers,
            registry=obs_metrics.MetricsRegistry())

    def test_batch_backfill_cannot_starve_interactive(self):
        """THE SLO-class property: with batch capped at share 0.25 of
        max_inflight=4 (cap 1), a saturating batch tenant sheds on its
        OWN cap while interactive still admits up to the global bound."""
        ac = self._ac()
        with ac.admit(1, None, "batch"):
            with pytest.raises(ShedError) as exc_info:
                with ac.admit(1, None, "batch"):
                    pass
            assert exc_info.value.reason == "tier_overload"
            assert exc_info.value.retry_after_ms > 0
            with ac.admit(3, None, "interactive"):   # up to the global 4
                pass
        st = ac.stats()
        assert st["tiers"]["batch"]["cap"] == 1
        assert st["tiers"]["batch"]["shed"] == {"tier_overload": 1}
        assert st["tiers"]["interactive"]["shed"] == {}

    def test_default_tier_is_the_highest_priority_one(self):
        ac = self._ac()
        with ac.admit(1, None, None):
            assert ac.tier_inflight("interactive") == 1
            assert ac.tier_inflight("batch") == 0

    def test_unknown_tier_is_a_loud_error(self):
        ac = self._ac()
        with pytest.raises(ValueError, match="unknown SLO tier"):
            with ac.admit(1, None, "nope"):
                pass

    def test_unarmed_controller_never_tier_sheds(self):
        ac = self._ac(max_inflight=0)
        with ac.admit(100, None, "batch"):           # unbounded
            with ac.admit(100, None, "batch"):
                pass

    def test_slots_release_per_tier_on_exit(self):
        ac = self._ac()
        with ac.admit(1, None, "batch"):
            pass
        with ac.admit(1, None, "batch"):             # admissible again
            pass
        assert ac.tier_inflight("batch") == 0

    def test_untiered_controller_ignores_tier_names(self):
        ac = AdmissionController(4, max_batch=4,
                                 registry=obs_metrics.MetricsRegistry())
        with ac.admit(1, None, "anything"):          # no tiers: pass-through
            pass
        assert "tiers" not in ac.stats()


class TestTierService:
    def test_tier_threads_through_service_and_http_with_429_and_400(self):
        slow = FakeEngine(delay_s=0.6)
        service = RetrievalService(
            slow, None,
            registry=obs_metrics.MetricsRegistry(),
            max_inflight=4, tiers="interactive:1.0,batch:0.25")
        server = serve_http(service, port=0)
        threading.Thread(target=server.serve_forever, daemon=True).start()
        base = f"http://127.0.0.1:{server.server_address[1]}"

        def post(route, payload):
            req = urllib.request.Request(
                base + route, data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"})
            return urllib.request.urlopen(req, timeout=30)

        try:
            started = threading.Event()

            def occupy():                      # batch's 1 slot, slowly
                started.set()
                try:
                    post("/v1/embed_text", {"token_ids": [[1, 1, 1, 1]],
                                            "tier": "batch"})
                except Exception:
                    pass

            t = threading.Thread(target=occupy, daemon=True)
            t.start()
            started.wait()
            deadline = time.monotonic() + 5.0
            while (time.monotonic() < deadline
                   and service._admission.tier_inflight("batch") < 1):
                time.sleep(0.01)
            assert service._admission.tier_inflight("batch") == 1
            # a second batch request: 429 with the tier_overload reason
            with pytest.raises(urllib.error.HTTPError) as exc_info:
                post("/v1/embed_text", {"token_ids": [[2, 2, 2, 2]],
                                        "tier": "batch"})
            err = exc_info.value
            assert err.code == 429
            body = json.loads(err.read())
            assert body["kind"] == "shed"
            assert body["reason"] == "tier_overload"
            assert int(err.headers["Retry-After"]) >= 1
            # interactive still served while batch is capped out
            with post("/v1/embed_text", {"token_ids": [[3, 3, 3, 3]],
                                         "tier": "interactive"}) as r:
                assert r.status == 200
            # unknown tier: 400, never a silent default
            with pytest.raises(urllib.error.HTTPError) as exc_info:
                post("/v1/embed_text", {"token_ids": [[4, 4, 4, 4]],
                                        "tier": "platinum"})
            assert exc_info.value.code == 400
            # /healthz surfaces the per-tier admission block
            with urllib.request.urlopen(base + "/healthz",
                                        timeout=30) as r:
                h = json.loads(r.read())
            tiers = h["admission"]["tiers"]
            assert tiers["batch"]["shed"].get("tier_overload", 0) >= 1
            t.join(timeout=10)
        finally:
            server.shutdown()
            server.server_close()
            service.close()


class TestKneeFinder:
    def test_knee_is_the_highest_load_inside_slo_and_served_frac(self):
        sb = _load_serve_bench()
        rounds = [
            {"qps_offered": 50, "p99_ms": 40.0, "served_frac": 1.0},
            {"qps_offered": 100, "p99_ms": 80.0, "served_frac": 0.98},
            {"qps_offered": 200, "p99_ms": 900.0, "served_frac": 0.6},
        ]
        assert sb.knee_from_rounds(rounds, slo_ms=100.0) == 100
        assert sb.knee_from_rounds(rounds, slo_ms=50.0) == 50
        assert sb.knee_from_rounds(rounds, slo_ms=10.0) is None

    def test_served_frac_gate_counts_refusals_against_the_knee(self):
        sb = _load_serve_bench()
        rounds = [{"qps_offered": 50, "p99_ms": 5.0, "served_frac": 0.5}]
        assert sb.knee_from_rounds(rounds, slo_ms=100.0) is None

    def test_tier_qps_spec_parses(self):
        sb = _load_serve_bench()
        assert sb.parse_tier_qps("interactive:80,batch:200") == {
            "interactive": 80.0, "batch": 200.0}
        with pytest.raises(ValueError):
            sb.parse_tier_qps("interactive")
        with pytest.raises(ValueError, match="UNIQUE"):
            sb.parse_tier_qps("interactive:80,interactive:200")


# ---------------------------------------------------------------------------
# ISSUE acceptance: the two-tier chaos bench — interactive + batch
# backfill, live-index ingest under index.swap_raise@%3 — gated against
# the committed baseline via obs_report --check (fast-child exemption in test_suite_hygiene.py)
# ---------------------------------------------------------------------------

TIER_BENCH_ARGS = [
    "--backend", "cpu", "--preset", "tiny", "--duration", "2",
    "--corpus", "12", "--distinct", "0",
    "--max_batch", "8", "--min_bucket", "8", "--cache_capacity", "0",
    "--timeout_ms", "250", "--live_index",
    "--ingest_rows", "4", "--ingest_interval_s", "0.3",
    "--max_inflight", "8",
    "--tiers", "interactive:25,batch:120",
    "--tier_shares", "interactive:1.0,batch:0.5",
    "--faults", "index.swap_raise@%3",
]


def test_two_tier_chaos_bench_acceptance(tmp_path):
    """Interactive + batch backfill under swap chaos: the bench
    completes with zero unstructured errors, the batch tier absorbs the
    shedding (its cap, not interactive's traffic, is the limiter),
    ingest keeps landing generations THROUGH injected swap failures,
    recompiles stay 0 — and the per-tier gate metrics clear
    ``obs_report --check`` against the committed baseline."""
    out = tmp_path / "SB_TIERS.json"
    env = dict(os.environ)
    env.pop("MILNCE_FAULTS", None)
    env.setdefault("JAX_COMPILATION_CACHE_DIR", "/tmp/jax_test_cache")
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.0")
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "scripts", "serve_bench.py")]
        + TIER_BENCH_ARGS + ["--out", str(out)],
        capture_output=True, text=True, timeout=540, env=env)
    assert proc.returncode == 0, (
        f"tier chaos bench failed\nstdout:\n{proc.stdout}\n"
        f"stderr:\n{proc.stderr}")
    report = json.loads(out.read_text())
    tiers = report["tiers"]
    assert set(tiers) == {"interactive", "batch"}
    # zero unstructured failures anywhere; refusals are structured sheds
    assert report["errors"] == 0
    for name, td in tiers.items():
        assert td["error_rate"] == 0.0, (name, td)
    # the batch tier absorbs the shedding: its share cap binds first
    assert tiers["batch"]["shed"] >= tiers["interactive"]["shed"]
    assert tiers["batch"]["shed"] >= 1, "backfill never hit its cap"
    # interactive kept being served through the chaos window
    assert tiers["interactive"]["requests"] >= 10
    # ingest survived the injected swap failures: generations advanced
    # AND failures actually fired
    ing = report["ingest"]
    assert ing["swap_failures"] >= 1, "index.swap_raise@%3 never fired"
    assert ing["generation"] >= 1 and ing["swaps"] >= 1
    assert ing["corpus_size"] > 12
    # steady state stayed pre-traced through ingest + swaps + chaos
    assert report["engine"]["recompiles"] in (0, -1)
    assert report["index"]["recompiles"] == 0

    # the obs_report gate: per-tier p99 + error_rate + qps against the
    # committed baseline.  Tolerance is deliberately wide (5x band):
    # the thread-per-arrival open-loop driver's latencies swing several-
    # fold run to run on a loaded CI box, so this gate is the
    # catastrophic-regression fence (a wedged batcher or a quarantine
    # storm blows through 5x instantly) while the structural pins above
    # are the tight ones
    baseline = os.path.join(_REPO, "SERVE_BENCH_tiny_tiers.json")
    assert os.path.exists(baseline), "committed tier baseline missing"
    gate = subprocess.run(
        [sys.executable, os.path.join(_REPO, "scripts", "obs_report.py"),
         str(out), "--check", "--baseline", baseline,
         "--tolerance", "4.0"],
        capture_output=True, text=True, timeout=120)
    assert gate.returncode == 0, (
        f"obs_report gate failed:\n{gate.stdout}\n{gate.stderr}")
    assert "latency_ms_p99@interactive" in gate.stdout
    assert "error_rate@batch" in gate.stdout
