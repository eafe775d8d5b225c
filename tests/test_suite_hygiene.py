"""Suite/tooling hygiene gates, fast enough for tier-1.

Two classes of silent rot this pins down:

- **Marker audit**: tests that spawn the measurement stack (bench
  children, probe subprocesses) are multi-minute; an unmarked one slips
  into the `-m 'not slow'` tier and eats the 870 s timeout for every
  later test.  The audit walks the test sources so a NEW probe/autotune
  test cannot land unmarked.
- **Report-header lint**: every auto-written report artifact must open
  by naming its generator — a table whose provenance is guessable only
  from git archaeology gets trusted (or distrusted) wrongly, and the
  round-5 advisor already caught two byte-identical probe artifacts
  drifting apart.
"""

import ast
import json
import os

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TESTS = os.path.join(_REPO, "tests")

# source fragments that mean "this test runs the measurement stack in a
# child process" — multi-minute by construction.  The lockrt hammer
# child is listed so the audit SEES it; its one caller is then an
# explicit, reasoned exemption below rather than an invisible spawn.
_EXPENSIVE_FRAGMENTS = ("bench.py", "stage_probe.py", "xla_flag_probe.py",
                        "milnce_loss_bench.py", "real_train_eval.py",
                        "._run_config(", "lockrt_hammer_child.py",
                        "live_index_hammer_child.py")

# audited exceptions: child-process tests that are seconds-scale by
# construction and REQUIRED tier-1 by their ISSUE (a fresh interpreter +
# tiny preset, not the measurement stack).  Each entry must say why.
_FAST_CHILD_EXEMPT = {
    # ISSUE 4 acceptance: serve_bench --preset tiny --duration 1 on CPU
    # (~20 s incl. jax import); the report format is the contract, so it
    # must run the real script, and the serving gates pin it tier-1.
    "test_serve_bench.py::test_cpu_smoke_emits_valid_report",
    # ISSUE 7 acceptance: the 16-thread serving hammer under
    # MILNCE_LOCK_SANITIZE=1 — a subprocess because the sanitizer must
    # be armed BEFORE the serving modules import (module-level
    # DEVICE_DISPATCH_LOCK); ~20 s on the shared persistent compile
    # cache (dimensions match test_serving's stack), and the lock-order
    # gate pins it tier-1.
    "test_lockrt.py::test_serving_hammer_subprocess_under_sanitizer",
    # ISSUE 10 acceptance: the closed-loop chaos bench — serve_bench
    # --preset tiny --duration 2 with serve.dispatch_raise@%5 armed and
    # one replica force-killed mid-run.  A subprocess because the chaos
    # acceptance pin IS the real script end-to-end (fault arming, pool
    # build, report schema); tiny preset + the shared persistent compile
    # cache keep it seconds-scale, and the serving-chaos gate pins it
    # tier-1.
    "test_serve_chaos.py::test_chaos_serve_bench_closed_loop_acceptance",
    # ISSUE 14 satellite: the 16-thread ingest-while-query hammer under
    # MILNCE_LOCK_SANITIZE=1 — a subprocess because the sanitizer must
    # be armed BEFORE the serving modules import; tiny dims (16-wide
    # embeddings, no model) keep it seconds-scale, and the live-index
    # gate pins it tier-1.
    "test_live_index.py::test_live_index_hammer_subprocess_under_sanitizer",
    # ISSUE 14 acceptance: the two-tier chaos bench (interactive +
    # batch backfill with live-index ingest under index.swap_raise@%3)
    # gated via obs_report --check.  A
    # subprocess because the acceptance pin IS the real script + gate
    # end-to-end; tiny preset + the shared persistent compile cache
    # keep it seconds-scale, and the live-index gate pins it tier-1.
    "test_serve_tiers.py::test_two_tier_chaos_bench_acceptance",
    # ISSUE 22: the REFUSAL paths — bench's measuring child and the flag
    # probe where JAX finds no TPU.  Subprocesses because the pin IS the
    # real process's exit code and output; each imports jax, sees the
    # CPU and exits in seconds without compiling anything.
    "test_bench.py::test_run_config_child_refuses_off_the_tpu",
    "test_probes.py::test_flag_probe_without_a_tpu_writes_no_row",
}


def _is_slow_marked(node, class_slow: bool) -> bool:
    for deco in getattr(node, "decorator_list", []):
        text = ast.unparse(deco)
        if "slow" in text and "mark" in text:
            return True
    return class_slow


def _iter_tests(tree):
    """(node, inherits_class_slow_mark) for every test function."""
    for node in tree.body:
        if isinstance(node, ast.ClassDef):
            class_slow = _is_slow_marked(node, False)
            for sub in node.body:
                if (isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef))
                        and sub.name.startswith("test")):
                    yield sub, class_slow
        elif (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and node.name.startswith("test")):
            yield node, False


def test_measurement_stack_tests_are_slow_marked():
    offenders = []
    for fname in sorted(os.listdir(_TESTS)):
        if not fname.endswith(".py") or fname == os.path.basename(__file__):
            continue
        src = open(os.path.join(_TESTS, fname)).read()
        tree = ast.parse(src)
        for node, class_slow in _iter_tests(tree):
            seg = ast.get_source_segment(src, node) or ""
            # only child-process launches count: monkeypatched fakes and
            # unit tests of the pure logic are cheap and belong in tier-1
            spawns = ("sys.executable" in seg
                      and any(f in seg for f in _EXPENSIVE_FRAGMENTS))
            calls_real_child = ("._run_config(" in seg
                                and "monkeypatch" not in seg)
            if ((spawns or calls_real_child)
                    and not _is_slow_marked(node, class_slow)
                    and f"{fname}::{node.name}" not in _FAST_CHILD_EXEMPT):
                offenders.append(f"{fname}::{node.name}")
    assert not offenders, (
        "tests spawning the measurement stack must carry "
        f"@pytest.mark.slow (tier-1 budget): {offenders}")


# artifact -> generator whose name its first line must carry.  Only
# artifacts present on disk are checked (probe outputs are re-written on
# the chip; a fresh clone may lack some).
_REPORT_GENERATORS = {
    "BENCH_NOTES.md": "bench.py",
    "STAGE_PROBE.md": "scripts/stage_probe.py",
    "STAGE_PROBE_native_fwdbwd.md": "scripts/stage_probe.py",
    "STAGE_AUTOTUNE.md": "scripts/stage_probe.py",
    "XLA_FLAGS_PROBE.md": "scripts/xla_flag_probe.py",
    "DATA_BENCH.md": "scripts/data_bench.py",
    "LINT.md": "scripts/graft_lint.py",
    "MEMPLAN.md": "scripts/mem_plan.py",
    "BENCH_MILNCE_LOSS.md": "scripts/milnce_loss_bench.py",
    "NUMERICS.md": "scripts/precision_audit.py",
}


def test_auto_written_reports_name_their_generator():
    bad = []
    for fname, generator in _REPORT_GENERATORS.items():
        path = os.path.join(_REPO, fname)
        if not os.path.exists(path):
            continue
        with open(path) as fh:
            first = fh.readline()
        if generator not in first or "auto-written" not in first:
            bad.append(f"{fname}: {first.strip()!r}")
    assert not bad, ("auto-written reports must open with "
                     f"'(auto-written by <generator>)': {bad}")


def test_report_writers_emit_generator_headers():
    """Source-side half of the lint: every md-writing helper in the
    measurement scripts opens its artifact with the auto-written header,
    so a NEW report can't ship anonymous."""
    writers = {
        os.path.join(_REPO, "bench.py"): "auto-written by bench.py",
        os.path.join(_REPO, "scripts", "stage_probe.py"):
            "auto-written by scripts/stage_probe.py",
        os.path.join(_REPO, "scripts", "xla_flag_probe.py"):
            "auto-written by scripts/xla_flag_probe.py",
        os.path.join(_REPO, "scripts", "data_bench.py"):
            "auto-written by scripts/data_bench.py",
        # LINT.md's renderer lives in the package; the header still names
        # the CLI that users run
        os.path.join(_REPO, "milnce_tpu", "analysis", "report.py"):
            "auto-written by scripts/graft_lint.py",
        os.path.join(_REPO, "scripts", "mem_plan.py"):
            "auto-written by scripts/mem_plan.py",
        os.path.join(_REPO, "scripts", "milnce_loss_bench.py"):
            "auto-written by scripts/milnce_loss_bench.py",
        os.path.join(_REPO, "scripts", "precision_audit.py"):
            "auto-written by scripts/precision_audit.py",
    }
    for path, header in writers.items():
        assert header in open(path).read(), (
            f"{os.path.basename(path)} writes a report without naming "
            f"itself ('{header}')")


# graftlint gate tests (ISSUE 2; ISSUE 7 added the concurrency pass and
# the runtime lock sanitizer; ISSUE 8 the static HBM planner): the
# static-analysis + trace-invariant + lock-discipline + memory-plan
# layer only guards the hot path if it runs on EVERY default `pytest`
# invocation — a slow-marked (or vanished) gate ships regressions (and
# re-ships the /healthz-dict class of race).
_ANALYSIS_GATES = ("test_graftlint.py", "test_graftlint_concurrency.py",
                   "test_lockrt.py", "test_trace_invariants.py",
                   "test_transfer_guard.py", "test_memplan.py",
                   "test_numerics.py")


def test_analysis_gates_exist_and_stay_tier1():
    for fname in _ANALYSIS_GATES:
        path = os.path.join(_TESTS, fname)
        assert os.path.exists(path), f"analysis gate {fname} is missing"
        src = open(path).read()
        tests = list(_iter_tests(ast.parse(src)))
        assert tests, f"{fname} defines no tests"
        slow = [node.name for node, class_slow in tests
                if _is_slow_marked(node, class_slow)]
        assert not slow, (
            "graftlint gates must be tier-1/CPU-safe, never @slow "
            f"(they ARE the fast regression fence): {fname}::{slow}")


# chaos-test gate (ISSUE 3): the fault-injection tests ARE the permanent
# regression harness for the recovery paths (watchdog, finite guard,
# rollback, ckpt retry) — and for PRs 1-2's hot-path guarantees holding
# UNDER injected faults.  Like the analysis gates, they only guard if
# they run on every default `pytest`: never @slow, never vanished.
_CHAOS_GATES = ("test_resilience.py",)


def test_chaos_gates_exist_and_stay_tier1():
    for fname in _CHAOS_GATES:
        path = os.path.join(_TESTS, fname)
        assert os.path.exists(path), f"chaos gate {fname} is missing"
        src = open(path).read()
        tests = list(_iter_tests(ast.parse(src)))
        assert tests, f"{fname} defines no tests"
        slow = [node.name for node, class_slow in tests
                if _is_slow_marked(node, class_slow)]
        assert not slow, (
            "chaos tests must be tier-1/CPU-safe, never @slow (they are "
            f"the fault-path regression fence): {fname}::{slow}")


# serving gates (ISSUE 4): the online-serving subsystem's tests — engine
# bucket ladder, batcher deadline semantics, export round-trip, the
# served-vs-offline parity pin, and the serve_bench smoke — are the
# regression fence for the request path.  Same rule as the analysis and
# chaos gates: tier-1, never @slow, never vanished.
_SERVING_GATES = ("test_serving.py", "test_serve_batcher.py",
                  "test_export.py", "test_serve_bench.py")


def test_serving_gates_exist_and_stay_tier1():
    for fname in _SERVING_GATES:
        path = os.path.join(_TESTS, fname)
        assert os.path.exists(path), f"serving gate {fname} is missing"
        src = open(path).read()
        tests = list(_iter_tests(ast.parse(src)))
        assert tests, f"{fname} defines no tests"
        slow = [node.name for node, class_slow in tests
                if _is_slow_marked(node, class_slow)]
        assert not slow, (
            "serving tests must be tier-1/CPU-safe, never @slow (they "
            f"are the request-path regression fence): {fname}::{slow}")


# serving-chaos gate (ISSUE 10): the replica-pool fault-injection tests
# — per-site survival (raise/hang/dead), quarantine-then-recovery,
# hedge determinism, shed-never-hangs, the HTTP error contract and the
# closed-loop chaos bench — are the permanent regression harness for
# serving-path failure isolation.  Same rule as every other gate:
# tier-1, never @slow, never vanished.
_SERVE_CHAOS_GATES = ("test_serve_chaos.py",)


def test_serve_chaos_gates_exist_and_stay_tier1():
    for fname in _SERVE_CHAOS_GATES:
        path = os.path.join(_TESTS, fname)
        assert os.path.exists(path), f"serving-chaos gate {fname} is missing"
        src = open(path).read()
        tests = list(_iter_tests(ast.parse(src)))
        assert tests, f"{fname} defines no tests"
        slow = [node.name for node, class_slow in tests
                if _is_slow_marked(node, class_slow)]
        assert not slow, (
            "serving chaos tests must be tier-1/CPU-safe, never @slow "
            "(they are the serving failure-isolation regression fence): "
            f"{fname}::{slow}")


# observability gates (ISSUE 5; ISSUE 9 added the attribution tier —
# goodput ledger, live MFU, anomaly->capture, pod aggregation): the obs
# subsystem's tests — registry thread-safety with exact counts, the
# Prometheus exposition golden, the obs_report regression gate, the
# instrumented-train-run event stream, and the ledger/capture
# acceptance runs — are the telemetry regression fence.  Same rule as
# the analysis/chaos/serving gates: tier-1, never @slow, never
# vanished.
_OBS_GATES = ("test_obs.py", "test_goodput.py")


def test_obs_gates_exist_and_stay_tier1():
    for fname in _OBS_GATES:
        path = os.path.join(_TESTS, fname)
        assert os.path.exists(path), f"obs gate {fname} is missing"
        src = open(path).read()
        tests = list(_iter_tests(ast.parse(src)))
        assert tests, f"{fname} defines no tests"
        slow = [node.name for node, class_slow in tests
                if _is_slow_marked(node, class_slow)]
        assert not slow, (
            "obs tests must be tier-1/CPU-safe, never @slow (they are "
            f"the telemetry regression fence): {fname}::{slow}")


# 2-D mesh gates (ISSUE 6): the FSDP sharding-map unit gates and the
# mesh-layout parity / zero-recompile / checkpoint-resharding /
# per-shard-byte-accounting tests are the regression fence for the
# pod-scale (data, model) training layout.  Same rule as every other
# subsystem gate: tier-1, never @slow, never vanished.
_MESH2D_GATES = ("test_sharding_map.py", "test_train_2d.py")


def test_mesh2d_gates_exist_and_stay_tier1():
    for fname in _MESH2D_GATES:
        path = os.path.join(_TESTS, fname)
        assert os.path.exists(path), f"2-D mesh gate {fname} is missing"
        src = open(path).read()
        tests = list(_iter_tests(ast.parse(src)))
        assert tests, f"{fname} defines no tests"
        slow = [node.name for node, class_slow in tests
                if _is_slow_marked(node, class_slow)]
        assert not slow, (
            "2-D mesh tests must be tier-1/CPU-safe, never @slow (they "
            f"are the pod-scale-layout regression fence): {fname}::{slow}")


# memory-efficient loss gates (ISSUE 12): the chunked MIL-NCE parity
# suite — dense-vs-chunked value/grad parity across backends and mesh
# layouts, plus the 2-optimizer-step train parity pins — is the
# regression fence for the streaming loss path.  Same rule as every
# other subsystem gate: tier-1, never @slow, never vanished.
_MEMLOSS_GATES = ("test_milnce_chunked.py",)


def test_memloss_gates_exist_and_stay_tier1():
    for fname in _MEMLOSS_GATES:
        path = os.path.join(_TESTS, fname)
        assert os.path.exists(path), f"mem-loss gate {fname} is missing"
        src = open(path).read()
        tests = list(_iter_tests(ast.parse(src)))
        assert tests, f"{fname} defines no tests"
        slow = [node.name for node, class_slow in tests
                if _is_slow_marked(node, class_slow)]
        assert not slow, (
            "chunked MIL-NCE tests must be tier-1/CPU-safe, never @slow "
            "(they are the memory-efficient-loss regression fence): "
            f"{fname}::{slow}")


# live-index + SLO-tier gates (ISSUE 14): the generation-swap parity
# pin, swap-failure chaos, snapshot round trip, the ingest-while-query
# hammer, the tier admission units and the two-tier chaos bench are the
# regression fence for the online-ingest serving path.  Same rule as
# every other subsystem gate: tier-1, never @slow, never vanished.
_LIVE_INDEX_GATES = ("test_live_index.py", "test_serve_tiers.py")


def test_live_index_gates_exist_and_stay_tier1():
    for fname in _LIVE_INDEX_GATES:
        path = os.path.join(_TESTS, fname)
        assert os.path.exists(path), f"live-index gate {fname} is missing"
        src = open(path).read()
        tests = list(_iter_tests(ast.parse(src)))
        assert tests, f"{fname} defines no tests"
        slow = [node.name for node, class_slow in tests
                if _is_slow_marked(node, class_slow)]
        assert not slow, (
            "live-index tests must be tier-1/CPU-safe, never @slow "
            "(they are the online-ingest regression fence): "
            f"{fname}::{slow}")


# curriculum gates (ISSUE 16): the staged-schedule grammar + plan
# simulator (including the resume_batch_offset / stop_save_label
# equivalence the flat path rides on), the checkpoint-compatible stage
# transitions, the pre-flight refusal and the goodput stage_switch
# attribution are the regression fence for curriculum training.  Same
# rule as every other subsystem gate: tier-1, never @slow, never
# vanished.
_CURRICULUM_GATES = ("test_curriculum.py",)


def test_curriculum_gates_exist_and_stay_tier1():
    for fname in _CURRICULUM_GATES:
        path = os.path.join(_TESTS, fname)
        assert os.path.exists(path), f"curriculum gate {fname} is missing"
        src = open(path).read()
        tests = list(_iter_tests(ast.parse(src)))
        assert tests, f"{fname} defines no tests"
        slow = [node.name for node, class_slow in tests
                if _is_slow_marked(node, class_slow)]
        assert not slow, (
            "curriculum tests must be tier-1/CPU-safe, never @slow "
            "(they are the staged-training regression fence): "
            f"{fname}::{slow}")


# edge-tier gates (ISSUE 19): the quantized-export bit-exact
# round-trip, the recall@10 degradation budgets (int8 + distilled
# student vs f32), strict class-pinned pool routing and the NUMERICS.md
# verdict parser are the regression fence for the edge serving tier.
# Same rule as every other subsystem gate: tier-1, never @slow, never
# vanished.
_QUANT_GATES = ("test_quant.py",)


def test_quant_gates_exist_and_stay_tier1():
    for fname in _QUANT_GATES:
        path = os.path.join(_TESTS, fname)
        assert os.path.exists(path), f"edge-tier gate {fname} is missing"
        src = open(path).read()
        tests = list(_iter_tests(ast.parse(src)))
        assert tests, f"{fname} defines no tests"
        slow = [node.name for node, class_slow in tests
                if _is_slow_marked(node, class_slow)]
        assert not slow, (
            "edge-tier tests must be tier-1/CPU-safe, never @slow "
            "(they are the quantized-serving regression fence): "
            f"{fname}::{slow}")


# elastic gates (ISSUE 20): the drain -> cross-topology-resume chaos
# chain (8-way -> 4x2 -> 4-way with loss-trajectory continuity), the
# stamp refusals, the drained-save atomicity regression and the
# straggler policy are the regression fence for elastic pod training.
# Same rule as every other subsystem gate: tier-1, never @slow, never
# vanished.
_ELASTIC_GATES = ("test_elastic.py",)


def test_elastic_gates_exist_and_stay_tier1():
    for fname in _ELASTIC_GATES:
        path = os.path.join(_TESTS, fname)
        assert os.path.exists(path), f"elastic gate {fname} is missing"
        src = open(path).read()
        tests = list(_iter_tests(ast.parse(src)))
        assert tests, f"{fname} defines no tests"
        slow = [node.name for node, class_slow in tests
                if _is_slow_marked(node, class_slow)]
        assert not slow, (
            "elastic tests must be tier-1/CPU-safe, never @slow "
            "(they are the preemption/topology-change regression fence): "
            f"{fname}::{slow}")


def test_fast_child_exemptions_stay_real():
    """Every _FAST_CHILD_EXEMPT entry must name a test that still
    exists — a stale exemption is a hole the audit thinks it covers."""
    for entry in _FAST_CHILD_EXEMPT:
        fname, _, test_name = entry.partition("::")
        path = os.path.join(_TESTS, fname)
        assert os.path.exists(path), f"exemption names missing file {fname}"
        names = {node.name for node, _ in
                 _iter_tests(ast.parse(open(path).read()))}
        assert test_name in names, f"exemption names missing test {entry}"


def test_autotune_artifact_carries_generator_key():
    """The JSON impl-map artifact can't carry a markdown header; its
    'generator' key is the same contract."""
    path = os.path.join(_REPO, "build", "impl_map.json")
    if not os.path.exists(path):
        return
    art = json.load(open(path))
    assert art["generator"].startswith("scripts/stage_probe.py")
