"""``expert_overhead_share.serve`` on runs made by hand."""

import pytest

from benchmarks import harness

NAME = "expert_overhead_share.serve"


def read(extra):
    run = harness.RunRecord(cell=None, peaks=None, events=[], window_s=10.0,
                            extra=extra)
    return harness.layer_metric_module(NAME).read(run)


def scopes(moe, kernels):
    inside = {"text_hybrid/mamba": 1.6, "text_hybrid/moe": moe,
              "grouped_matmul": kernels, "*": 2.9}
    return {"scope_seconds": {"inside": inside,
                              "whole": {k: v * 1.1 for k, v in inside.items()},
                              "ops": {}}}


def test_the_expert_layers_time_that_is_not_their_kernels():
    """The parent of PR 33 by the same reader (PERF.md section 5, PR 32's
    traces): (1,005 - 295) / 1,005."""
    assert read(scopes(1.005, 0.295)) == pytest.approx(70.647, abs=1e-3)
    assert read(scopes(0.600, 0.300)) == pytest.approx(50.0)


def test_a_run_with_no_scopes_reads_none():
    """An untraced run, a tower whose program has no such scope (the
    other cells), a trace the driver could not reduce."""
    assert read({}) is None
    assert read({"scope_seconds": None}) is None
    assert read(scopes(0.0, 0.0)) is None
    no_moe = scopes(1.0, 0.3)
    del no_moe["scope_seconds"]["inside"]["text_hybrid/moe"]
    assert read(no_moe) is None


def test_the_metric_is_in_the_benchmark_as_the_issue_names_it():
    bench = harness.load_benchmark()
    assert bench["per_layer"][-1]["name"] == NAME   # appended, nothing moved
    mod = harness.layer_metric_module(NAME)
    assert bench["per_layer"][-1] == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "model",
        "moves": "queries_per_s", "workloads": ["query-step-granite4h-c32"]}
    assert (mod.UNIT, mod.SOURCE, mod.LAYER, mod.MOVES) == (
        "%", "device_trace", "model", "queries_per_s")
