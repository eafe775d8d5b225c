"""``scatter_overlap_share.serve`` on records made by hand, and in the tiny
query cell on the CPU: where a pass's scatter ran, by its rows."""

import pytest

from benchmarks import harness
from benchmarks.tests.conftest import run_cell

NAME = "scatter_overlap_share.serve"


def read(events):
    run = harness.RunRecord(cell=None, peaks=None, events=events,
                            window_s=10.0)
    return harness.layer_metric_module(NAME).read(run)


def a_pass(rows, rode=None, **extra):
    record = {"kind": "span", "name": "topk.flush", "batcher": "topk",
              "mono": 2.0, "rows": rows, "bucket": 64, "dur_ms": 8.2,
              "chained_rows": 0, **extra}
    if rode is not None:
        record["rode"] = rode
    return record


def test_the_rows_that_rode_a_later_program_over_all_pass_rows():
    events = [a_pass(30, "topk"), a_pass(26, "text"), a_pass(4, "none"),
              {"kind": "span", "name": "batcher.flush", "batcher": "text",
               "mono": 1.0, "rows": 12, "rode": "topk"},      # not a pass
              {"kind": "span", "name": "dispatch", "site": "index.topk",
               "mono": 3.0, "rows": 30, "overlap_rows": 26}]  # not read
    assert read(events) == pytest.approx(100.0 * 56 / 60)


def test_records_without_the_attribute_are_none():
    """The parent commit's records, and those of a service that scatters
    every pass at once (no device times): nothing to read, no raise."""
    parent = [a_pass(52), a_pass(51, error="RuntimeError"),
              {"kind": "span", "name": "topk.flush", "mono": 1.0},
              {"kind": "event", "name": "worker.turn", "batcher": "topk",
               "rows": 52, "scatter_ms": 1.2},
              {"kind": "span", "name": "batcher.flush", "rows": 11}]
    assert read(parent) is None
    assert read([]) is None


def test_a_window_of_scatters_at_once_reads_zero():
    assert read([a_pass(1, "none"), a_pass(2, "none")]) == 0.0


def test_the_metric_is_in_the_benchmark_under_its_name():
    entries = {m["name"]: m for m in harness.load_benchmark()["per_layer"]}
    mod = harness.layer_metric_module(NAME)
    assert entries[NAME] == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_span", "layer": "serving",
        "moves": "queries_per_s"}
    assert (mod.UNIT, mod.SOURCE, mod.LAYER, mod.MOVES) == (
        "%", "program_span", "serving", "queries_per_s")


def test_tiny_query_cell_says_where_each_pass_scattered(bench, bench_dir,
                                                         tmp_path):
    result, out = run_cell(bench, bench_dir, "tiny-query", tmp_path,
                           trace=True, seed=4100000077)
    assert result["correct"], result["compared"]
    events = out["record"].events
    passes = [e for e in events if e.get("name") == "topk.flush"]
    assert passes and all(e["rode"] in ("topk", "text", "none")
                          for e in passes)
    share = result["metrics"][NAME]["value"]
    assert 0.0 <= share <= 100.0
    # a pass whose scatter rode a program is named on that program's hold
    overlapped = sum(e.get("overlap_rows", 0) for e in events
                     if e.get("name") == "dispatch")
    rode = sum(e["rows"] for e in passes if e["rode"] != "none")
    assert abs(overlapped - rode) <= max(e["rows"] for e in passes)
    # every text dispatch is still followed by a scan before the next one
    sites = [e["site"] for e in events if e.get("name") == "dispatch"
             and e.get("site") in ("engine.text", "index.topk")]
    assert "engine.text,engine.text" not in ",".join(sites)
