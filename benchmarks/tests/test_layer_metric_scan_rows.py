"""``scan_rows_mean.serve`` on records made by hand, and in the tiny query
cells on the CPU: 4 callers of one row share passes, and a caller's 3 rows
are never split over two."""

import pytest

from benchmarks import harness
from benchmarks.tests.conftest import run_cell

NAME = "scan_rows_mean.serve"


def read(events):
    run = harness.RunRecord(cell=None, peaks=None, events=events,
                            window_s=10.0)
    return harness.layer_metric_module(NAME).read(run)


def dispatch(site, rows, bucket=16):
    return {"kind": "span", "name": "dispatch", "site": site, "mono": 1.0,
            "rows": rows, "bucket": bucket, "hold_ms": 12.0,
            "lock_wait_ms": 0.1}


def test_the_mean_rows_of_the_scans_dispatches():
    events = [dispatch("index.topk", 40, 64), dispatch("index.topk", 24, 32),
              dispatch("index.topk", 2),
              dispatch("engine.text", 9),           # a text flush: not a scan
              dispatch("index.upload", 3_000_000),  # the index's upload
              {"kind": "span", "name": "topk.flush", "rows": 64, "mono": 2.0,
               "dur_ms": 13.0}]
    assert read(events) == pytest.approx(22.0)


def test_one_row_a_call_reads_one():
    """The records of a program in which every caller scans for itself (the
    parent commit): one row a dispatch."""
    assert read([dispatch("index.topk", 1) for _ in range(50)]) == 1.0


def test_nothing_to_read_is_none():
    old = [{"kind": "span", "name": "batcher.flush", "mono": 1.0, "rows": 3,
            "dur_ms": 2.0},
           {"kind": "span", "name": "dispatch", "site": "index.topk",
            "mono": 2.0, "hold_ms": 12.0}]           # no rows on the record
    assert read(old) is None
    assert read([]) is None


def test_the_metric_is_in_the_benchmark_as_the_issue_names_it():
    entries = {m["name"]: m for m in harness.load_benchmark()["per_layer"]}
    mod = harness.layer_metric_module(NAME)
    assert entries[NAME] == {
        "name": NAME, "unit": "rows", "better": "higher",
        "source": "program_span", "layer": "serving",
        "moves": "queries_per_s"}
    assert (mod.UNIT, mod.SOURCE, mod.LAYER, mod.MOVES) == (
        "rows", "program_span", "serving", "queries_per_s")
    assert harness.load_benchmark()["per_layer"][-1]["name"] == NAME


@pytest.mark.parametrize("workload,rows_per_call",
                         [("tiny-query", 1), ("tiny-query-bulk", 3)])
def test_tiny_query_cells_share_their_scans(bench, bench_dir, tmp_path,
                                            workload, rows_per_call):
    result, out = run_cell(bench, bench_dir, workload, tmp_path, trace=True,
                           seed=3000000029)
    assert result["correct"], result["compared"]
    mean = result["metrics"][NAME]["value"]
    scans = [e for e in out["record"].events
             if e.get("name") == "dispatch" and e.get("site") == "index.topk"]
    top = max(e["bucket"] for e in scans)
    assert rows_per_call <= mean <= top
    assert all(e["rows"] % rows_per_call == 0 for e in scans)
    # 4 closed-loop callers: some pass carried more than one call
    assert max(e["rows"] for e in scans) > rows_per_call
    # the coalescer's flushes carry their own name: what reads
    # ``batcher.flush`` still reads the text batcher alone
    events = out["record"].events
    assert {e["batcher"] for e in events
            if e.get("name") == "topk.flush"} == {"topk"}
    assert {e["batcher"] for e in events
            if e.get("name") == "batcher.flush"} == {"text"}
