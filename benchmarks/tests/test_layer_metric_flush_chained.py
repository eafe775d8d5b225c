"""``flush_chained_share.serve`` on records made by hand, and in the tiny
query cell on the CPU: what the text batcher flushes rides the pass right
after its flush."""

import pytest

from benchmarks import harness
from benchmarks.tests.conftest import run_cell

NAME = "flush_chained_share.serve"


def read(events):
    run = harness.RunRecord(cell=None, peaks=None, events=events,
                            window_s=10.0)
    return harness.layer_metric_module(NAME).read(run)


def flush(rows):
    return {"kind": "span", "name": "batcher.flush", "batcher": "text",
            "mono": 1.0, "rows": rows, "bucket": 32, "dur_ms": 50.0}


def a_pass(rows, chained=None):
    record = {"kind": "span", "name": "topk.flush", "batcher": "topk",
              "mono": 2.0, "rows": rows, "bucket": 32, "dur_ms": 8.0}
    if chained is not None:
        record["chained_rows"] = chained
    return record


def test_the_chained_rows_over_the_flushed_rows():
    events = [flush(30), a_pass(32, 30),      # two hits rode along
              flush(20), a_pass(12, 12),      # a block of 8 was held over
              a_pass(9, 0),                   # ... and rode unchained
              {"kind": "span", "name": "dispatch", "site": "index.topk",
               "mono": 3.0, "rows": 32, "chained_rows": 99}]    # not read
    assert read(events) == pytest.approx(100.0 * 42 / 50)


def test_records_without_the_attribute_read_zero():
    """The parent commit's passes: every row went back through its caller."""
    assert read([flush(21), a_pass(22), flush(20), a_pass(21)]) == 0.0


def test_nothing_flushed_is_none():
    assert read([a_pass(5, 0)]) is None
    assert read([]) is None


def test_the_metric_is_in_the_benchmark_as_the_issue_names_it():
    entries = {m["name"]: m for m in harness.load_benchmark()["per_layer"]}
    mod = harness.layer_metric_module(NAME)
    assert entries[NAME] == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_span", "layer": "serving",
        "moves": "queries_per_s"}
    assert (mod.UNIT, mod.SOURCE, mod.LAYER, mod.MOVES) == (
        "%", "program_span", "serving", "queries_per_s")


def test_tiny_query_cell_chains_what_it_flushes(bench, bench_dir, tmp_path):
    result, out = run_cell(bench, bench_dir, "tiny-query", tmp_path,
                           trace=True, seed=3000000061)
    assert result["correct"], result["compared"]
    events = out["record"].events
    passes = [e for e in events if e.get("name") == "topk.flush"]
    assert passes and all("chained_rows" in e for e in passes)
    assert all(0 <= e["chained_rows"] <= e["rows"] for e in passes)
    # the window's first pass may rank what a flush before the window
    # embedded, and its last flush may be ranked after it
    top = max(e["bucket"] for e in events if e.get("name") == "batcher.flush")
    flushed = sum(e["rows"] for e in events
                  if e.get("name") == "batcher.flush")
    share = result["metrics"][NAME]["value"]
    assert 100.0 * (1 - top / flushed) <= share <= 100.0 * (1 + top / flushed)
    # every text dispatch is followed by a scan before the next one
    sites = [e["site"] for e in events if e.get("name") == "dispatch"
             and e.get("site") in ("engine.text", "index.topk")]
    assert "engine.text,engine.text" not in ",".join(sites)
