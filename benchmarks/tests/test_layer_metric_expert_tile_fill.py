"""``expert_tile_fill.serve`` on records made by hand."""

import pytest

from benchmarks import harness

NAME = "expert_tile_fill.serve"


def read(events):
    run = harness.RunRecord(cell=None, peaks=None, events=events,
                            window_s=10.0)
    return harness.layer_metric_module(NAME).read(run)


def flush(pairs, tile_rows=None, site="engine.text"):
    record = {"kind": "span", "name": "dispatch", "site": site,
              "mono": 1.0, "rows": 30, "bucket": 32, "tokens": 400,
              "moe_pairs_held": pairs, "moe_expert_max": 40,
              "moe_pairs_total": 22400}
    if tile_rows is not None:
        record["moe_tile_rows"] = tile_rows
    return record


def test_the_windows_pairs_over_the_windows_tile_rows():
    events = [flush(1500, 7 * 13 * 128), flush(2900, 7 * 14 * 128),
              flush(99, 128, site="index.topk"),              # not read
              {"kind": "span", "name": "batcher.flush", "mono": 2.0,
               "moe_pairs_held": 5, "moe_tile_rows": 5}]      # not read
    assert read(events) == pytest.approx(
        100.0 * 4400 / (7 * 27 * 128))


def test_records_without_the_counter_read_none():
    """The parent commit's tower: three counters, no walk of its own."""
    assert read([flush(1500), flush(2900)]) is None
    assert read([]) is None
    assert read([flush(0, 0)]) is None


def test_the_metric_is_in_the_benchmark_as_the_issue_names_it():
    entries = {m["name"]: m for m in harness.load_benchmark()["per_layer"]}
    mod = harness.layer_metric_module(NAME)
    assert entries[NAME] == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_span", "layer": "kernels",
        "moves": "queries_per_s", "workloads": ["query-text-axk1-c64"]}
    assert (mod.UNIT, mod.SOURCE, mod.LAYER, mod.MOVES) == (
        "%", "program_span", "kernels", "queries_per_s")
