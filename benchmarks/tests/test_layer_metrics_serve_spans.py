"""The six readers of the serving path's own spans (``dispatch``,
``query``, ``batcher.flush.queue_wait_ms``) on records made by hand, and
in the tiny query cell traced on the CPU beside the readers that were
there."""

import pytest

from benchmarks import harness
from benchmarks.tests.conftest import run_cell

NEW = ("answer_gap_max_ms.serve", "batcher_wait_ms_p50.serve",
       "flush_lock_wait_ms_p50.serve", "topk_lock_wait_ms_p50.serve",
       "topk_hold_ms_p50.serve", "dispatch_lock_free_share.serve")


def read(name, events, window_s=10.0):
    run = harness.RunRecord(cell=None, peaks=None, events=events,
                            window_s=window_s)
    return harness.layer_metric_module(name).read(run)


def query(mono):
    return {"kind": "span", "name": "query", "mono": mono, "dur_ms": 5.0}


def dispatch(site, mono, hold_ms, lock_wait_ms=1.0):
    return {"kind": "span", "name": "dispatch", "site": site, "mono": mono,
            "hold_ms": hold_ms, "lock_wait_ms": lock_wait_ms}


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_is_none(name):
    """A program without the spans (the parent commit) gives records the
    readers find nothing in: None, and the line leaves the metric out."""
    old = [{"kind": "span", "name": "batcher.flush", "mono": 1.0,
            "rows": 3, "dur_ms": 2.0},
           {"kind": "event", "name": "anomaly", "mono": 2.0}]
    assert read(name, old) is None
    assert read(name, []) is None


def test_every_new_metric_is_in_the_benchmark_as_the_issue_names_it():
    entries = {m["name"]: m for m in harness.load_benchmark()["per_layer"]}
    for name in NEW:
        mod = harness.layer_metric_module(name)
        assert entries[name] == {
            "name": name, "unit": mod.UNIT, "better": "lower",
            "source": mod.SOURCE, "layer": mod.LAYER, "moves": mod.MOVES}
        assert mod.SOURCE == "program_span" and mod.LAYER == "serving"


def test_answer_gap_inside_the_window():
    events = [query(100.0 + 0.1 * i) for i in range(50)]       # to 104.9
    events += [query(107.4 + 0.1 * i) for i in range(26)]      # to 109.9
    assert read("answer_gap_max_ms.serve", events) == pytest.approx(2500.0)


def test_answer_gap_at_the_windows_edge():
    """Answers every 100 ms for 6 s of a 10 s window: nobody was answered
    for the other 4 s, wherever in the window they lie."""
    events = [query(100.0 + 0.1 * i) for i in range(61)]
    assert read("answer_gap_max_ms.serve", events) == pytest.approx(4000.0)
    # an event record of that name, or another span, is no answer
    events += [{"kind": "event", "name": "query", "mono": 109.0},
               {"kind": "span", "name": "dispatch", "mono": 109.5}]
    assert read("answer_gap_max_ms.serve", events) == pytest.approx(4000.0)


def test_batcher_wait_is_the_median_queue_wait_of_flushes():
    events = [{"kind": "span", "name": "batcher.flush", "mono": float(i),
               "queue_wait_ms": w, "rows": 2, "dur_ms": 1.0}
              for i, w in enumerate((500.0, 5.0, 560.0))]
    events.append({"kind": "event", "name": "batcher.flush", "mono": 9.0,
                   "queue_wait_ms": 580.0, "dur_ms": 1.0})    # pipelined
    assert read("batcher_wait_ms_p50.serve", events) == 530.0


def test_lock_waits_and_holds_are_read_by_site():
    events = [dispatch("engine.text", 1.0, 0.5, lock_wait_ms=550.0),
              dispatch("engine.text", 2.0, 0.5, lock_wait_ms=540.0),
              dispatch("engine.text", 3.0, 0.5, lock_wait_ms=560.0),
              dispatch("index.topk", 4.0, 12.0, lock_wait_ms=400.0),
              dispatch("index.topk", 5.0, 13.0, lock_wait_ms=300.0),
              dispatch("index.upload", 6.0, 900.0, lock_wait_ms=0.1)]
    assert read("flush_lock_wait_ms_p50.serve", events) == 550.0
    assert read("topk_lock_wait_ms_p50.serve", events) == 350.0
    assert read("topk_hold_ms_p50.serve", events) == 12.5


def test_lock_free_share_sums_holds_that_do_not_overlap():
    events = [dispatch("index.topk", 100.0 + i, 900.0) for i in range(10)]
    assert read("dispatch_lock_free_share.serve", events) \
        == pytest.approx(10.0)


def test_lock_free_share_counts_overlapping_holds_once():
    """Two engines with a lock each (a replica pool) hold at the same
    time: 2 x 10 x 900 ms of hold in a 10 s window is not -80% free."""
    events = []
    for i in range(10):
        events.append(dispatch("engine.text", 100.0 + i, 900.0))
        events.append(dispatch("engine.text", 100.1 + i, 900.0))
    free = read("dispatch_lock_free_share.serve", events)
    assert free == pytest.approx(0.0, abs=1e-6)      # 10 x 1.0 s covered


def test_traced_query_cell_reports_the_six_beside_the_rest(bench, bench_dir,
                                                            tmp_path):
    result, out = run_cell(bench, bench_dir, "tiny-query", tmp_path,
                           trace=True, seed=3000000023)
    assert result["correct"], result["compared"]
    got = result["metrics"]
    assert {"flush_rows_mean.serve", "flush_ms_p50.serve", "query_mfu",
            "device_idle_share.serve"} <= set(got)
    assert set(NEW) <= set(got)
    for name in NEW:
        assert got[name]["value"] == got[name]["value"]      # a number
        assert abs(got[name]["value"]) < 1e7
    window_ms = 2000.0
    assert 0 < got["answer_gap_max_ms.serve"]["value"] < window_ms
    assert 0 <= got["dispatch_lock_free_share.serve"]["value"] <= 100
    assert 0 < got["topk_hold_ms_p50.serve"]["value"] < window_ms
    # a flush waits for the lock, a row for its flush
    assert got["flush_lock_wait_ms_p50.serve"]["value"] \
        <= got["flush_ms_p50.serve"]["value"]
    assert got["batcher_wait_ms_p50.serve"]["value"] > 0
