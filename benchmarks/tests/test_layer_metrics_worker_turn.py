"""The six readers of the device worker's turn (``worker.turn``) and of the
runtime watcher's beat (``runtime.beat``) on records made by hand, on the
parent's records, against their entries in ``BENCHMARK.json``, and in the
tiny query cell traced on the CPU."""

import pytest

from benchmarks import harness
from benchmarks.tests.conftest import run_cell

WORKER = ("worker_host_ms_p50.serve", "worker_scatter_us_per_row.serve",
          "worker_interp_wait_share.serve", "worker_cpu_share.serve")
BEAT = ("interp_wait_ms_mean.serve", "host_stall_max_ms.serve")
NEW = WORKER + BEAT
MOVES = dict.fromkeys(WORKER, "queries_per_s") | dict.fromkeys(
    BEAT, "query_p95_ms")
UNITS = dict(zip(NEW, ("ms", "us", "%", "%", "ms", "ms")))
PHASES = ("take", "prepare", "run", "scatter", "account")
# a kernel that accounts CPU time by the tick (the chip's host: 10 ms)
# reads a thread's CPU up to one tick above its wall time
TICK_MS = 10.5


def read(name, events, window_s=10.0):
    run = harness.RunRecord(cell=None, peaks=None, events=events,
                            window_s=window_s)
    return harness.layer_metric_module(name).read(run)


def turn(mono, rows=4, wall=(1.0, 2.0, 30.0, 4.0, 1.0),
         cpu=(0.5, 1.0, 3.0, 2.0, 0.5), sleep_ms=0.0, batcher="text"):
    """One ``worker.turn`` record: ``wall`` / ``cpu`` in the order of
    ``PHASES``."""
    rec = {"kind": "event", "name": "worker.turn", "mono": mono,
           "batcher": batcher, "rows": rows, "bucket": 8, "epoch": int(mono),
           "sleep_ms": sleep_ms, "dur_ms": sleep_ms + sum(wall)}
    for p, w, c in zip(PHASES, wall, cpu):
        rec[p + "_ms"], rec[p + "_cpu_ms"] = w, c
    return rec


def beat(mono, beats, late_mean_ms, late_max_ms):
    return {"kind": "event", "name": "runtime.beat", "mono": mono,
            "beats": beats, "late_mean_ms": late_mean_ms,
            "late_max_ms": late_max_ms, "proc_cpu_ms": 900.0,
            "dur_ms": 1000.0}


PARENT = [{"kind": "span", "name": "batcher.flush", "mono": 1.0, "rows": 3,
           "dur_ms": 2.0, "queue_wait_ms": 1.0},
          {"kind": "span", "name": "topk.flush", "mono": 1.5, "rows": 3,
           "dur_ms": 2.0, "chained_rows": 3},
          {"kind": "span", "name": "dispatch", "site": "index.topk",
           "mono": 1.4, "hold_ms": 1.0, "lock_wait_ms": 0.0},
          {"kind": "span", "name": "query", "mono": 1.6, "dur_ms": 5.0},
          {"kind": "event", "name": "runtime.gc", "mono": 2.0, "dur_ms": 7.0}]


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_is_none(name):
    """The parent's records have no turn and no beat: None, and the line
    leaves the metric out."""
    assert read(name, PARENT) is None
    assert read(name, []) is None


@pytest.mark.parametrize("name", NEW)
def test_the_entry_in_the_benchmark_is_its_readers(name):
    entries = {m["name"]: m for m in harness.load_benchmark()["per_layer"]}
    mod = harness.layer_metric_module(name)
    assert entries[name] == {
        "name": name, "unit": mod.UNIT, "better": "lower",
        "source": mod.SOURCE, "layer": mod.LAYER, "moves": mod.MOVES}
    assert (mod.UNIT, mod.MOVES) == (UNITS[name], MOVES[name])
    assert mod.SOURCE == "program_span" and mod.LAYER == "serving"


def test_the_six_were_appended_together_and_list_no_cells():
    """Pinned by name, not by place: a later PR appends after them."""
    per_layer = harness.load_benchmark()["per_layer"]
    names = [m["name"] for m in per_layer]
    at = names.index(NEW[0])
    assert names[at:at + 6] == list(NEW) and at >= 29      # after PR 34's
    assert all("workloads" not in m for m in per_layer[at:at + 6])


def test_host_time_is_the_median_of_a_turn_less_run_and_sleep():
    events = PARENT + [
        turn(1.0), turn(2.0, wall=(2.0, 2.0, 90.0, 10.0, 2.0)),
        turn(3.0, wall=(1.0, 1.0, 5.0, 1.0, 1.0), sleep_ms=400.0)]
    # 8, 16 and 4 ms: run and sleep are not the worker's host time
    assert read("worker_host_ms_p50.serve", events) == 8.0


def test_scatter_per_row_is_a_ratio_of_the_windows_sums():
    events = [turn(1.0, rows=4), turn(2.0, rows=60,
                                      wall=(1.0, 2.0, 30.0, 28.0, 1.0))]
    assert read("worker_scatter_us_per_row.serve", events) \
        == pytest.approx(1e3 * 32.0 / 64)
    assert read("worker_scatter_us_per_row.serve",
                [turn(1.0, rows=0)]) is None


def test_interp_wait_share_is_wall_less_cpu_outside_run():
    # wall 1+2+4+1 = 8, CPU 0.5+1+2+0.5 = 4: half of it the worker stood;
    # the run phase's own wait (for the device) does not count
    assert read("worker_interp_wait_share.serve", [turn(1.0), turn(2.0)]) \
        == pytest.approx(50.0)
    busy = turn(3.0, cpu=(1.0, 2.0, 3.0, 4.0, 1.0))
    assert read("worker_interp_wait_share.serve", [busy]) \
        == pytest.approx(0.0)
    # the two clocks differ by a hair: never below 0
    over = turn(4.0, cpu=(1.1, 2.0, 3.0, 4.0, 1.0))
    assert read("worker_interp_wait_share.serve", [over]) == 0.0


def test_cpu_share_is_every_phases_cpu_over_the_window():
    events = [turn(float(i)) for i in range(100)]       # 7 ms of CPU each
    assert read("worker_cpu_share.serve", events, window_s=10.0) \
        == pytest.approx(7.0)
    assert read("worker_cpu_share.serve", events, window_s=0.0) is None


def test_interpreter_wait_weighs_a_beat_by_its_wake_ups():
    events = PARENT + [beat(1.0, 50, 0.2, 1.0), beat(2.0, 25, 2.0, 160.0),
                       beat(3.0, 0, 0.0, 0.0)]
    assert read("interp_wait_ms_mean.serve", events) \
        == pytest.approx((50 * 0.2 + 25 * 2.0) / 75)
    assert read("host_stall_max_ms.serve", events) == 160.0


def test_traced_query_cell_reports_the_six_beside_the_rest(bench, bench_dir,
                                                            tmp_path):
    result, out = run_cell(bench, bench_dir, "tiny-query", tmp_path,
                           trace=True, seed=3700000043)
    assert result["correct"], result["compared"]
    got = result["metrics"]
    assert {"flush_ms_p50.serve", "dispatch_lock_free_share.serve",
            "answer_gap_max_ms.serve"} <= set(got)
    assert set(NEW) <= set(got)
    for name in NEW:
        assert got[name]["unit"] == UNITS[name]
        assert 0 <= got[name]["value"] < 1e6, name
    assert got["worker_interp_wait_share.serve"]["value"] <= 100.0
    assert got["worker_cpu_share.serve"]["value"] <= 105.0
    assert got["interp_wait_ms_mean.serve"]["value"] \
        <= got["host_stall_max_ms.serve"]["value"]
    # the window's turns account for the window, and each flush record
    # has the one turn of its epoch and batcher
    events = out["record"].events
    turns = [e for e in events if e.get("name") == "worker.turn"]
    assert sum(e["dur_ms"] for e in turns) / 1e3 == pytest.approx(
        out["record"].window_s, rel=0.1)
    keys = [(e["epoch"], e["batcher"]) for e in turns]
    assert len(set(keys)) == len(keys)
    flushes = [e for e in events
               if e.get("name") in ("batcher.flush", "topk.flush")]
    inner = sorted((e["epoch"], e["batcher"]) for e in flushes)[:-1]
    assert flushes and set(inner) <= set(keys)
    for e in events:
        if e.get("name") == "dispatch":
            assert 0 <= e["cpu_ms"] <= e["hold_ms"] + TICK_MS
