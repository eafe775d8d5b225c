"""The harness is driven by data: cells, configurations, traffic mixes,
drivers and per-layer metrics are files found by name."""

import json
import os
import re
import subprocess
import sys

import pytest

from benchmarks import harness
from benchmarks.tests.conftest import CELLS, ROOT, overlay

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_json_keeps_the_contract():
    bench = harness.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks"]
    assert 1 <= bench["run_seconds"] <= 51
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(names)) == len(names)
    for entry in (bench["configs"] + bench["workloads"] + bench["end_to_end"]
                  + bench["per_layer"]):
        assert NAME.match(entry["name"]), entry["name"]
        for key in ("why", "layer", "source"):
            if key in entry:
                assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key]
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    cells = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(cells)) == len(cells)
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(cells) // 4)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_every_cell_finds_its_files_and_reports_what_it_must():
    bench = harness.load_benchmark()
    for w in bench["workloads"]:
        cell = harness.load_cell(bench, w["name"])
        assert cell.chips == w["chips"]
        assert cell.config["name"] == w["config"]
        harness.load_driver(cell.driver)
        e2e = harness.metric_names_for(bench, cell, "end_to_end")
        assert "setup_s" in e2e and len(e2e) >= 2
        layers = harness.metric_names_for(bench, cell, "per_layer")
        assert layers
        by_name = {m["name"]: m for m in bench["per_layer"]}
        for name in layers:
            module = harness.layer_metric_module(name)
            entry = by_name[name]
            assert (module.LAYER, module.UNIT, module.SOURCE, module.MOVES) \
                == (entry["layer"], entry["unit"], entry["source"],
                    entry["moves"]), name
            assert entry["moves"] in e2e
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        cfg = harness.load_json(os.path.join(ROOT, c["file"]))
        assert cfg["reduced"] == c["reduced"]


def test_a_new_cell_config_traffic_and_metric_are_files_not_edits(tmp_path):
    """What a later PR adds: a configuration, a traffic mix, a cell and a
    per-layer metric, each a new file plus an entry in BENCHMARK.json.
    No file under benchmarks/ changes, and the harness finds all four."""
    extra = tmp_path / "extra"
    for kind in ("workloads", "configs", "traffic", "layer_metrics"):
        (extra / kind).mkdir(parents=True)
    cfg = harness.load_json(os.path.join(CELLS, "configs",
                                         "tiny-s3dg.json"))
    cfg["name"] = "later-config"
    (extra / "configs" / "later-config.json").write_text(json.dumps(cfg))
    mix = harness.load_json(os.path.join(CELLS, "traffic",
                                         "tiny-closed-c4.json"))
    mix["callers"] = 7
    (extra / "traffic" / "later-mix.json").write_text(json.dumps(mix))
    (extra / "workloads" / "later-cell.json").write_text(json.dumps(
        {"name": "later-cell", "config": "later-config",
         "traffic": "later-mix", "driver": "serve", "chips": 1,
         "why": "added by a later PR", "limits": {"rank_gap": 0.5}}))
    (extra / "layer_metrics" / "cache_hits_later.py").write_text(
        'LAYER, UNIT, SOURCE = "serving", "hits", "program_counter"\n'
        'MOVES = "queries_per_s"\n\n\n'
        'def read(run):\n    return run.extra.get("cache_hits")\n')
    bench_dir = overlay(str(tmp_path / "benchdir"), str(extra))
    bench = harness.load_benchmark()
    bench["configs"].append({"name": "later-config", "source": "x",
                             "file": "benchmarks/configs/later-config.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "later-cell",
                               "config": "later-config",
                               "traffic": "later-mix", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "cache_hits.later", "unit": "hits",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "serving",
                               "moves": "queries_per_s",
                               "workloads": ["later-cell"]})
    cell = harness.load_cell(bench, "later-cell", bench_dir=bench_dir)
    assert cell.traffic["callers"] == 7 and cell.driver == "serve"
    assert cell.config["name"] == "later-config"
    assert harness.load_driver(cell.driver, bench_dir).run
    names = harness.metric_names_for(bench, cell, "per_layer")
    # its own metric, and every one that moves what the cell reports
    assert "cache_hits.later" in names and "query_mfu" in names
    run = harness.RunRecord(cell=cell, peaks={}, events=[], window_s=1.0,
                            extra={"cache_hits": 30})
    metrics = harness.read_layer_metrics(bench, run)
    # the new reader reads; readers that find nothing return nothing
    assert metrics == {"cache_hits.later": {"value": 30.0, "unit": "hits"}}
    other = harness.load_cell(bench, "query-text-c64", bench_dir=bench_dir)
    assert "cache_hits.later" not in harness.metric_names_for(
        bench, other, "per_layer")


def test_judge_percentile_spread_and_the_result_line(capsys):
    assert harness.judge({"a": {"value": 0.1, "limit": 0.2}})
    assert not harness.judge({"a": {"value": 0.3, "limit": 0.2}})
    assert not harness.judge({"a": {"value": float("nan"), "limit": 1.0}})
    assert not harness.judge({"a": {"value": float("inf"), "limit": 1.0}})
    assert not harness.judge({})
    assert harness.percentile(list(range(1, 101)), 95) == 95
    assert harness.percentile([5.0], 95) == 5.0
    assert harness.quantile_spread([10, 10, 10, 10, 10, 10]) == 0.0
    harness.emit({"correct": True, "metrics": {}},
                 {"gap": {"value": 0.5, "limit": 1.0}})
    out, err = capsys.readouterr()
    last = json.loads(out.strip().splitlines()[-1])
    assert list(last)[-1] == "compared"
    assert err.strip().splitlines()[-1] == "compared gap = 0.5 (limit 1.0)"


@pytest.mark.parametrize("where", ["no_chip", "bare_directory"])
def test_run_py_refuses_and_prints_no_result(tmp_path, where):
    """No accelerator: another exit code than 0 and no result; the same
    in a directory that holds only BENCHMARK.json and benchmarks/."""
    import shutil

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cwd = ROOT
    if where == "bare_directory":
        cwd = str(tmp_path)
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), cwd)
        shutil.copytree(os.path.join(ROOT, "benchmarks"),
                        os.path.join(cwd, "benchmarks"),
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "query-text-c64", "--seed", "3000000019", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
