"""What every cell shares: finding a cell's files by name, the record a
driver hands back, the reading of the per-layer metrics, and the result
line.  Drivers and per-layer metrics are found by name
(``benchmarks/drivers/<driver>.py``, ``benchmarks/layer_metrics/<name>.py``);
nothing here knows a cell, a configuration or a metric by name.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import re
import statistics
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_benchmark(checkout: str = CHECKOUT) -> dict:
    return load_json(os.path.join(checkout, "BENCHMARK.json"))


@dataclasses.dataclass
class Cell:
    """One workload with the files its names lead to."""
    name: str
    chips: int
    driver: str
    config_name: str
    config: dict            # benchmarks/configs/<config>.json
    traffic: dict           # benchmarks/traffic/<traffic>.json
    limits: dict            # the numbers `correct` compares, with limits
    bench_dir: str = BENCH_DIR


def load_cell(bench: dict, workload: str, bench_dir: str = BENCH_DIR) -> Cell:
    entries = [w for w in bench["workloads"] if w["name"] == workload]
    if not entries:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    entry = entries[0]
    cfgs = [c for c in bench["configs"] if c["name"] == entry["config"]]
    if not cfgs:
        raise SystemExit(f"workload {workload!r} names no known config")
    cell_file = load_json(os.path.join(bench_dir, "workloads",
                                       f"{workload}.json"))
    config = load_json(os.path.join(bench_dir, "configs",
                                    os.path.basename(cfgs[0]["file"])))
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     f"{entry['traffic']}.json"))
    if cell_file["chips"] != entry["chips"]:
        raise SystemExit(f"{workload}: chips differ between BENCHMARK.json "
                         "and the cell's file")
    return Cell(name=workload, chips=int(entry["chips"]),
                driver=cell_file["driver"], config_name=entry["config"],
                config=config, traffic=traffic,
                limits=cell_file.get("limits", {}), bench_dir=bench_dir)


def group_flags(config: dict, groups) -> list:
    """``--<group>.<key> <value>`` for every key of the configuration's
    ``groups``: the program's command line from the file of sizes."""
    argv = []
    for group in groups:
        for key, value in config.get(group, {}).items():
            argv += [f"--{group}.{key}", flag(value)]
    return argv


def flag(value) -> str:
    return str(value).lower() if isinstance(value, bool) else str(value)


def metric_names_for(bench: dict, cell: Cell, group: str) -> list:
    """The metrics of ``group`` ('end_to_end' | 'per_layer') this cell
    reports.  One with a ``workloads`` key: the cells it lists.  An
    end-to-end metric without: every cell.  A per-layer metric without:
    every cell that reports the end-to-end metric it ``moves``."""
    end_to_end = [m["name"] for m in bench["end_to_end"]
                  if "workloads" not in m or cell.name in m["workloads"]]
    if group == "end_to_end":
        return end_to_end
    return [m["name"] for m in bench["per_layer"]
            if (cell.name in m["workloads"] if "workloads" in m
                else m["moves"] in end_to_end)]


def _load_file(kind: str, name: str, bench_dir: str):
    """The module ``<bench_dir>/<kind>/<name>.py``, found by its name."""
    mod = re.sub(r"[^A-Za-z0-9_]", "_", name)
    path = os.path.join(bench_dir, kind, f"{mod}.py")
    if not os.path.exists(path):
        raise SystemExit(f"no {path} for {name!r}")
    if os.path.abspath(bench_dir) == BENCH_DIR:
        return importlib.import_module(f"benchmarks.{kind}.{mod}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_{mod}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_driver(name: str, bench_dir: str = BENCH_DIR):
    """``<bench_dir>/drivers/<name>.py``."""
    return _load_file("drivers", name, bench_dir)


def layer_metric_module(name: str, bench_dir: str = BENCH_DIR):
    """``<bench_dir>/layer_metrics/<name with . and - as _>.py``."""
    return _load_file("layer_metrics", name, bench_dir)


@dataclasses.dataclass
class RunRecord:
    """What a driver hands to the per-layer readers (``read(run)``)."""
    cell: Cell
    peaks: dict                     # benchmarks/peaks.py entry
    events: list                    # span/event records inside the window
    window_s: float                 # the measured window, host clock
    trace: object = None            # trace_reduce.Reduction of a traced run
    traced_work: float = 0.0        # clips (train) / queries (serve) that
    #                                 finished inside the traced window
    extra: dict = dataclasses.field(default_factory=dict)


def read_layer_metrics(bench: dict, run: RunRecord) -> dict:
    """name -> {"value", "unit"} for every per-layer metric of the cell
    whose reader finds something to read (None = left out)."""
    out = {}
    units = {m["name"]: m["unit"] for m in bench["per_layer"]}
    for name in metric_names_for(bench, run.cell, "per_layer"):
        value = layer_metric_module(name, run.cell.bench_dir).read(run)
        if value is not None:
            out[name] = {"value": float(value), "unit": units[name]}
    return out


def quantile_spread(values) -> float:
    """(Q3 - Q1) / median, by ``statistics.quantiles(values, n=4)``."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of a non-empty list."""
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * q // 100))
    return ordered[int(rank) - 1]


def judge(compared: dict) -> bool:
    """``compared``: name -> {"value", "limit"}; correct iff every value
    is a number at or under its limit."""
    ok = bool(compared)
    for item in compared.values():
        v = item["value"]
        ok = ok and v is not None and v == v and v <= item["limit"]
    return ok


def result_line(bench: dict, cell: Cell, out: dict, devices, chip_peaks,
                trace: bool) -> dict:
    """The result of one run from what its driver handed back: with
    ``trace`` the cell's per-layer metrics (and the breakdown), without
    its end-to-end metrics."""
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
    end_to_end = {n: {"value": float(out["metrics"][n]), "unit": units[n]}
                  for n in metric_names_for(bench, cell, "end_to_end")}
    record = out["record"]
    record.peaks = chip_peaks
    result = {"correct": judge(out["compared"]),
              "attempted": int(out["attempted"]),
              "failed": int(out["failed"])}
    if trace:
        result["metrics"] = read_layer_metrics(bench, record)
        result["end_to_end"] = end_to_end
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in record.trace.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in record.trace.top_gaps(10)]}
    else:
        result["metrics"] = end_to_end
    result["device"] = device_block(devices, out["peak_bytes"],
                                    record.trace if trace else None)
    result["notes"] = out.get("notes", {})
    return result


def device_block(devices, peak_bytes: int, trace=None) -> dict:
    d = {"platform": devices[0].platform, "kind": devices[0].device_kind,
         "count": len(devices), "memory_peak_bytes": int(peak_bytes)}
    if trace is not None:
        d["busy_s"] = trace.busy_s
        d["window_s"] = trace.window_s
    return d


def peak_bytes_in_use(devices) -> int:
    """The fullest chip's peak, from ``memory_stats()``: the larger of
    ``peak_bytes_in_use`` (live arrays: state, batches, an index) and
    ``peak_bytes_reserved`` (what the backend set aside for running
    programs' temporaries; on the v5e a train step that needs 14 GB reads
    1.7 GB in use and 13.9 GB reserved, PERF.md).  The statistics give no
    joint peak, so this is a lower bound of it.  0 where the backend
    reports none, as the CPU of the tests."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)),
                   int(stats.get("peak_bytes_reserved", 0)))
    return peak


def emit(result: dict, compared: dict) -> None:
    """The compared numbers as the last lines of standard error, and the
    result as the last line of standard output (``compared`` last)."""
    result = dict(result)
    result["compared"] = compared
    sys.stdout.flush()
    for name, item in compared.items():
        print(f"compared {name} = {item['value']!r} (limit {item['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
