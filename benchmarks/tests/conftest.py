"""CPU rehearsals of the benchmark.  Run with
``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``; four virtual
devices for the ``chips: 4`` rehearsal are asked for here, before JAX
starts its backend."""

import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402

from benchmarks import harness  # noqa: E402

CELLS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "cells")
KINDS = ("workloads", "configs", "traffic", "layer_metrics", "drivers")


def overlay(dst: str, *sources: str) -> str:
    """A benchmark directory at ``dst``: the real one's files of every
    kind, then each of ``sources`` laid over it.  Nothing under
    ``benchmarks/`` is edited: the harness is pointed at the copy."""
    for kind in KINDS:
        os.makedirs(os.path.join(dst, kind), exist_ok=True)
        for src in (harness.BENCH_DIR,) + sources:
            folder = os.path.join(src, kind)
            if os.path.isdir(folder):
                for f in os.listdir(folder):
                    if os.path.isfile(os.path.join(folder, f)):
                        shutil.copy(os.path.join(folder, f),
                                    os.path.join(dst, kind, f))
    return dst


def tiny_benchmark() -> dict:
    """BENCHMARK.json with the rehearsal cells in place of the real ones
    (same metrics, same layers)."""
    bench = harness.load_benchmark()
    # the train cells' metrics are not in BENCHMARK.json yet: the entries
    # a later PR adds with them are kept beside the rehearsal cells
    extra = harness.load_json(os.path.join(CELLS, "train_entries.json"))
    bench["end_to_end"] = extra["end_to_end"] + bench["end_to_end"]
    bench["per_layer"] = extra["per_layer"] + bench["per_layer"]
    bench["configs"] = [{"name": "tiny-s3dg", "source": "config.py tiny",
                         "file": "benchmarks/configs/tiny-s3dg.json",
                         "reduced": [], "why": "rehearsal"}]
    bench["workloads"] = [
        {"name": "tiny-train", "config": "tiny-s3dg",
         "traffic": "tiny-feed", "chips": 1, "why": "rehearsal"},
        {"name": "tiny-train-dp4", "config": "tiny-s3dg",
         "traffic": "tiny-feed", "chips": 4, "why": "rehearsal"},
        {"name": "tiny-query", "config": "tiny-s3dg",
         "traffic": "tiny-closed-c4", "chips": 1, "why": "rehearsal"},
        {"name": "tiny-query-bulk", "config": "tiny-s3dg",
         "traffic": "tiny-bulk3-c4", "chips": 1, "why": "rehearsal"}]
    for m in bench["end_to_end"]:
        if m["name"] in ("queries_per_s", "query_p95_ms"):
            m["workloads"] = ["tiny-query", "tiny-query-bulk"]
    return bench


@pytest.fixture(scope="session")
def bench_dir(tmp_path_factory):
    return overlay(str(tmp_path_factory.mktemp("benchdir")), CELLS)


@pytest.fixture(scope="session")
def bench():
    return tiny_benchmark()


def run_cell(bench, bench_dir, name, tmp_path, *, trace=False, seconds=2.0,
             seed=3000000019, fault=None):
    """One rehearsal run through the cell's driver, as run.py makes it
    after its look for a chip -> (result line, driver output)."""
    import jax

    from benchmarks import peaks

    cell = harness.load_cell(bench, name, bench_dir=bench_dir)
    driver = harness.load_driver(cell.driver, bench_dir)
    out = driver.run(cell, seed=seed, seconds=seconds, trace=trace,
                     work=str(tmp_path / name), platform="cpu", fault=fault)
    result = harness.result_line(bench, cell, out,
                                 jax.devices()[:cell.chips],
                                 peaks.PEAKS["TPU v5 lite"], trace=trace)
    return result, out
