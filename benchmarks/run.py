"""The benchmark's command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: it takes the chips itself and starts no child.  It finds the
cell's file by the workload's name (``benchmarks/workloads/<cell>.json``),
the configuration and the traffic by the names in ``BENCHMARK.json``, the
driver by the name in the cell's file (``benchmarks/drivers/<driver>.py``)
and each per-layer metric by its own name
(``benchmarks/layer_metrics/<name>.py``).  It refuses to measure unless
JAX finds a TPU with as many chips as the cell asks for: it then exits
with another code than 0 and prints no result.  The last line of standard
output is the result; the numbers that decided ``correct`` are its last
key and the last lines of standard error.
"""

from __future__ import annotations

import time

T_START = time.monotonic()      # set-up is counted from here

import argparse     # noqa: E402
import os           # noqa: E402
import shutil       # noqa: E402
import sys          # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(CHECKOUT, "milnce_tpu")):
        print("benchmarks/run.py: no milnce_tpu/ beside benchmarks/: there "
              "is no system to measure here", file=sys.stderr)
        return 2
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.chdir(CHECKOUT)          # the program's cache is build/jax_cache here

    from benchmarks import harness, peaks

    bench = harness.load_benchmark(CHECKOUT)
    cell = harness.load_cell(bench, args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"benchmarks/run.py: no TPU (jax found platform="
              f"{devices[0].platform!r}): refusing to measure",
              file=sys.stderr)
        return 3
    if len(devices) < cell.chips:
        print(f"benchmarks/run.py: {args.workload} asks for {cell.chips} "
              f"chips, jax sees {len(devices)}", file=sys.stderr)
        return 3
    devices = devices[:cell.chips]
    chip_peaks = peaks.peaks_for(devices[0].device_kind)

    work = os.path.join(CHECKOUT, "build", "bench", cell.name)
    driver = harness.load_driver(cell.driver, cell.bench_dir)
    try:
        out = driver.run(cell, seed=args.seed, seconds=args.seconds,
                         trace=bool(args.trace), work=work, platform="tpu",
                         t_start=T_START)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    result = harness.result_line(bench, cell, out, devices, chip_peaks,
                                 trace=bool(args.trace))
    harness.emit(result, out["compared"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
