#!/usr/bin/env python
"""graftlint CLI: JAX-aware static analysis + trace invariants.

Usage:
    python scripts/graft_lint.py                  # all passes, write LINT.md
    python scripts/graft_lint.py --check          # exit 1 on any finding
    python scripts/graft_lint.py --check --no-trace   # AST passes only
                                                      # (fast, no jax import)
    python scripts/graft_lint.py --no-concurrency # skip Pass 3 (GL010-012)
    python scripts/graft_lint.py --no-memplan     # skip Pass 4 (GL013-015)
    python scripts/graft_lint.py --no-numerics    # skip Pass 5 (GL016-018)
    python scripts/graft_lint.py milnce_tpu/train # explicit scope

Default scope is the ``milnce_tpu`` package — the library code that runs
on the hot path.  The measurement harnesses (bench.py, scripts/*_probe)
deliberately wall-clock-time things and are out of scope by default;
lint them explicitly when touching them.

The tier-1 gate (tests/test_graftlint.py) runs ``--check --no-trace`` as
a subprocess and the trace pass in-process, so a new finding fails the
suite, not just this tool.
"""

from __future__ import annotations

import argparse
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# Must happen before any jax import (the trace pass needs the hermetic
# multi-device CPU platform the tests use; see tests/conftest.py).
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from milnce_tpu.analysis.astlint import lint_paths_full  # noqa: E402
from milnce_tpu.analysis.report import render_report  # noqa: E402

DEFAULT_SCOPE = ["milnce_tpu"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="*", default=None,
                    help="files/dirs to lint (default: milnce_tpu)")
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero on any unsuppressed finding or "
                         "failed invariant")
    ap.add_argument("--no-trace", action="store_true",
                    help="skip the trace-invariant pass (no jax import)")
    ap.add_argument("--no-concurrency", action="store_true",
                    help="skip the concurrency pass (GL010-GL012 + the "
                         "lock-order graph); still jax-free either way")
    ap.add_argument("--no-memplan", action="store_true",
                    help="skip the static HBM planner pass (GL013-GL015 "
                         "peak/donation/contributor gates; implied by "
                         "--no-trace)")
    ap.add_argument("--no-numerics", action="store_true",
                    help="skip the numerics pass (GL016-GL018 dtype "
                         "census / cast-inventory / f32-residency gates; "
                         "implied by --no-trace)")
    ap.add_argument("--report", default=os.path.join(_REPO, "LINT.md"),
                    help="report path ('' to skip writing)")
    args = ap.parse_args(argv)

    os.chdir(_REPO)          # findings print repo-relative paths
    paths = args.paths or DEFAULT_SCOPE
    findings, lock_graph = lint_paths_full(
        paths, concurrency=not args.no_concurrency)
    active = [f for f in findings if not f.suppressed]
    for f in active:
        print(f.format())

    trace_results = None
    if not args.no_trace:
        # jax config must be applied before the backend initializes
        import jax

        jax.config.update("jax_platforms", "cpu")
        from milnce_tpu.utils.compile_cache import configure_compile_cache

        configure_compile_cache()
        from milnce_tpu.analysis.trace_invariants import run_trace_invariants

        trace_results = run_trace_invariants()
        for r in trace_results:
            print(r.format())

    mem_results = None
    if not args.no_trace and not args.no_memplan:
        # Pass 4 rides on the same hermetic mesh + cached tiny setup the
        # trace pass just built, so it costs tracing, not model builds
        from milnce_tpu.analysis.memplan import run_memplan_checks

        mem_results = run_memplan_checks()
        for r in mem_results:
            print(r.format())

    numerics_results = None
    if not args.no_trace and not args.no_numerics:
        # Pass 5 audits the SAME traced programs Pass 4 just cached
        # (memplan._traced_entry), so it costs walks, not traces
        from milnce_tpu.analysis.numerics import run_numerics_checks

        numerics_results = run_numerics_checks()
        for r in numerics_results:
            print(r.format())

    if args.report:
        with open(args.report, "w") as fh:
            fh.write(render_report(findings, trace_results, paths,
                                   lock_graph, mem_results,
                                   numerics_results))
        print(f"report: {args.report}")

    n_bad = (len(active) + sum(not r.ok for r in trace_results or [])
             + sum(not r.ok for r in mem_results or [])
             + sum(not r.ok for r in numerics_results or []))
    suppressed = sum(f.suppressed for f in findings)
    print(f"graftlint: {len(active)} finding(s), {suppressed} audited "
          f"suppression(s)"
          + ("" if trace_results is None else
             f", {sum(not r.ok for r in trace_results)} invariant "
             f"failure(s)")
          + ("" if mem_results is None else
             f", {sum(not r.ok for r in mem_results)} memplan "
             f"failure(s)")
          + ("" if numerics_results is None else
             f", {sum(not r.ok for r in numerics_results)} numerics "
             f"failure(s)"))
    return 1 if (args.check and n_bad) else 0


if __name__ == "__main__":
    raise SystemExit(main())
