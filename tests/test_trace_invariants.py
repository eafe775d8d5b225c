"""graftlint Pass 2 gates: jaxpr-level invariants over the hot-path entry
points, on the hermetic 8-virtual-device CPU mesh (tier-1 by design —
see ISSUE/ANALYSIS.md; the marker audit in test_suite_hygiene.py pins
these as NOT slow).

The positive test runs the full registered suite (train-step variants,
soft-DTW, retrieval embedders, conv-impl treedefs, double-call recompile
checks).  The negative tests plant each failure class and assert the
detector actually fires — an invariant checker that can't fail is
decoration.
"""

import jax
import jax.numpy as jnp
import numpy as np

from milnce_tpu.analysis.trace_invariants import (CheckResult,
                                                  collective_counts,
                                                  f64_sites,
                                                  run_trace_invariants,
                                                  _recompile_check)


def test_all_registered_entry_invariants_hold():
    results = run_trace_invariants()
    bad = [r.format() for r in results if not r.ok]
    assert not bad, "trace invariants violated:\n" + "\n".join(bad)
    # required coverage: train step, softdtw, retrieval (the ISSUE floor)
    # + the serving entries (ISSUE 4: bucket-ladder recompile gate and
    # pinned index collectives)
    entries = {r.entry for r in results}
    assert {"train_step_milnce", "train_step_milnce_guarded",
            "train_step_milnce_instrumented", "train_step_sdtw3",
            "grad_cache_step_milnce", "video_embed", "text_embed",
            "softdtw_scan_grad", "param_treedef",
            # ISSUE 12: chunked streaming MIL-NCE — dense-identical
            # collective pins, collective-free chunk scans, and the
            # backend-dispatch no-recompile gate
            "train_step_milnce_chunked", "train_step_milnce_chunked_2d",
            "milnce_chunked_dispatch",
            "serve_embed_ladder", "serve_text_embed", "serve_video_embed",
            "serve_index_topk",
            # ISSUE 10: pooled serving — per-replica ladder recompile pin
            # + collective-free replica embed programs
            "serve_pool_embed", "serve_pool_text_embed",
            "serve_pool_video_embed",
            # ISSUE 14: generation-swapped live index — same pinned
            # program + zero query-path recompiles across swaps
            "serve_live_index"} <= entries
    # the double-call recompile detector ran on every executable entry
    recompiled = {r.entry for r in results if r.check == "recompile"}
    assert {"train_step_milnce", "train_step_milnce_guarded",
            "train_step_milnce_instrumented",
            "video_embed", "text_embed",
            "softdtw_scan_grad", "serve_embed_ladder",
            "serve_index_topk"} <= recompiled
    # ISSUE 5 acceptance: the instrumented step executed under the
    # steady-state transfer guard and its pins match the plain step's
    checks = {(r.entry, r.check) for r in results}
    assert ("train_step_milnce_instrumented", "transfer-guard") in checks
    assert ("train_step_milnce_instrumented",
            "identical-to-uninstrumented") in checks
    # ISSUE 14 tentpole pin: swaps never compile on the query path
    assert ("serve_live_index", "recompile-across-swaps") in checks


def test_f64_detector_catches_planted_upcast():
    def f(x):
        return x.astype("float64") + 1.0

    with jax.enable_x64():
        jaxpr = jax.make_jaxpr(f)(np.ones((3,), np.float32)).jaxpr
    assert f64_sites(jaxpr), "planted f64 upcast not detected"


def test_f64_detector_clean_on_f32():
    jaxpr = jax.make_jaxpr(lambda x: x * 2.0)(
        np.ones((3,), np.float32)).jaxpr
    assert f64_sites(jaxpr) == []


def test_collective_counter_sees_through_nested_jaxprs():
    from jax.sharding import PartitionSpec as P

    from milnce_tpu.parallel.mesh import build_mesh
    from milnce_tpu.config import ParallelConfig

    mesh = build_mesh(ParallelConfig())

    @jax.jit
    def summed(x):
        return jax.shard_map(lambda xs: jax.lax.psum(xs.sum(), "data"),
                             mesh=mesh, in_specs=P("data"), out_specs=P())(x)

    jaxpr = jax.make_jaxpr(summed)(np.ones((8,), np.float32)).jaxpr
    assert collective_counts(jaxpr) == {"psum": 1}


def test_recompile_detector_catches_dtype_drift():
    """Same shape, drifting dtype across calls — the classic silent
    retrace (e.g. an np.zeros fallback built without dtype= on one call
    path): the detector must flag the second cache entry."""
    f = jax.jit(lambda x: x + 1)

    def make_args(seed):
        return (np.ones((4,), np.float32 if seed == 0 else np.int32),)

    r = _recompile_check("planted", f, make_args)
    assert isinstance(r, CheckResult)
    if "skipped" in r.detail:       # jax without _cache_size introspection
        return
    assert not r.ok and "cache entries" in r.detail


def test_recompile_detector_passes_stable_fn():
    f = jax.jit(lambda x: x * 2)

    def make_args(seed):
        return (np.full((4,), seed, np.float32),)

    assert _recompile_check("stable", f, make_args).ok


def test_treedef_mismatch_would_be_reported():
    """The treedef check compares structure AND leaf shapes/dtypes; spot
    check the comparison logic on a synthetic divergence."""
    a = {"w": jax.ShapeDtypeStruct((2, 3), jnp.float32)}
    b = {"w": jax.ShapeDtypeStruct((3, 2), jnp.float32)}
    ta, tb = (jax.tree_util.tree_structure(x) for x in (a, b))
    la, lb = (jax.tree_util.tree_leaves(x) for x in (a, b))
    same = ta == tb and all(
        x.shape == y.shape and x.dtype == y.dtype for x, y in zip(la, lb))
    assert not same
