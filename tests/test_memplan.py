"""graftlint Pass 4 gates: the static HBM planner (analysis/memplan.py).

Four layers, mirroring how the other passes are pinned:

- **unit**: live-range corner cases the model must get right — scan
  bodies reuse their per-iteration buffers (peak is body-peak plus the
  stacked IO, never iterations x temp), donated args free at last use,
  sharded leaves divide by the mesh-axis extent, and trailing-None
  normalized specs land on the same divisor as their un-normalized
  twins.
- **calibration**: planner-vs-reality on the CPU backend — the per-chip
  resident bytes the planner claims for an entry's arguments must match
  the per-shard byte accounting of the ACTUAL committed arrays
  (train/state.per_device_state_bytes, the PR 6 helpers) within ±10%,
  for the 1-D milnce step AND the 4x2 2-D FSDP step.
- **planted failures**: each of GL013/GL014/GL015 must fire exactly
  once on a planted regression — a detector that can't fail is
  decoration (same discipline as the graftlint fixture's exact
  per-rule counts).
- **the gate**: every registered entry plans green against the pins,
  with the coverage floor asserted — this is the tier-1 check the
  tentpole exists for.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from milnce_tpu.analysis import memplan
from jax import shard_map


def _mesh1d():
    return Mesh(np.array(jax.devices()).reshape(-1), ("data",))


# ---- unit: live-range corner cases ---------------------------------------

def test_scan_body_buffers_are_reused_across_iterations():
    """16 iterations whose body holds a 1 MB temp must plan ~1 temp +
    the stacked IO — a planner that charges temp x iterations would
    refuse every microbatched config that actually fits."""
    n, width = 16, 65536            # 16 x 256 KB slices

    def scanned(xs):
        def body(carry, x):
            big = jnp.outer(x, jnp.ones((4,), jnp.float32))  # 4x the slice
            return carry + big.sum(), x * 2.0

        return jax.lax.scan(body, jnp.float32(0.0), xs)

    xs = jax.ShapeDtypeStruct((n, width), jnp.float32)
    plan = memplan.analyze_jaxpr(jax.make_jaxpr(scanned)(xs))
    stacked = n * width * 4                       # xs, and ys same size
    body_temp = width * 4 * 4                     # the outer-product temp
    assert plan.peak_bytes < 2 * stacked + 4 * body_temp, (
        f"scan peak {plan.peak_bytes} charges per-iteration temps "
        f"cumulatively (stacked IO {stacked}, body temp {body_temp})")
    assert plan.peak_bytes >= 2 * stacked, "stacked xs+ys must be counted"


def test_donated_arg_frees_at_last_use():
    """A consumed-and-returned buffer donated vs pinned: donation must
    lower the planned peak by about one copy."""
    def update(state, grad):
        return state + grad * 0.1, (grad ** 2).sum()

    args = (jax.ShapeDtypeStruct((1 << 20,), jnp.float32),
            jax.ShapeDtypeStruct((1 << 20,), jnp.float32))
    closed = jax.make_jaxpr(update)(*args)
    pinned = memplan.analyze_jaxpr(closed, donated=[False, False],
                                   labels=["state", "grad"])
    donated = memplan.analyze_jaxpr(closed, donated=[True, False],
                                    labels=["state", "grad"])
    one_copy = (1 << 20) * 4
    assert pinned.peak_bytes - donated.peak_bytes >= one_copy // 2, (
        f"donation saved only {pinned.peak_bytes - donated.peak_bytes} B "
        f"of a {one_copy} B reusable state")


def test_sharded_leaf_divides_by_axis_extent():
    """P('data') over the 8-way mesh: the entry arg contributes 1/8 of
    its global bytes per chip; a replicated arg contributes all of it."""
    mesh = _mesh1d()
    ndev = len(jax.devices())

    def f(w, x):
        return shard_map(lambda wv, xv: (xv * 2.0 + wv.sum()),
                         mesh=mesh, in_specs=(P(), P("data")),
                         out_specs=P("data"), check_vma=False)(w, x)

    w = jax.ShapeDtypeStruct((1024,), jnp.float32)      # replicated
    x = jax.ShapeDtypeStruct((8 * 1024,), jnp.float32)  # sharded
    plan = memplan.analyze_jaxpr(jax.make_jaxpr(f)(w, x),
                                 labels=["w", "x"])
    want = 1024 * 4 + (8 * 1024 * 4) // ndev
    assert plan.arg_bytes == want, (plan.arg_bytes, want)


def test_trailing_none_normalized_specs_same_divisor():
    """P('data') and P('data', None) (the sharding_map._dim_spec
    normalization concern) must produce identical per-chip plans — the
    divisor reads sharded dims only, never the spec's rank padding."""
    mesh = _mesh1d()

    def build(spec):
        def f(x):
            return shard_map(lambda xv: xv * 2.0, mesh=mesh,
                             in_specs=(spec,), out_specs=spec,
                             check_vma=False)(x)
        return jax.make_jaxpr(f)(
            jax.ShapeDtypeStruct((8, 64), jnp.float32))

    a = memplan.analyze_jaxpr(build(P("data")), labels=["x"])
    b = memplan.analyze_jaxpr(build(P("data", None)), labels=["x"])
    assert a.arg_bytes == b.arg_bytes
    assert a.peak_bytes == b.peak_bytes


def test_contributor_labels_name_args_by_tree_path():
    args = ({"params": {"w": jnp.zeros((4,), jnp.float32)}},
            jnp.zeros((2,), jnp.float32))
    labels = memplan.arg_leaf_labels(args, ("state", "x"))
    assert labels == ["state/params/w", "x"]
    assert memplan.donated_leaf_flags(args, (0,)) == [True, False]


# ---- calibration: planner vs committed arrays ----------------------------

def _measured_per_chip_bytes(trees) -> float:
    """Max per-device committed bytes across placed pytrees — the PR 6
    per-shard accounting (train/state.per_device_state_bytes reasoning)
    applied to everything the entry holds resident."""
    per_dev: dict = {}
    for tree in trees:
        for leaf in jax.tree_util.tree_leaves(tree):
            for sh in getattr(leaf, "addressable_shards", ()):
                per_dev[sh.device] = per_dev.get(sh.device, 0) + sh.data.nbytes
    return max(per_dev.values())


def test_calibration_1d_milnce_step_args_within_10pct():
    from milnce_tpu.analysis.trace_invariants import _setup
    from milnce_tpu.data.pipeline import shard_placer
    from milnce_tpu.parallel.mesh import replicate_to_mesh

    model, _opt, mesh, state, batch = _setup()
    plans = memplan.plan_all(["train_step_milnce"])
    plan = plans["train_step_milnce"]
    place = shard_placer(mesh)
    placed_state = replicate_to_mesh(state, mesh)
    placed_batch = [place(b) for b in batch()]
    measured = _measured_per_chip_bytes([placed_state] + placed_batch)
    ratio = plan.arg_bytes / measured
    assert 0.9 <= ratio <= 1.1, (
        f"planner args/chip {plan.arg_bytes} vs measured committed "
        f"{measured} ({ratio:.3f}x) — the sharding-aware byte model "
        "drifted from reality")


def test_calibration_2d_fsdp_step_args_within_10pct():
    """The 4x2 (data, model) twin: sharded state leaves count 1/2 per
    chip, the batch 1/8 — planner and committed arrays must agree."""
    from milnce_tpu.analysis.trace_invariants import _setup_2d
    from milnce_tpu.parallel.mesh import batch_sharding

    _model, _opt, mesh, _specs, state, batch = _setup_2d()
    plans = memplan.plan_all(["train_step_milnce_2d"])
    plan = plans["train_step_milnce_2d"]
    sh = batch_sharding(mesh, ("data", "model"))
    placed_batch = [jax.device_put(b, sh) for b in batch()]
    measured = _measured_per_chip_bytes([state] + placed_batch)
    ratio = plan.arg_bytes / measured
    assert 0.9 <= ratio <= 1.1, (
        f"planner args/chip {plan.arg_bytes} vs measured committed "
        f"{measured} ({ratio:.3f}x) on the 4x2 FSDP mesh")
    # and the FSDP layout must actually be cheaper than replication
    full = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(state))
    assert plan.arg_bytes < full, "2-D plan shows no sharding saving"


# ---- planted failures: each rule fires exactly once ----------------------

def test_gl013_fires_once_on_planted_peak_drift(monkeypatch):
    plans = memplan.plan_all(["train_step_milnce"])
    real = plans["train_step_milnce"].peak_bytes
    monkeypatch.setitem(memplan.EXPECTED_PEAK_BYTES, "train_step_milnce",
                        int(real * 2))
    results = memplan.run_memplan_checks(["train_step_milnce"],
                                         plans=plans)
    bad = [r for r in results if not r.ok]
    assert [r.check for r in bad] == ["GL013-peak-budget"], (
        [r.format() for r in results])
    assert "re-pin" in bad[0].detail


def test_gl015_fires_once_on_planted_contributor_drift(monkeypatch):
    plans = memplan.plan_all(["train_step_milnce"])
    monkeypatch.setitem(memplan.EXPECTED_TOP_CONTRIBUTORS,
                        "train_step_milnce",
                        ("phantom_buffer_a", "phantom_buffer_b",
                         "phantom_buffer_c"))
    results = memplan.run_memplan_checks(["train_step_milnce"],
                                         plans=plans)
    bad = [r for r in results if not r.ok]
    assert [r.check for r in bad] == ["GL015-top-contributors"], (
        [r.format() for r in results])
    assert "phantom_buffer_a" in bad[0].detail


def test_gl014_fires_once_per_planted_donation_bug():
    # (a) donated buffer that matches no output — dead-weight donation
    def no_alias(state, x):
        return (x * 2.0).sum()

    args = (jnp.zeros((1 << 16,), jnp.float32),
            jnp.zeros((8,), jnp.float32))
    found = memplan.donation_findings(
        no_alias, args, argnames=("state", "x"), donate_argnums=(0,),
        grad_bearing=True)
    assert len(found) == 1 and "matches no program output" in found[0]
    assert "state" in found[0]

    # (b) large aliasable arg NOT donated on a grad-bearing entry
    def aliasable(state, x):
        return state + 1.0, (x * 2.0).sum()

    found = memplan.donation_findings(
        aliasable, args, argnames=("state", "x"), donate_argnums=(),
        grad_bearing=True)
    assert len(found) == 1 and "not donated" in found[0]

    # (c) donated passthrough — buffer live to the end
    def passthrough(state, x):
        return state, (x + state.sum()).sum()

    found = memplan.donation_findings(
        passthrough, args, argnames=("state", "x"), donate_argnums=(0,),
        grad_bearing=True)
    assert len(found) == 1 and "returned unchanged" in found[0]

    # and the clean shape: consumed + same-shape output + donated
    def clean(state, x):
        return state + 1.0, (x * 2.0).sum()

    assert memplan.donation_findings(
        clean, args, argnames=("state", "x"), donate_argnums=(0,),
        grad_bearing=True) == []

    # an UNDONATED passthrough must stay silent on BOTH branches:
    # donating it could never take effect, so "donate it" would
    # oscillate with the passthrough finding above (review r13)
    def undonated_passthrough(state, x):
        return state, (x + state.sum()).sum()

    assert memplan.donation_findings(
        undonated_passthrough, args, argnames=("state", "x"),
        donate_argnums=(), grad_bearing=True) == []


def test_production_step_donates_its_state_on_the_cpu_too():
    """No backend gate: the donated step consumes its input state here
    exactly as on the chip (jax 0.9.0 resumes cleanly with donation on
    the CPU — the resume suites run the donated program)."""
    spec = memplan._entries()["train_step_milnce"]
    fn, args = spec.build(donate=True)
    state = jax.tree_util.tree_map(
        lambda s: jax.numpy.zeros(s.shape, s.dtype), args[0])
    rest = [jax.numpy.zeros(a.shape, a.dtype) for a in args[1:]]
    new_state, _ = fn(state, *rest)[:2]
    assert jax.tree_util.tree_leaves(state.params)[0].is_deleted()
    assert not jax.tree_util.tree_leaves(new_state.params)[0].is_deleted()


def test_gl014_wiring_read_off_the_traced_program():
    """The wiring half of GL014 must interrogate what the factory REALLY
    passes to jax.jit, not round-trip a registry constant (review r13:
    a factory that drops its donate_argnums= plumbing must fail).  The
    donated production build traces one donated invar per state leaf;
    the donate=False build — exactly what a plumbing-less factory would
    produce — traces zero."""
    traced, expected = memplan._donation_wired("train_step_milnce")
    assert expected > 0 and traced == expected, (traced, expected)
    # the regression shape: no donate wiring -> zero donated invars
    spec = memplan._entries()["train_step_milnce"]
    fn, args = spec.build(donate=False)
    assert memplan.traced_donated_invar_count(fn, args) == 0


def test_entry_name_filter_rejects_typos():
    """A typo'd --entries filter must fail loudly, never plan zero
    entries and pass the gate vacuously (review r13)."""
    with pytest.raises(ValueError, match="unknown memplan entries"):
        memplan.plan_all(["train_step_milcne"])
    with pytest.raises(ValueError, match="unknown memplan entries"):
        memplan.run_memplan_checks(["no_such_entry"])


# ---- the gate ------------------------------------------------------------

def test_all_registered_entries_plan_green():
    """The Pass 4 merge gate: GL013 + GL014 + GL015 hold for every
    registered entry on both hermetic meshes, with the grad-bearing
    coverage floor asserted (the ISSUE 8 acceptance)."""
    results = memplan.run_memplan_checks()
    bad = [r.format() for r in results if not r.ok]
    assert not bad, "memplan invariants violated:\n" + "\n".join(bad)
    entries = {r.entry for r in results}
    assert {"train_step_milnce", "train_step_milnce_guarded",
            "train_step_sdtw3", "grad_cache_step_milnce",
            "train_step_milnce_2d", "grad_cache_2d",
            # ISSUE 12: the chunked step + the loss-only pair isolating
            # the O(B_local*Bg*K) -> O(B_local*chunk) claim
            "train_step_milnce_chunked", "milnce_loss_dense",
            "milnce_loss_chunked",
            "serve_text_embed@b0", "serve_text_embed@b1",
            "serve_video_embed@b0", "serve_video_embed@b1",
            "serve_index_topk",
            # ISSUE 14: the live index's generation program at its
            # capacity rung
            "serve_index_topk@gen",
            "train_step_milnce_instrumented"} <= entries
    # every grad-bearing entry carries all three rule checks + TPU gate
    checks = {(r.entry, r.check) for r in results}
    for entry in ("train_step_milnce", "train_step_milnce_2d",
                  "grad_cache_2d"):
        assert (entry, "GL013-peak-budget") in checks
        assert (entry, "GL015-top-contributors") in checks
        assert (entry, "GL014-donation") in checks
        assert (entry, "GL014-donation-requested") in checks


def test_guarded_step_peak_exceeds_plain_by_one_state_copy():
    """A real property the planner surfaced: the finite guard's
    skip-select keeps the OLD params/opt_state live until the end of
    the step, so donation cannot retire them — its pinned peak sits one
    state copy above the plain step's.  If these ever converge, the
    guard semantics (or the planner's donation model) changed."""
    plain = memplan.EXPECTED_PEAK_BYTES["train_step_milnce"]
    guarded = memplan.EXPECTED_PEAK_BYTES["train_step_milnce_guarded"]
    assert guarded > plain * 1.2


def test_milnce_chunked_loss_peak_strictly_below_dense():
    """The ISSUE 12 acceptance pin, stated on the pins themselves: at
    the loss-only entry shape (B_local=64, Bg=512, K=5) the chunked
    stream's per-chip peak is strictly — and substantially — below the
    dense cube's, and the chunked step never exceeds the dense step.
    The GL015 names behind the numbers: dense peaks at the
    (B_local, Bg*K) cube intermediates, chunked at one
    (B_local, chunk*K) streamed block (analysis/memplan.py)."""
    e = memplan.EXPECTED_PEAK_BYTES
    assert e["milnce_loss_chunked"] < e["milnce_loss_dense"]
    # the gap is structural (Bg/chunk = 8 at this shape), not noise
    assert e["milnce_loss_chunked"] < 0.5 * e["milnce_loss_dense"]
    assert e["train_step_milnce_chunked"] <= e["train_step_milnce"]
    # and the planned (not just pinned) values agree with the claim
    plans = memplan.plan_all(["milnce_loss_dense", "milnce_loss_chunked"])
    assert (plans["milnce_loss_chunked"].peak_bytes
            < plans["milnce_loss_dense"].peak_bytes)


def test_what_if_loss_impl_axis_reaches_the_traced_program(monkeypatch):
    """--loss-impl / --milnce-chunk must reach the step FACTORY (a
    config-only dead knob here would quietly un-gate the 8192 crossover
    table in BENCH_MILNCE_LOSS.md): a spy on make_train_step captures
    the loss_cfg the what-if actually builds with — one trace instead
    of a dense/chunked plan pair (the strictly-below direction is
    already pinned by the milnce_loss_* entries; the Bg=8192 pair lives
    in the committed table).  A --milnce-chunk without a chunked impl
    is refused outright."""
    import milnce_tpu.train.step as step_mod

    with pytest.raises(ValueError, match="milnce-chunk"):
        memplan.what_if_step(batch=16, frames=4, size=32, words=6, k=3,
                             preset="tiny", milnce_chunk=8)
    seen = {}
    real = step_mod.make_train_step

    def spy(*args, **kwargs):
        seen["loss_cfg"] = kwargs.get("loss_cfg")
        return real(*args, **kwargs)

    monkeypatch.setattr(step_mod, "make_train_step", spy)
    plan = memplan.what_if_step(batch=64, frames=4, size=32, words=6,
                                k=3, dtype="float32", preset="tiny",
                                loss_impl="chunked", milnce_chunk=8)
    assert "loss=chunked" in plan.entry
    assert seen["loss_cfg"] is not None
    assert seen["loss_cfg"].milnce_impl == "chunked"
    assert seen["loss_cfg"].milnce_chunk == 8


def test_2d_entries_plan_below_their_1d_twins():
    """FSDP must show up in the plan: the 4x2 sharded step's peak is
    strictly below the 8-way replicated step's (the PR 6 storage win,
    now claimed statically rather than only by live-byte counting)."""
    e = memplan.EXPECTED_PEAK_BYTES
    assert e["train_step_milnce_2d"] < e["train_step_milnce"]
    assert e["grad_cache_2d"] < e["grad_cache_step_milnce"]


# ---- what-if -------------------------------------------------------------

def test_what_if_refuses_oversized_config():
    """Library-level refusal on the tiny preset (the CLI twin is the
    subprocess test below): predicted peak over budget -> fits=False
    with the top-3 contributors named in the message."""
    plan = memplan.what_if_step(batch=16, frames=4, size=32, words=6,
                                k=3, dtype="float32", preset="tiny")
    fits, msg = memplan.budget_verdict(plan, hbm_gib=1e-4)
    assert not fits and "EXCEEDS" in msg
    assert msg.count("MiB") >= 3, f"top-3 contributors not named: {msg}"
    ok, msg2 = memplan.budget_verdict(plan, hbm_gib=1024.0)
    assert ok and "fits" in msg2


def test_mem_plan_cli_what_if_refuses_with_nonzero_exit():
    """The ISSUE 8 acceptance, end to end through the real CLI: an
    oversized config exits 1 with the top-3 contributors named; the
    same config under a generous budget exits 0.  Tiny preset keeps the
    child seconds-scale (the batch-256 full-preset refusal is the same
    code path — budget_verdict — pinned above at library level)."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cli = [sys.executable, os.path.join(repo, "scripts", "mem_plan.py"),
           "--what-if", "--preset", "tiny", "--batch", "16",
           "--frames", "4", "--size", "32", "--words", "6", "--k", "3",
           "--dtype", "float32"]
    proc = subprocess.run(cli + ["--hbm-gib", "0.0001"],
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "EXCEEDS" in proc.stdout and proc.stdout.count("MiB") >= 3
    proc = subprocess.run(cli + ["--hbm-gib", "1024"],
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "fits" in proc.stdout


def test_what_if_rejects_mesh_larger_than_devices():
    with pytest.raises(ValueError, match="devices"):
        memplan.what_if_step(batch=8, frames=4, size=32, words=6, k=3,
                             preset="tiny",
                             mesh_axes={"data": 64, "model": 4})


def test_what_if_grad_accum_plans_below_single_pass():
    """The grad-cache two-pass step exists to cut activation memory;
    the planner must agree at a shape where activations dominate
    (16f@112: ~1.1 GiB single-pass vs ~0.46 GiB at M=4 when this pin
    was taken — at activation-light shapes the cached embeddings +
    grad-carry overhead genuinely flips the ordering, which is exactly
    the crossover the what-if mode exists to predict)."""
    single = memplan.what_if_step(batch=64, frames=16, size=112, words=6,
                                  k=3, dtype="float32", preset="tiny")
    cached = memplan.what_if_step(batch=64, frames=16, size=112, words=6,
                                  k=3, dtype="float32", preset="tiny",
                                  grad_accum=4)
    assert cached.peak_bytes < 0.7 * single.peak_bytes, (
        f"grad-cache plan {cached.peak_bytes} not meaningfully below "
        f"single-pass {single.peak_bytes} at an activation-dominated "
        "shape")


# ---- stage_probe pre-flight ----------------------------------------------

def test_stage_probe_preflight_budget_env(monkeypatch):
    import os
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts"))
    import stage_probe

    monkeypatch.setenv("MILNCE_HBM_GIB", "2")
    assert stage_probe._hbm_budget_bytes() == 2 * 2 ** 30
    monkeypatch.delenv("MILNCE_HBM_GIB")
    # CPU backend exposes no bytes_limit -> pre-flight off
    assert stage_probe._hbm_budget_bytes() in (None,) or isinstance(
        stage_probe._hbm_budget_bytes(), float)


def test_preflight_fn_peak_scales_with_shape():
    def probe(x):
        return (x.astype(jnp.float32) * 2.0).sum()

    small = memplan.preflight_fn_peak(
        probe, jax.ShapeDtypeStruct((1024,), jnp.float32))
    big = memplan.preflight_fn_peak(
        probe, jax.ShapeDtypeStruct((1024 * 64,), jnp.float32))
    assert big > small * 16
