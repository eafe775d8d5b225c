"""graftlint Pass 2: trace-level invariants over the registered entry points.

Where Pass 1 reads source, this pass reads *jaxprs*: every hot-path entry
point (train step variants, soft-DTW ops, eval retrieval embedders, the
serving engine's bucket ladder + sharded top-k retrieval) is
traced on a hermetic CPU mesh (the same 8-virtual-device layout the test
suite uses) and checked for the regressions that erase TPU throughput
without failing any functional test:

- **no-f64**: no value of dtype float64 anywhere in the jaxpr, and no
  ``convert_element_type`` targeting it — one f64 operand upcasts every
  downstream op (2x HBM traffic, off the MXU fast path);
- **collectives**: the exact multiset of collective primitives per step
  is pinned for the 8-way data mesh.  A diff means the communication
  structure changed — sometimes intended (then re-pin the number in
  ``EXPECTED_COLLECTIVES``, consciously), often a silent extra gather
  or a lost psum;
- **treedef**: the three conv formulations (native / fold2d / im2col)
  must init byte-identical param trees — the per-stage impl map
  (ModelConfig.conv_impl_map) and checkpoint portability both rely on
  it;
- **recompile**: each executable entry point is called twice with fresh
  same-shaped inputs and must hit the jit cache the second time — a
  miss is the seed of a recompilation storm (weak-type drift, unstable
  static argument, non-hashable closure).

Everything here must run under ``JAX_PLATFORMS=cpu`` in tier-1 time:
the model is a 1-block S3D at 4 frames / 32 px.  jax imports live
inside functions so ``astlint`` stays importable without jax.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

# Collective primitives whose per-step count we pin.  ``reduce_scatter``
# is what ``lax.psum_scatter`` lowers to on this jax — the FSDP grad
# reduction's signature primitive (train/step.py _reduce_grads_2d).
COLLECTIVES = ("psum", "all_gather", "psum_scatter", "reduce_scatter",
               "all_to_all", "ppermute", "pbroadcast")
# under check_vma=True a psum traces as ``psum_invariant`` — the same
# collective, counted under the one name
_PRIMITIVE_ALIASES = {"psum_invariant": "psum"}

# Pinned per-entry collective multisets for the 8-way data mesh (absent
# primitive = expected 0).  Derived by tracing on the tiny entry config;
# the invariant is that they never change SILENTLY — a deliberate
# communication-structure change re-pins the number in the same commit.
#
# Reading the milnce step: 2 all_gathers (video+text negatives ride ICI
# once each); the 78 psums are the scalar loss reduction, the grad
# psum (jax binds one psum per LEAF of a reduced tree: 53 param leaves),
# and the 24 pmean-lowered BatchNorm stat merges; the 2
# reduce_scatters are the AD transposes of the loss's embedding gathers
# (every grad-bearing step has them — they were always in the program,
# uncounted until ISSUE 6 added reduce_scatter to COLLECTIVES for the
# FSDP entries, a conscious same-commit re-pin of every entry below).
# sdtw_3 trades one psum for a third all_gather (clip start-times feed
# the alignment; the start gather carries no gradient).
EXPECTED_COLLECTIVES = {
    "train_step_milnce": {"all_gather": 2, "psum": 78,
                          "reduce_scatter": 2},
    # the finite-update guard (ISSUE 3) must add NO collectives and no
    # host sync: its all-finite check runs on the already-psum'd
    # (replicated) grads and the skip is a jnp.where select — the pin
    # being IDENTICAL to the unguarded step is the invariant
    "train_step_milnce_guarded": {"all_gather": 2, "psum": 78,
                                  "reduce_scatter": 2},
    # the obs span instrumentation (ISSUE 5) wraps the step DISPATCH in
    # a host-side recorder (train/loop.py `rec.span("step")`); it must
    # add NO collectives, no transfers, no sync — the pin being
    # IDENTICAL to the uninstrumented step is the tentpole invariant,
    # and the entry also EXECUTES it under transfer_guard("disallow")
    "train_step_milnce_instrumented": {"all_gather": 2, "psum": 78,
                                       "reduce_scatter": 2},
    # curriculum step (ISSUE 16): ONE step_fn serves every stage; each
    # stage's (frames, resolution, batch) shape is its own jit entry,
    # compiled once at stage entry.  The invariant is twofold: every
    # stage's traced program carries the SAME collective multiset as the
    # single-stage step (shapes scale tensors, never communication
    # structure), and within a stage the cache never grows (zero
    # recompiles; entering stage 2 adds exactly one entry)
    "train_step_curriculum": {"all_gather": 2, "psum": 78,
                              "reduce_scatter": 2},
    # chunked MIL-NCE (ISSUE 12): the streaming loss must keep the DENSE
    # step's exact communication structure — the same 2 negative
    # all_gathers (whose AD transposes stay the same 2 reduce_scatters)
    # and the same psum census; the chunk scan adds compute structure,
    # never collectives (its body is pinned collective-free by the
    # scan-reduction-free check on these entries).  The pins being
    # IDENTICAL to train_step_milnce / train_step_milnce_2d is the
    # invariant, exactly like the guarded/instrumented twins above.
    "train_step_milnce_chunked": {"all_gather": 2, "psum": 78,
                                  "reduce_scatter": 2},
    "train_step_milnce_chunked_2d": {"all_gather": 22, "psum": 78,
                                     "reduce_scatter": 22},
    # elastic 4-way layout (ISSUE 20): the DOWNSIZED data mesh a drained
    # run resumes onto (parallel.num_devices=4 on an 8-device host).
    # The multiset is pinned IDENTICAL to the 8-way step by construction
    # — collective STRUCTURE is a function of the program, not the axis
    # size (4-way vs 8-way only changes shard extents) — and pinning it
    # per layout is what makes a topology change's communication plan a
    # deliberate re-pin instead of an accident.
    "train_step_milnce@4way": {"all_gather": 2, "psum": 78,
                               "reduce_scatter": 2},
    "train_step_sdtw3": {"all_gather": 3, "psum": 77,
                         "reduce_scatter": 2},
    "grad_cache_step_milnce": {"all_gather": 2, "psum": 78,
                               "reduce_scatter": 2},
    # 2-D (data, model) FSDP step on the 4x2 grid (ISSUE 6): 22
    # all_gathers = 20 sharded-param materializations before the forward
    # + the 2 loss negative gathers; 22 reduce_scatters = the 20
    # model-axis halves of the per-leaf grad reduction (GSPMD's textbook
    # gather/reduce-scatter pair, here explicit and therefore countable)
    # + the 2 loss-gather transposes; the psums are the per-leaf
    # data-axis grad reductions plus the replicated leaves' both-axes
    # psums (overlap_grad_reduce=True emits them per leaf so the
    # scheduler can overlap each with the backward) and the loss/BN
    # reductions.  The guarded 2-D step adds exactly ONE psum — the
    # model-axis finite-verdict reduction that keeps the skip decision
    # uniform across model columns.  Counts are a function of the tiny
    # entry model's leaf census under _FSDP_MIN_SIZE — a model/threshold
    # change re-pins them in the same commit, like every other entry.
    "train_step_milnce_2d": {"all_gather": 22, "psum": 78,
                             "reduce_scatter": 22},
    "train_step_milnce_2d_guarded": {"all_gather": 22, "psum": 79,
                                     "reduce_scatter": 22},
    # grad-cache on the 2-D mesh: identical communication to the
    # single-pass 2-D step — the whole point of the once-per-step
    # property (gather before pass 1, reduce after pass 2, NOTHING per
    # microbatch; the scan-reduction-free check pins the structure)
    "grad_cache_2d": {"all_gather": 22, "psum": 78, "reduce_scatter": 22},
    "video_embed": {},
    "text_embed": {},
    "softdtw_scan_grad": {},
    # serving (ISSUE 4): the engine's embed entries are the same
    # shard_map programs as offline eval — collective-free by
    # construction; the sharded top-k retrieval ships exactly the two
    # (Q, k) candidate gathers (scores + global indices), never the
    # (Q, R_local) score matrix
    "serve_text_embed": {},
    "serve_video_embed": {},
    "serve_index_topk": {"all_gather": 2},
    # replica pool (ISSUE 10): each replica's engine runs on its OWN
    # mesh (single-device on the CPU backend) — its embed programs must
    # stay collective-free like the single-engine entries
    "serve_pool_text_embed": {},
    "serve_pool_video_embed": {},
    # live index (ISSUE 14): the generation-swapped index runs the SAME
    # top-k program — identical pinned communication, whatever
    # generation is live
    "serve_live_index": {"all_gather": 2},
    # quantized edge tier (ISSUE 19): the int8 engine is the same embed
    # program with an in-jit dequantize prologue (i8 -> f32 convert +
    # scale multiply, quant/quantize.py) — it must stay collective-free
    # like every other embed entry, and GL016-clean by construction:
    # every matmul accumulates in f32 because the ONLY low-precision
    # dtype in the program is int8 storage, never a compute dtype
    "serve_quant_text_embed": {},
    "serve_quant_video_embed": {},
}


@dataclass
class CheckResult:
    entry: str
    check: str              # no-f64 | collectives | treedef | recompile
    ok: bool
    detail: str = ""

    def format(self) -> str:
        mark = "ok" if self.ok else "FAIL"
        tail = f" — {self.detail}" if self.detail else ""
        return f"[{mark}] {self.entry}/{self.check}{tail}"


# --------------------------------------------------------------------------
# jaxpr utilities
# --------------------------------------------------------------------------

def iter_eqns(jaxpr):
    """Every eqn in a (possibly nested) jaxpr, including the inner jaxprs
    of pjit / shard_map / scan / custom_vjp / pallas_call params."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            vals = p if isinstance(p, (list, tuple)) else [p]
            for v in vals:
                if isinstance(v, ClosedJaxpr):
                    yield from iter_eqns(v.jaxpr)
                elif isinstance(v, Jaxpr):
                    yield from iter_eqns(v)


def collective_counts(jaxpr) -> dict:
    out: dict[str, int] = {}
    for eqn in iter_eqns(jaxpr):
        name = _PRIMITIVE_ALIASES.get(eqn.primitive.name, eqn.primitive.name)
        if name in COLLECTIVES:
            out[name] = out.get(name, 0) + 1
    return out


def scan_collective_counts(jaxpr) -> dict:
    """Collective counts INSIDE ``lax.scan`` bodies, anywhere in the
    nest — the once-per-optimizer-step grad-reduction pin (ISSUE 6): a
    cross-mesh reduction that slips under the microbatch scan executes
    M times per step and silently re-pays the collective for the same
    bytes (the structure behind the ga=8 throughput hole BENCH_NOTES.md
    records).  Sibling scans accumulate; nested scans would double-count
    through their parent (none exist in the pinned programs)."""
    from jax.extend.core import ClosedJaxpr

    out: dict[str, int] = {}
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "scan":
            continue
        body = eqn.params.get("jaxpr")
        inner = body.jaxpr if isinstance(body, ClosedJaxpr) else body
        for name, n in collective_counts(inner).items():
            out[name] = out.get(name, 0) + n
    return out


def f64_sites(jaxpr) -> list[str]:
    """Primitive names whose inputs or outputs carry float64."""
    import numpy as np

    sites = []
    for eqn in iter_eqns(jaxpr):
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            if getattr(aval, "dtype", None) == np.float64:  # graftlint: disable=GL004(dtype comparison constant — this IS the f64 detector)
                sites.append(f"{eqn.primitive.name}: {aval}")
        if (eqn.primitive.name == "convert_element_type"
                and str(eqn.params.get("new_dtype", "")) == "float64"):
            sites.append("convert_element_type -> float64")
    return sites


# --------------------------------------------------------------------------
# tiny entry config (shared across entry points; built once per process)
# --------------------------------------------------------------------------

_TINY = dict(embedding_dim=16, vocab_size=32, word_embedding_dim=8,
             text_hidden_dim=16, inception_blocks=1)
_FRAMES, _SIZE, _WORDS = 4, 32, 5


@functools.lru_cache(maxsize=1)
def _setup():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from milnce_tpu.config import OptimConfig, ParallelConfig
    from milnce_tpu.models import S3D
    from milnce_tpu.parallel.mesh import build_mesh
    from milnce_tpu.train.schedule import build_schedule
    from milnce_tpu.train.state import build_optimizer, create_train_state

    ndev = len(jax.devices())
    assert ndev >= 2, (
        "trace invariants need a multi-device mesh (run under the test "
        "conftest or scripts/graft_lint.py, which force 8 virtual CPU "
        f"devices); got {ndev}")
    model = S3D(num_classes=_TINY["embedding_dim"],
                vocab_size=_TINY["vocab_size"],
                word_embedding_dim=_TINY["word_embedding_dim"],
                text_hidden_dim=_TINY["text_hidden_dim"],
                inception_blocks=_TINY["inception_blocks"])
    b = 2 * ndev                      # 2 per shard: grad-cache can split M=2
    variables = model.init(
        jax.random.PRNGKey(0),
        jnp.zeros((2, _FRAMES, _SIZE, _SIZE, 3), jnp.float32),
        jnp.zeros((2, _WORDS), jnp.int32))
    opt = build_optimizer(OptimConfig(warmup_steps=2),
                          build_schedule(OptimConfig(warmup_steps=2), 10))
    state = create_train_state(variables, opt)
    mesh = build_mesh(ParallelConfig())

    def batch(seed: int = 0):
        rng = np.random.default_rng(seed)
        video = rng.integers(0, 255, (b, _FRAMES, _SIZE, _SIZE, 3),
                             dtype=np.uint8)
        text = rng.integers(0, _TINY["vocab_size"], (b, _WORDS)).astype(
            np.int32)
        start = np.zeros((b,), np.float32)
        return video, text, start

    return model, opt, mesh, state, batch


def _jaxpr_checks(name: str, fn, args, scan_reduction_free: bool = False
                  ) -> list[CheckResult]:
    import jax

    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    bad = f64_sites(jaxpr)
    got = collective_counts(jaxpr)
    want = EXPECTED_COLLECTIVES[name]
    out = [
        CheckResult(name, "no-f64", not bad,
                    "; ".join(bad[:4]) if bad else ""),
        CheckResult(name, "collectives", got == want,
                    "" if got == want else f"expected {want}, traced {got} "
                    "(communication structure changed — if intended, re-pin "
                    "EXPECTED_COLLECTIVES)"),
    ]
    if scan_reduction_free:
        inside = scan_collective_counts(jaxpr)
        out.append(CheckResult(
            name, "scan-reduction-free", not inside,
            "" if not inside else
            f"collectives inside scan bodies: {inside} — the cross-mesh "
            "grad reduction must run ONCE per optimizer step, after the "
            "microbatch scan, never per microbatch"))
    return out


def _recompile_check(name: str, fn, make_args, call=None) -> CheckResult:
    """Execute twice with fresh same-shaped inputs; the second call must
    hit the jit cache.  ``call`` adapts calling conventions."""
    call = call or (lambda f, a: f(*a))
    if not hasattr(fn, "_cache_size"):
        return CheckResult(name, "recompile", True,
                           "skipped: no _cache_size on this jax")
    call(fn, make_args(0))
    call(fn, make_args(1))
    n = fn._cache_size()
    return CheckResult(
        name, "recompile", n == 1,
        "" if n == 1 else f"{n} cache entries after two same-shape calls — "
        "something retraces per call (weak-type or static-arg drift)")


# --------------------------------------------------------------------------
# entry points
# --------------------------------------------------------------------------

def _entry_train_step_milnce() -> list[CheckResult]:
    from milnce_tpu.train.step import make_train_step

    model, opt, mesh, state, batch = _setup()
    step = make_train_step(model, opt, mesh, donate=False)
    name = "train_step_milnce"
    out = _jaxpr_checks(name, step, (state,) + batch())
    out.append(_recompile_check(name, step,
                                lambda s: (state,) + batch(s)))
    return out


def _entry_train_step_milnce_guarded() -> list[CheckResult]:
    from milnce_tpu.train.step import make_train_step

    model, opt, mesh, state, batch = _setup()
    step = make_train_step(model, opt, mesh, donate=False, finite_guard=True)
    name = "train_step_milnce_guarded"
    out = _jaxpr_checks(name, step, (state,) + batch())
    out.append(_recompile_check(name, step,
                                lambda s: (state,) + batch(s)))
    return out


def _entry_train_step_curriculum() -> list[CheckResult]:
    """ISSUE 16: the per-stage re-traced curriculum step.  Two stage
    shapes (4f and 8f at the tiny size) through ONE step_fn:

    - collectives: both stages' traced programs must match the pinned
      single-stage multiset — a curriculum changes tensor shapes, never
      communication structure;
    - one-entry-per-stage: two same-shape calls per stage, cache size
      must go 1 -> 2 across the boundary (zero recompiles WITHIN a
      stage, exactly one fresh jit entry per stage entered — the
      runtime guarantee train/loop.py's boundary relies on)."""
    import jax
    import numpy as np

    from milnce_tpu.train.step import make_train_step

    model, opt, mesh, state, batch = _setup()
    step = make_train_step(model, opt, mesh, donate=False)
    name = "train_step_curriculum"
    ndev = len(jax.devices())
    b = 2 * ndev

    def stage_batch(frames: int, seed: int = 0):
        rng = np.random.default_rng(seed)
        video = rng.integers(0, 255, (b, frames, _SIZE, _SIZE, 3),
                             dtype=np.uint8)
        text = rng.integers(0, _TINY["vocab_size"], (b, _WORDS)).astype(
            np.int32)
        return video, text, np.zeros((b,), np.float32)

    out = _jaxpr_checks(name, step, (state,) + stage_batch(_FRAMES))
    got2 = collective_counts(
        jax.make_jaxpr(step)(state, *stage_batch(2 * _FRAMES)).jaxpr)
    want = EXPECTED_COLLECTIVES[name]
    out.append(CheckResult(
        name, "collectives-stage2", got2 == want,
        "" if got2 == want else
        f"stage-2 shape traced {got2}, expected {want} — a stage "
        "boundary changed the step's communication structure"))
    if hasattr(step, "_cache_size"):
        step(state, *stage_batch(_FRAMES, 0))
        step(state, *stage_batch(_FRAMES, 1))
        n1 = step._cache_size()
        step(state, *stage_batch(2 * _FRAMES, 0))
        step(state, *stage_batch(2 * _FRAMES, 1))
        n2 = step._cache_size()
        ok = n1 == 1 and n2 == 2
        out.append(CheckResult(
            name, "one-entry-per-stage", ok,
            "" if ok else f"cache sizes {n1} -> {n2} across two stages; "
            "expected 1 -> 2 (one jit entry per stage, zero recompiles "
            "within a stage)"))
    else:
        out.append(CheckResult(name, "one-entry-per-stage", True,
                               "skipped: no _cache_size on this jax"))
    return out


def _entry_train_step_milnce_instrumented() -> list[CheckResult]:
    """ISSUE 5 tentpole invariant: the obs instrumentation is free.

    Wraps the step dispatch in a live :class:`SpanRecorder` span exactly
    the way ``train/loop.py`` does, then (a) pins the traced program's
    collectives IDENTICAL to ``train_step_milnce`` (the recorder must
    not change what the device runs), and (b) EXECUTES the instrumented
    dispatch twice under ``jax.transfer_guard("disallow")`` with
    explicitly placed inputs — a hidden ``device_get`` in the recorder
    or a smuggled implicit H2D raises here instead of stalling a real
    run — while the double-call recompile detector confirms the span
    doesn't retrace the step."""
    import jax

    from milnce_tpu.data.pipeline import shard_placer
    from milnce_tpu.obs import spans as obs_spans
    from milnce_tpu.parallel.mesh import replicate_to_mesh
    from milnce_tpu.train.step import make_train_step

    model, opt, mesh, state, batch = _setup()
    step = make_train_step(model, opt, mesh, donate=False)
    rec = obs_spans.SpanRecorder()          # ring-only, like a test run
    name = "train_step_milnce_instrumented"

    def instrumented(s, video, text, start):
        with rec.span("step"):
            return step(s, video, text, start)

    out = _jaxpr_checks(name, instrumented, (state,) + batch())
    same = (EXPECTED_COLLECTIVES[name]
            == EXPECTED_COLLECTIVES["train_step_milnce"])
    out.append(CheckResult(
        name, "identical-to-uninstrumented", same,
        "" if same else "pins diverged — instrumented and plain step "
        "must share one communication structure"))
    place = shard_placer(mesh)
    placed = replicate_to_mesh(state, mesh)

    def make_args(seed):
        video, text, start = batch(seed)
        return (placed, place(video), place(text), place(start))

    try:
        with jax.transfer_guard("disallow"):
            # execute the guarded dispatches OURSELVES: the recompile
            # helper skips execution entirely on jax builds without
            # _cache_size, and the span-count assertion below must hold
            # on those builds too
            instrumented(*make_args(0))
            instrumented(*make_args(1))
            recompile = _recompile_check(
                name, step, make_args, call=lambda _f, a: instrumented(*a))
        spans = [r for r in rec.tail() if r.get("name") == "step"]
        guard = CheckResult(
            name, "transfer-guard", len(spans) >= 2,
            "" if len(spans) >= 2 else f"only {len(spans)} step spans "
            "recorded across two guarded dispatches")
    except Exception as exc:
        recompile = None
        guard = CheckResult(
            name, "transfer-guard", False,
            f"instrumented dispatch broke the steady-state guard — the "
            f"recorder added a host sync/transfer: "
            f"{type(exc).__name__}: {exc}")
    out.append(guard)
    if recompile is not None:
        out.append(recompile)
    return out


# FSDP threshold for the 2-D entries: low enough that the tiny entry
# model actually SHARDS several kernels on the 4x2 grid (the production
# default, 65536 elements, would shard nothing at this scale and the
# entries would pin a vacuously-replicated program).
_FSDP_MIN_SIZE = 256


@functools.lru_cache(maxsize=1)
def _setup_2d():
    """The 4x2 ``(data, model)`` twin of :func:`_setup`: same tiny model
    and state, mesh reshaped, state sharded per the FSDP map and placed."""
    from milnce_tpu.config import ParallelConfig
    from milnce_tpu.parallel.mesh import build_mesh
    from milnce_tpu.parallel.sharding_map import shard_and_place_state

    model, opt, _mesh1, state, batch = _setup()
    mesh = build_mesh(ParallelConfig(model_axis="model",
                                     model_parallel_size=2))
    placement = shard_and_place_state(state, mesh, "model",
                                      min_size=_FSDP_MIN_SIZE)
    assert placement.n_sharded > 0, (
        "2-D entry setup shards nothing — the pinned program would be "
        f"pure replication (threshold {_FSDP_MIN_SIZE})")
    return model, opt, mesh, placement.specs, placement.state, batch


@functools.lru_cache(maxsize=1)
def _setup_4way():
    """The downsized elastic twin of :func:`_setup`: same tiny model and
    state, 1-D data mesh over the FIRST 4 of the host's 8 virtual
    devices — the layout ``parallel.num_devices=4`` builds for a
    drained run resuming at half capacity (milnce_tpu/elastic/)."""
    import jax
    import numpy as np

    from milnce_tpu.config import ParallelConfig
    from milnce_tpu.parallel.mesh import build_mesh

    model, opt, _mesh8, state, _batch8 = _setup()
    assert len(jax.devices()) >= 8, "4-way elastic entry needs 8 devices"
    mesh = build_mesh(ParallelConfig(), devices=jax.devices()[:4])
    b = 2 * 4                         # 2 per shard on the smaller mesh

    def batch(seed: int = 0):
        rng = np.random.default_rng(seed)
        video = rng.integers(0, 255, (b, _FRAMES, _SIZE, _SIZE, 3),
                             dtype=np.uint8)
        text = rng.integers(0, _TINY["vocab_size"], (b, _WORDS)).astype(
            np.int32)
        start = np.zeros((b,), np.float32)
        return video, text, start

    return model, opt, mesh, state, batch


def _entry_train_step_4way() -> list[CheckResult]:
    """ISSUE 20: the elastic resume layout's per-layout pins — the
    4-way step must keep the 8-way collective multiset (a topology
    change rescales shard extents, never communication structure) and
    compile exactly once (the acceptance's 0-recompiles-per-topology-
    segment, at the trace layer)."""
    from milnce_tpu.train.step import make_train_step

    model, opt, mesh, state, batch = _setup_4way()
    step = make_train_step(model, opt, mesh, donate=False)
    name = "train_step_milnce@4way"
    out = _jaxpr_checks(name, step, (state,) + batch())
    out.append(_recompile_check(name, step,
                                lambda s: (state,) + batch(s)))
    return out


def _entry_train_step_2d() -> list[CheckResult]:
    """ISSUE 6 tentpole pins: the 2-D FSDP step's all_gather /
    reduce_scatter pairs and per-leaf psums, the double-call recompile
    check, and the guarded variant costing exactly ONE extra psum (the
    model-axis finite-verdict reduction)."""
    from milnce_tpu.train.step import make_train_step

    model, opt, mesh, specs, state, batch = _setup_2d()
    step = make_train_step(model, opt, mesh, donate=False,
                           state_specs=specs, model_axis="model")
    name = "train_step_milnce_2d"
    out = _jaxpr_checks(name, step, (state,) + batch())
    out.append(_recompile_check(name, step, lambda s: (state,) + batch(s)))
    gstep = make_train_step(model, opt, mesh, donate=False,
                            finite_guard=True, state_specs=specs,
                            model_axis="model")
    out += _jaxpr_checks("train_step_milnce_2d_guarded", gstep,
                         (state,) + batch())
    return out


def _entry_grad_cache_2d() -> list[CheckResult]:
    """2-D grad-cache: pinned collectives PLUS the once-per-step
    structural pin — zero collectives inside the microbatch scans (the
    param gather runs before pass 1, the reduction after pass 2)."""
    from milnce_tpu.config import LossConfig
    from milnce_tpu.train.step import make_grad_cache_step

    model, opt, mesh, specs, state, batch = _setup_2d()
    step = make_grad_cache_step(model, opt, mesh, 2, donate=False,
                                loss_cfg=LossConfig(name="milnce"),
                                state_specs=specs, model_axis="model")
    return _jaxpr_checks("grad_cache_2d", step, (state,) + batch(),
                         scan_reduction_free=True)


def _chunked_loss_cfg():
    """The chunked-step entries' LossConfig: scan backend (the pinned
    program must not depend on the host platform) and chunk=6 on the
    16-clip entry batch — 3 chunks with a masked uneven tail, so the
    pinned program exercises the Bg % chunk != 0 path."""
    from milnce_tpu.config import LossConfig

    return LossConfig(name="milnce", milnce_impl="chunked",
                      milnce_chunk=6, milnce_backend="scan")


def _entry_train_step_milnce_chunked() -> list[CheckResult]:
    """ISSUE 12 tentpole pins: the chunked streaming MIL-NCE step keeps
    the dense step's collective multiset (2 gathers / 2 reduce_scatter
    transposes / same psums), its chunk scan is collective-free, and
    the double-call recompile check holds."""
    from milnce_tpu.train.step import make_train_step

    model, opt, mesh, state, batch = _setup()
    step = make_train_step(model, opt, mesh, donate=False,
                           loss_cfg=_chunked_loss_cfg())
    name = "train_step_milnce_chunked"
    out = _jaxpr_checks(name, step, (state,) + batch(),
                        scan_reduction_free=True)
    same = (EXPECTED_COLLECTIVES[name]
            == EXPECTED_COLLECTIVES["train_step_milnce"])
    out.append(CheckResult(
        name, "identical-to-dense", same,
        "" if same else "pins diverged — the chunked and dense steps "
        "must share one communication structure (the stream changes "
        "memory, never collectives)"))
    out.append(_recompile_check(name, step,
                                lambda s: (state,) + batch(s)))
    return out


def _entry_train_step_milnce_chunked_2d() -> list[CheckResult]:
    """The 4x2 FSDP twin: chunked loss under the 2-D step keeps the 2-D
    dense pins (gather/reduce-scatter pairs + per-leaf psums) with a
    collective-free chunk scan."""
    from milnce_tpu.train.step import make_train_step

    model, opt, mesh, specs, state, batch = _setup_2d()
    step = make_train_step(model, opt, mesh, donate=False,
                           loss_cfg=_chunked_loss_cfg(),
                           state_specs=specs, model_axis="model")
    name = "train_step_milnce_chunked_2d"
    out = _jaxpr_checks(name, step, (state,) + batch(),
                        scan_reduction_free=True)
    same = (EXPECTED_COLLECTIVES[name]
            == EXPECTED_COLLECTIVES["train_step_milnce_2d"])
    out.append(CheckResult(
        name, "identical-to-dense", same,
        "" if same else "pins diverged — the chunked and dense 2-D "
        "steps must share one communication structure"))
    out.append(_recompile_check(name, step,
                                lambda s: (state,) + batch(s)))
    return out


def _entry_milnce_chunked_dispatch() -> list[CheckResult]:
    """ISSUE 12 acceptance: ``milnce_loss_chunked(backend='auto')``
    keeps a stable compiled path across its shape-dispatch rule — the
    probed shapes straddle ``milnce_pallas.prefers_pallas`` (one fused-
    kernel shape, one scan shape) and a second same-shape call of the
    jitted value-and-grad must hit the jit cache (the sdtw_pallas_
    dispatch gate discipline)."""
    import jax
    import numpy as np

    from milnce_tpu.losses.milnce_chunked import (milnce_default_chunk,
                                                  milnce_loss_chunked)
    from milnce_tpu.ops.milnce_pallas import prefers_pallas

    name = "milnce_chunked_dispatch"
    fn = jax.jit(jax.value_and_grad(
        lambda v, t: milnce_loss_chunked(v, t, backend="auto"),
        argnums=(0, 1)))
    # (B, K, D): one shape where the auto rule picks the fused kernel
    # (lane-aligned D, VMEM-resident blocks), one where it picks the
    # scan (D off the lane grid) — re-derive with prefers_pallas if the
    # rule moves
    shapes = [(8, 2, 128), (8, 2, 16)]
    sides = set()
    for b, k, d in shapes:
        chunk = milnce_default_chunk(b, k, b)
        sides.add(prefers_pallas(b, b, k, d, chunk))
    out = [CheckResult(
        name, "dispatch-coverage", sides == {True, False},
        "" if sides == {True, False} else
        f"probe shapes no longer straddle the auto rule ({sides}) — "
        "re-pick shapes so both backends stay gated")]

    def args(b, k, d, seed):
        r = np.random.default_rng(seed)
        return (r.standard_normal((b, d)).astype(np.float32),
                r.standard_normal((b * k, d)).astype(np.float32))

    if not hasattr(fn, "_cache_size"):
        out.append(CheckResult(name, "recompile", True,
                               "skipped: no _cache_size on this jax"))
        return out
    for b, k, d in shapes:
        fn(*args(b, k, d, 0))
        fn(*args(b, k, d, 1))
    n_entries = fn._cache_size()
    out.append(CheckResult(
        name, "recompile", n_entries == len(shapes),
        "" if n_entries == len(shapes) else
        f"{n_entries} jit-cache entries for {len(shapes)} dispatch "
        "shapes called twice each — the auto backend retraces per call "
        "(unstable dispatch input)"))
    return out


def _entry_sdtw_pallas_dispatch() -> list[CheckResult]:
    """ROADMAP item 1 loose end: ``SoftDTW(backend='auto')`` must keep a
    STABLE compiled path across its shape-dispatch rule — one jit-cache
    entry per dispatch shape, the second same-shape call a cache hit
    (no recompiles), with the probed shapes covering BOTH sides of
    ``prefers_pallas`` so the gate exercises kernel and scan paths alike
    (the same gate discipline as the conv impls)."""
    import jax
    import numpy as np

    from milnce_tpu.ops.softdtw import SoftDTW
    from milnce_tpu.ops.softdtw_pallas import prefers_pallas

    name = "sdtw_pallas_dispatch"
    sd = SoftDTW(gamma=0.1, dist_func="negative_dot", backend="auto")
    fn = jax.jit(jax.value_and_grad(lambda x, y: sd(x, y).sum()))
    # (B, N, M): one shape where the auto rule picks the Pallas kernel
    # (batch-on-lanes regime), one where it picks the scan (tables past
    # the VMEM budget) — re-derive with prefers_pallas if the rule moves
    shapes = [(64, 4, 4), (2, 160, 160)]
    sides = {prefers_pallas(b, n, m) for b, n, m in shapes}
    out = [CheckResult(
        name, "dispatch-coverage", sides == {True, False},
        "" if sides == {True, False} else
        f"probe shapes no longer straddle the auto rule ({sides}) — "
        "re-pick shapes so both backends stay gated")]

    def args(b, n, m, seed):
        r = np.random.default_rng(seed)
        return (r.standard_normal((b, n, 8)).astype(np.float32),
                r.standard_normal((b, m, 8)).astype(np.float32))

    if not hasattr(fn, "_cache_size"):
        out.append(CheckResult(name, "recompile", True,
                               "skipped: no _cache_size on this jax"))
        return out
    for b, n, m in shapes:
        fn(*args(b, n, m, 0))
        fn(*args(b, n, m, 1))
    n_entries = fn._cache_size()
    out.append(CheckResult(
        name, "recompile", n_entries == len(shapes),
        "" if n_entries == len(shapes) else
        f"{n_entries} jit-cache entries for {len(shapes)} dispatch "
        "shapes called twice each — the auto backend retraces per call "
        "(unstable dispatch input)"))
    return out


def _entry_train_step_sdtw3() -> list[CheckResult]:
    from milnce_tpu.config import LossConfig
    from milnce_tpu.train.step import make_train_step

    model, opt, mesh, state, batch = _setup()
    step = make_train_step(model, opt, mesh, donate=False,
                           loss_cfg=LossConfig(name="sdtw_3",
                                               sdtw_backend="scan"))
    return _jaxpr_checks("train_step_sdtw3", step, (state,) + batch())


def _entry_grad_cache_step() -> list[CheckResult]:
    from milnce_tpu.config import LossConfig
    from milnce_tpu.train.step import make_grad_cache_step

    model, opt, mesh, state, batch = _setup()
    step = make_grad_cache_step(model, opt, mesh, 2, donate=False,
                                loss_cfg=LossConfig(name="milnce"))
    return _jaxpr_checks("grad_cache_step_milnce", step, (state,) + batch(),
                         scan_reduction_free=True)


def _entry_retrieval_embed() -> list[CheckResult]:
    from milnce_tpu.train.step import (make_text_embed_fn,
                                       make_video_embed_fn)

    model, _opt, mesh, state, batch = _setup()
    varz = {"params": state.params, "batch_stats": state.batch_stats}
    vfn = make_video_embed_fn(model, mesh)
    tfn = make_text_embed_fn(model, mesh)
    out = _jaxpr_checks("video_embed", vfn, (varz, batch()[0]))
    out += _jaxpr_checks("text_embed", tfn, (varz, batch()[1]))
    out.append(_recompile_check("video_embed", vfn,
                                lambda s: (varz, batch(s)[0])))
    out.append(_recompile_check("text_embed", tfn,
                                lambda s: (varz, batch(s)[1])))
    return out


def _entry_softdtw_scan() -> list[CheckResult]:
    import jax
    import numpy as np

    from milnce_tpu.ops.softdtw import softdtw_scan

    name = "softdtw_scan_grad"

    def value(D, gamma):
        return softdtw_scan(D, gamma).sum()

    def make_D(seed):
        return np.abs(np.random.default_rng(seed).standard_normal(
            (4, 9, 7))).astype(np.float32)

    grad_fn = jax.jit(jax.value_and_grad(value))
    out = _jaxpr_checks(name, grad_fn, (make_D(0), np.float32(0.5)))
    out.append(_recompile_check(
        name, grad_fn, lambda s: (make_D(s), np.float32(0.5))))
    return out


def _entry_param_treedef() -> list[CheckResult]:
    import jax
    import jax.numpy as jnp

    from milnce_tpu.config import CONV_IMPLS, ModelConfig
    from milnce_tpu.models.build import build_model

    shapes = {}
    for impl in CONV_IMPLS:
        m = build_model(ModelConfig(conv_impl=impl, **_TINY))
        shapes[impl] = jax.eval_shape(
            m.init, jax.random.PRNGKey(0),
            jnp.zeros((2, _FRAMES, _SIZE, _SIZE, 3), jnp.float32),
            jnp.zeros((2, _WORDS), jnp.int32))
    ref_impl = CONV_IMPLS[0]
    ref = shapes[ref_impl]
    ref_td = jax.tree_util.tree_structure(ref)
    ref_leaves = jax.tree_util.tree_leaves(ref)
    out = []
    for impl in CONV_IMPLS[1:]:
        td = jax.tree_util.tree_structure(shapes[impl])
        leaves = jax.tree_util.tree_leaves(shapes[impl])
        same = (td == ref_td and len(leaves) == len(ref_leaves) and all(
            a.shape == b.shape and a.dtype == b.dtype
            for a, b in zip(leaves, ref_leaves)))
        out.append(CheckResult(
            "param_treedef", f"{ref_impl}-vs-{impl}", same,
            "" if same else "param trees diverged — the per-stage impl map "
            "and checkpoint portability both require identical layouts"))
    return out


def _entry_serve_embed_ladder() -> list[CheckResult]:
    """The serving engine's no-recompile-across-the-bucket-ladder gate
    (ISSUE 4 acceptance): after the startup warmup sweep, a FULL sweep of
    both embed entries over every bucket — including non-bucket request
    sizes that pad up — must create zero new jit-cache entries.  Also
    pins the entries' jaxprs collective-free at the top bucket."""
    import numpy as np

    from milnce_tpu.serving.engine import InferenceEngine

    model, _opt, mesh, state, _batch = _setup()
    varz = {"params": state.params, "batch_stats": state.batch_stats}
    import jax

    ndev = len(jax.devices())
    engine = InferenceEngine(model, varz, mesh, text_words=_WORDS,
                             video_shape=(_FRAMES, _SIZE, _SIZE, 3),
                             max_batch=2 * ndev)   # 2-rung ladder
    rng = np.random.default_rng(0)
    sizes = list(engine.buckets) + [1, engine.buckets[0] + 1]  # pad paths
    for n in sizes:
        engine.embed_text(rng.integers(
            0, _TINY["vocab_size"], (n, _WORDS)).astype(np.int32))
        engine.embed_video(rng.integers(
            0, 255, (n, _FRAMES, _SIZE, _SIZE, 3), dtype=np.uint8))
    n_re = engine.recompiles()
    out = [CheckResult(
        "serve_embed_ladder", "recompile", n_re == 0,
        "" if n_re == 0 else f"{n_re} jit-cache entries appeared AFTER the "
        "warmup bucket sweep — a request shape is escaping the ladder "
        "(weak-type drift, or a pad path missing)")]
    b = engine.buckets[-1]
    entries = engine.jit_entries()      # the supported analysis surface
    out += _jaxpr_checks("serve_text_embed", entries["text"],
                         (varz, np.zeros((b, _WORDS), np.int32)))
    out += _jaxpr_checks("serve_video_embed", entries["video"],
                         (varz, np.zeros((b, _FRAMES, _SIZE, _SIZE, 3),
                                         np.uint8)))
    return out


def _entry_serve_pool_embed() -> list[CheckResult]:
    """Pooled serving (ISSUE 10 acceptance): a 2-replica pool — single-
    device engines on the CPU backend, each with its own dispatch lock —
    sweeps the FULL bucket ladder (every rung plus pad-path sizes), both
    per-replica and routed through the pool, and must create ZERO
    jit-cache entries after warmup on EVERY replica.  Also pins each
    replica's embed jaxprs collective-free (a one-device shard_map ships
    nothing)."""
    import numpy as np

    from milnce_tpu.serving.pool import ReplicaPool

    model, _opt, _mesh, state, _batch = _setup()
    varz = {"params": state.params, "batch_stats": state.batch_stats}
    pool = ReplicaPool.build(model, varz, 2, text_words=_WORDS,
                             video_shape=(_FRAMES, _SIZE, _SIZE, 3),
                             max_batch=4, min_bucket=2,
                             probe_interval_s=60.0)
    try:
        rng = np.random.default_rng(0)
        sizes = list(pool.buckets) + [1, pool.buckets[0] + 1]  # pad paths

        def t_rows(n):
            return rng.integers(0, _TINY["vocab_size"],
                                (n, _WORDS)).astype(np.int32)

        def v_rows(n):
            return rng.integers(0, 255, (n, _FRAMES, _SIZE, _SIZE, 3),
                                dtype=np.uint8)

        for r in pool.replicas:           # every replica, every rung
            for n in sizes:
                r.engine.embed_text(t_rows(n))
                r.engine.embed_video(v_rows(n))
        for n in sizes:                   # and routed through the pool
            pool.embed_text(t_rows(n))
            pool.embed_video(v_rows(n))
        out = []
        for r in pool.replicas:
            n_re = r.engine.recompiles()
            out.append(CheckResult(
                "serve_pool_embed", f"recompile-replica{r.rid}", n_re == 0,
                "" if n_re == 0 else f"{n_re} jit-cache entries appeared "
                f"AFTER the warmup sweep on replica {r.rid} — a request "
                "shape is escaping the replica's ladder"))
        b = pool.buckets[-1]
        entries = pool.replicas[0].engine.jit_entries()
        out += _jaxpr_checks("serve_pool_text_embed", entries["text"],
                             (varz, np.zeros((b, _WORDS), np.int32)))
        out += _jaxpr_checks("serve_pool_video_embed", entries["video"],
                             (varz, np.zeros((b, _FRAMES, _SIZE, _SIZE, 3),
                                             np.uint8)))
        return out
    finally:
        pool.close()


def _entry_serve_quant_embed_ladder() -> list[CheckResult]:
    """Quantized edge engine (ISSUE 19): the int8 tower behind the SAME
    bucket ladder — quantize the tiny model per the readiness rule, run
    the full post-warmup sweep (every rung plus pad-path sizes), and
    require zero new jit-cache entries; then pin both entries' jaxprs
    collective-free.  The in-jit dequantize must change neither the
    recompile story nor the communication structure — that is what makes
    a quantized export a drop-in replica class in a mixed pool."""
    import numpy as np

    from milnce_tpu.quant.quantize import (QuantizedModel,
                                           quantize_variables)
    from milnce_tpu.serving.engine import InferenceEngine

    model, _opt, mesh, state, _batch = _setup()
    varz = {"params": state.params, "batch_stats": state.batch_stats}
    qvarz = quantize_variables(varz)
    qmodel = QuantizedModel(model)
    import jax

    ndev = len(jax.devices())
    engine = InferenceEngine(qmodel, qvarz, mesh, text_words=_WORDS,
                             video_shape=(_FRAMES, _SIZE, _SIZE, 3),
                             max_batch=2 * ndev)   # 2-rung ladder
    rng = np.random.default_rng(0)
    sizes = list(engine.buckets) + [1, engine.buckets[0] + 1]  # pad paths
    for n in sizes:
        engine.embed_text(rng.integers(
            0, _TINY["vocab_size"], (n, _WORDS)).astype(np.int32))
        engine.embed_video(rng.integers(
            0, 255, (n, _FRAMES, _SIZE, _SIZE, 3), dtype=np.uint8))
    n_re = engine.recompiles()
    out = [CheckResult(
        "serve_quant_embed_ladder", "recompile", n_re == 0,
        "" if n_re == 0 else f"{n_re} jit-cache entries appeared AFTER "
        "the warmup bucket sweep on the QUANTIZED engine — the dequant "
        "prologue is destabilizing the jit cache (scales tree drift?)")]
    b = engine.buckets[-1]
    entries = engine.jit_entries()      # the supported analysis surface
    out += _jaxpr_checks("serve_quant_text_embed", entries["text"],
                         (qvarz, np.zeros((b, _WORDS), np.int32)))
    out += _jaxpr_checks("serve_quant_video_embed", entries["video"],
                         (qvarz, np.zeros((b, _FRAMES, _SIZE, _SIZE, 3),
                                          np.uint8)))
    return out


def _entry_serve_index_topk() -> list[CheckResult]:
    """Sharded retrieval: exactly 2 all_gathers (the (Q, k) score and
    index candidate lists), no f64, and the double-call recompile check
    on the jitted top-k program."""
    import jax
    import numpy as np

    from milnce_tpu.serving.index import DeviceRetrievalIndex

    _model, _opt, mesh, _state, _batch = _setup()
    ndev = len(jax.devices())
    rng = np.random.default_rng(0)
    corpus = rng.standard_normal((3 * ndev - 2, _TINY["embedding_dim"]))
    index = DeviceRetrievalIndex(mesh, corpus.astype(np.float32), k=3,
                                 query_buckets=(ndev,))
    name = "serve_index_topk"
    fn, operands = index.topk_program()  # the supported analysis surface

    def make_q(seed):
        # committed to the index's replicated query sharding — an
        # uncommitted host array would key a SEPARATE jit-cache entry
        # and false-positive the recompile detector
        r = np.random.default_rng(seed)
        return jax.device_put(
            r.standard_normal((ndev, index.dim)).astype(np.float32),
            index.query_sharding)

    out = _jaxpr_checks(name, fn, operands + (make_q(0),))
    out.append(_recompile_check(
        name, fn, lambda s: operands + (make_q(s),)))
    return out


def _entry_serve_live_index() -> list[CheckResult]:
    """Generation-swapped live index (ISSUE 14): the SAME pinned
    program as ``serve_index_topk`` (2 all_gathers, no f64), plus the
    tentpole's recompile story — two ingest+swap cycles INSIDE a corpus
    rung followed by queries must leave the query path's jit cache
    untouched (``recompiles() == 0``), because swapped generations at
    one rung are shape-identical."""
    import jax
    import numpy as np

    from milnce_tpu.serving.live_index import LiveRetrievalIndex

    _model, _opt, mesh, _state, _batch = _setup()
    ndev = len(jax.devices())
    rng = np.random.default_rng(0)
    dim = _TINY["embedding_dim"]
    corpus = rng.standard_normal((3 * ndev - 2, dim)).astype(np.float32)
    index = LiveRetrievalIndex(mesh, corpus, k=3, query_buckets=(ndev,))
    name = "serve_live_index"
    try:
        q = rng.standard_normal((ndev, dim)).astype(np.float32)
        index.topk(q)
        for _ in range(2):              # two swaps inside the boot rung
            index.add(rng.standard_normal((2, dim)).astype(np.float32))
            if not index.flush(30.0):
                return [CheckResult(name, "swap", False,
                                    "ingest flush timed out — the "
                                    "builder never published")]
            index.topk(q)
        n_re = index.recompiles()
        out = [CheckResult(
            name, "recompile-across-swaps", n_re == 0,
            "" if n_re == 0 else f"{n_re} jit-cache entries appeared on "
            "the QUERY path across generation swaps — a swap is leaking "
            "a compile (rung rule broken, or the builder stopped "
            "warming new shapes)")]
        fn, operands = index.topk_program()
        qd = jax.device_put(q, index.query_sharding)
        out += _jaxpr_checks(name, fn, operands + (qd,))
        return out
    finally:
        index.close()


ENTRY_POINTS = {
    "train_step_milnce": _entry_train_step_milnce,
    "train_step_milnce_guarded": _entry_train_step_milnce_guarded,
    "train_step_milnce_instrumented": _entry_train_step_milnce_instrumented,
    "train_step_curriculum": _entry_train_step_curriculum,
    "train_step_sdtw3": _entry_train_step_sdtw3,
    "grad_cache_step_milnce": _entry_grad_cache_step,
    "train_step_milnce@4way": _entry_train_step_4way,
    "train_step_milnce_2d": _entry_train_step_2d,
    "grad_cache_2d": _entry_grad_cache_2d,
    "train_step_milnce_chunked": _entry_train_step_milnce_chunked,
    "train_step_milnce_chunked_2d": _entry_train_step_milnce_chunked_2d,
    "milnce_chunked_dispatch": _entry_milnce_chunked_dispatch,
    "sdtw_pallas_dispatch": _entry_sdtw_pallas_dispatch,
    "retrieval_embed": _entry_retrieval_embed,
    "softdtw_scan": _entry_softdtw_scan,
    "param_treedef": _entry_param_treedef,
    "serve_embed_ladder": _entry_serve_embed_ladder,
    "serve_quant_embed_ladder": _entry_serve_quant_embed_ladder,
    "serve_index_topk": _entry_serve_index_topk,
    "serve_pool_embed": _entry_serve_pool_embed,
    "serve_live_index": _entry_serve_live_index,
}


def run_trace_invariants(entries=None) -> list[CheckResult]:
    """Run the invariant checks; entries=None runs all registered ones.
    Builder exceptions become failing results, never crashes — the CLI
    must always finish its report."""
    results: list[CheckResult] = []
    for name in (entries or ENTRY_POINTS):
        try:
            results.extend(ENTRY_POINTS[name]())
        except Exception as exc:                    # pragma: no cover
            results.append(CheckResult(name, "build", False,
                                       f"{type(exc).__name__}: {exc}"))
    return results
