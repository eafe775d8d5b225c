"""graftlint Pass 5: numerics — static precision-flow analysis.

The whole perf story runs on bf16 (BENCH_NOTES.md headline: batch 256,
bf16) and ROADMAP item 5 wants an int8 edge tier — but precision
placement in this repo was, until this pass, an emergent property:
Pass 4's GL015 named the f32 BatchNorm intermediates as the top HBM
contributor *on the bf16 model* and nobody could say whether that f32
residency was load-bearing or accidental.  Pass 5 makes dtype placement
a STATIC, pinned property, the same "pin it, then change it
consciously" treatment Passes 2/4 gave collectives and bytes:

- **dtype census**: every registered entry's closed jaxpr is walked and
  its buffer bytes are bucketed by dtype (entry args + every primitive
  output, per level; ``call``/``shard_map`` results are counted once at
  the level that materializes them).
- **cast inventory**: every ``convert_element_type`` is NAMED by its
  route and location — ``"f32->bf16 @ state/params/conv1/kernel"`` for
  a cast of an entry arg, ``"bf16->f32 @ dot_general"`` for a cast of
  an intermediate (by producing primitive, GL015-style).  An appearing
  or vanishing cast is a readable diff, not a mystery loss-curve
  divergence.
- **f32-residency set**: the labels that must stay f32 — BatchNorm
  statistics (``batch_stats``), optimizer moments (``mu``/``nu``) and
  the log-domain accumulators (``log``/``log1p`` operands, i.e. the
  logsumexp/loss chain) — audited against the traced program.

Three rules ride on the walk (catalogue: analysis/rules.py):

- **GL016 low-precision-accumulation**: an add-based reduction
  (``reduce_sum``), ``dot_general`` accumulation or cross-replica
  ``psum`` whose accumulator dtype is bf16/f16 at reduction extent
  >= ``GL016_MIN_EXTENT`` — the missing ``preferred_element_type=f32``
  detector.  ``psum`` fires at ANY extent: its true extent is the pod's
  replica count, which the jaxpr doesn't carry and which exceeds any
  sensible threshold at real scale.
- **GL017 unstabilized-exp-domain**: the jaxpr half — every ``exp``
  whose operand's producer chain (through shape/dtype/scale
  passthroughs) does not reach a subtraction or a bounded-domain op.
  The AST half lives in astlint (pattern over ``losses/``, inline-
  suppressible); HERE deliberately-unguarded sites are registered
  per entry in ``EXPECTED_UNGUARDED_EXP`` — entry-level discipline.
- **GL018 dtype-boundary-drift**: the census and the cast inventory are
  pinned per entry (``EXPECTED_DTYPE_CENSUS`` / ``EXPECTED_CASTS``)
  exactly like collective multisets; drift fails tier-1 with a named
  diff and the CLI prints the paste-ready re-pin dict.

Known approximations (documented in ANALYSIS.md): loop bodies are
censused once (a scan's per-iteration buffers are one program buffer);
``add``-chain accumulations inside scan carries are not GL016 sites
(the registered entries reduce via ``reduce_sum``/``psum``); guard
detection follows the FIRST operand through passthrough ops, so a
guard arriving via the second operand of a ``mul`` is conservatively
treated as present only if the chain bottoms out at a boundary.

Everything runs on the hermetic 8-virtual-CPU-device mesh; jax imports
live inside functions so astlint stays importable without jax.
``scripts/precision_audit.py`` is the CLI (NUMERICS.md, ``--check``,
``--what-if --dtype bf16``, and the quantization-readiness report over
an export artifact — the ROADMAP item 5 feed).
"""

from __future__ import annotations

import functools
import hashlib
import json
from dataclasses import dataclass, field

from milnce_tpu.analysis.trace_invariants import CheckResult

# GL016 reduction-extent floor: summing N same-sign bf16 terms loses
# ~log2(N) of the 8 mantissa bits, so 64 terms (6 bits) is where the
# fraction is mostly gone.  Below it, the finding costs more attention
# than the ulps cost accuracy.
GL016_MIN_EXTENT = 64

# accumulator dtypes GL016 objects to (short names, see _short)
LOW_PRECISION = ("bf16", "f16")

_DTYPE_SHORT = {
    "float64": "f64", "float32": "f32", "bfloat16": "bf16",
    "float16": "f16", "int64": "i64", "int32": "i32", "int16": "i16",
    "int8": "i8", "uint64": "u64", "uint32": "u32", "uint8": "u8",
    "bool": "bool",
}


def _short(dtype) -> str:
    s = str(dtype)
    return _DTYPE_SHORT.get(s, s)


@dataclass
class NumericsAudit:
    """Per-entry result of the dtype-flow walk."""
    entry: str = ""
    census: dict = field(default_factory=dict)   # short dtype -> bytes
    casts: dict = field(default_factory=dict)    # "src->dst @ loc" -> n
    gl016_sites: tuple = ()                      # low-precision accums
    exp_sites: tuple = ()                        # unguarded exp (jaxpr)
    f32_residency: tuple = ()                    # labels audited f32
    residency_violations: tuple = ()             # must-be-f32 that isn't
    mesh: str = ""

    def census_hash(self) -> str:
        """12-hex digest over (census, casts) — the bench-record /
        obs_report cross-precision identity (a dtype-structure change
        shows as a differing hash, like the sharding-map hash)."""
        blob = json.dumps({"census": self.census, "casts": self.casts},
                          sort_keys=True)
        return hashlib.sha256(blob.encode()).hexdigest()[:12]


# --------------------------------------------------------------------------
# the dtype-flow walk
# --------------------------------------------------------------------------

# ops through which a max-subtraction guard still reaches the exp:
# shape/dtype changes, sign/scale changes.  The chain follows the FIRST
# operand (documented approximation).
_GUARD_PASSTHROUGH = frozenset({
    "convert_element_type", "broadcast_in_dim", "reshape", "transpose",
    "squeeze", "expand_dims", "copy", "stop_gradient", "slice",
    "dynamic_slice", "neg", "abs", "mul", "div",
})

# producers whose output domain is bounded above — exp of these cannot
# overflow (clamp/min/logistic/tanh and the max-trick's own sub)
_GUARD_TERMINAL = frozenset({
    "sub", "min", "reduce_min", "clamp", "logistic", "tanh", "erf",
    "log", "log1p",
})


def _exp_guarded(v, producer, depth: int = 12) -> bool:
    """Does ``v``'s producer chain show a max-subtraction (or bounded
    domain) before ``depth`` hops?  Chains that bottom out at a jaxpr
    boundary (entry arg, nest invar, literal) are treated guarded —
    the guard may live one level up, and a boundary false-positive
    would punish every scan-carried accumulator."""
    from milnce_tpu.analysis.memplan import _is_literal

    for _ in range(depth):
        if _is_literal(v):
            return True
        eqn = producer.get(v)
        if eqn is None:
            return True
        name = eqn.primitive.name
        if name in _GUARD_TERMINAL:
            return True
        if name in _GUARD_PASSTHROUGH:
            v = eqn.invars[0]
            continue
        return False
    return False


def _gl016_eqn(eqn) -> list:
    """Low-precision-accumulation sites for one equation."""
    from milnce_tpu.analysis.memplan import _is_dropvar

    name = eqn.primitive.name
    sites = []
    if name == "reduce_sum":
        op = eqn.invars[0]
        if _short(op.aval.dtype) in LOW_PRECISION:
            extent = 1
            for a in eqn.params.get("axes", ()):
                extent *= int(op.aval.shape[a])
            if extent >= GL016_MIN_EXTENT:
                sites.append(
                    f"reduce_sum {op.aval.str_short()} extent {extent} — "
                    f"{_short(op.aval.dtype)} accumulator")
    elif name == "dot_general":
        out = eqn.outvars[0]
        if not _is_dropvar(out) and _short(out.aval.dtype) in LOW_PRECISION:
            (lhs_c, _rhs_c), _batch = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval
            extent = 1
            for d in lhs_c:
                extent *= int(lhs.shape[d])
            if extent >= GL016_MIN_EXTENT:
                sites.append(
                    f"dot_general {out.aval.str_short()} contraction "
                    f"{extent} — accumulates in {_short(out.aval.dtype)} "
                    "(preferred_element_type=f32 missing)")
    elif name == "psum":
        for op in eqn.invars:
            aval = getattr(op, "aval", None)
            if aval is not None and _short(aval.dtype) in LOW_PRECISION:
                sites.append(
                    f"psum {aval.str_short()} — low-precision "
                    "cross-replica accumulator (extent = replica count)")
    return sites


def _audit_level(jaxpr, lab, audit_state) -> None:
    """One jaxpr level of the walk.  ``lab`` maps this level's vars to
    names (entry-arg tree paths zipped through ``call``/``shard_map``
    boundaries); intermediates are named by producing primitive."""
    from milnce_tpu.analysis.memplan import (_is_dropvar, _is_literal,
                                             _nested, _open, aval_bytes)

    census, casts, gl016, exps, resid_bad = audit_state
    producer: dict = {}
    for eqn in jaxpr.eqns:
        kind, bodies = _nested(eqn)
        # census: primitive outputs materialize at this level; a call /
        # shard_map result IS its body's output buffer — count it once,
        # inside (loop/branch outputs are fresh stacked buffers: count)
        if kind not in ("call", "shard_map"):
            for v in eqn.outvars:
                if _is_dropvar(v):
                    continue
                key = _short(v.aval.dtype)
                census[key] = census.get(key, 0) + aval_bytes(v.aval)
        # cast inventory
        if eqn.primitive.name == "convert_element_type":
            src = eqn.invars[0]
            dst = _short(eqn.params.get("new_dtype",
                                        eqn.outvars[0].aval.dtype))
            if _is_literal(src):
                loc, sdt = "literal", _short(src.aval.dtype)
            elif src in lab:
                loc, sdt = lab[src], _short(src.aval.dtype)
            elif src in producer:
                loc = producer[src].primitive.name
                sdt = _short(src.aval.dtype)
            else:
                loc, sdt = "nest-boundary", _short(src.aval.dtype)
            key = f"{sdt}->{dst} @ {loc}"
            casts[key] = casts.get(key, 0) + 1
        # GL016 low-precision accumulation
        gl016.extend(_gl016_eqn(eqn))
        # GL017 jaxpr half: unguarded exp
        if eqn.primitive.name == "exp":
            op = eqn.invars[0]
            if not _exp_guarded(op, producer):
                via = (lab.get(op) or
                       (producer[op].primitive.name if op in producer
                        else "boundary"))
                exps.append(f"exp {op.aval.str_short()} of {via}")
        # f32-residency: the log-domain accumulators (logsumexp / loss
        # chain) must not run in a low-precision dtype
        if eqn.primitive.name in ("log", "log1p"):
            op = eqn.invars[0]
            aval = getattr(op, "aval", None)
            if aval is not None and _short(aval.dtype) in LOW_PRECISION:
                resid_bad.append(
                    f"{eqn.primitive.name} operand {aval.str_short()} — "
                    "log-domain accumulator demoted below f32")
        for v in eqn.outvars:
            if not _is_dropvar(v):
                producer[v] = eqn
        # recurse, threading labels through call-kind boundaries
        for body in bodies:
            bj = _open(body)
            sub_lab: dict = {}
            if (kind in ("call", "shard_map")
                    and len(bj.invars) == len(eqn.invars)):
                for bv, ov in zip(bj.invars, eqn.invars):
                    if not _is_literal(ov) and ov in lab:
                        sub_lab[bv] = lab[ov]
            _audit_level(bj, sub_lab, audit_state)


# arg-leaf label substrings whose buffers belong to the f32-residency
# set: BatchNorm statistics and Adam moments.  The paper's own recipe
# (and PERF.md's "Batch cliffs" finding) keeps these f32 on the bf16
# model — this audit is what makes that deliberate.
_RESIDENT_MARKERS = ("batch_stats", "/mu/", "/nu/")


def audit_jaxpr(closed_jaxpr, *, labels=None, entry="") -> NumericsAudit:
    """Dtype-flow walk of an entry's closed jaxpr -> NumericsAudit."""
    from milnce_tpu.analysis.memplan import _open, aval_bytes

    jaxpr = _open(closed_jaxpr)
    n = len(jaxpr.invars)
    labels = list(labels) if labels is not None else [f"arg{i}"
                                                      for i in range(n)]
    census: dict = {}
    casts: dict = {}
    gl016: list = []
    exps: list = []
    resid_bad: list = []
    resident: list = []
    lab = dict(zip(jaxpr.invars, labels))
    for v, label in zip(jaxpr.invars, labels):
        key = _short(v.aval.dtype)
        census[key] = census.get(key, 0) + aval_bytes(v.aval)
        if any(m in label for m in _RESIDENT_MARKERS):
            resident.append(label)
            if _short(v.aval.dtype) != "f32":
                resid_bad.append(
                    f"{label} is {_short(v.aval.dtype)} — BN stats and "
                    "optimizer moments must stay f32")
    for v in jaxpr.constvars:
        key = _short(v.aval.dtype)
        census[key] = census.get(key, 0) + aval_bytes(v.aval)
    _audit_level(jaxpr, lab, (census, casts, gl016, exps, resid_bad))
    return NumericsAudit(entry=entry, census=census, casts=casts,
                         gl016_sites=tuple(gl016), exp_sites=tuple(exps),
                         f32_residency=tuple(resident),
                         residency_violations=tuple(resid_bad))


def audit_fn(fn, args, *, argnames=None, entry="") -> NumericsAudit:
    """Trace ``fn(*args)`` and audit — the bench-record hook (every
    record carries ``dtype_census_hash``) and the planted-fixture path."""
    import jax

    from milnce_tpu.analysis.memplan import arg_leaf_labels

    closed = jax.make_jaxpr(fn)(*args)
    labels = (arg_leaf_labels(args, argnames) if argnames is not None
              else None)
    return audit_jaxpr(closed, labels=labels, entry=entry)


# --------------------------------------------------------------------------
# registered entries + pins (the Pass 5 gate)
# --------------------------------------------------------------------------

def entry_names() -> tuple:
    """Every audited entry: the Pass 4 registry (same traced programs,
    shared cache — zero extra tracing) plus the curriculum stage-2
    shape, which memplan doesn't price but whose dtype boundaries must
    match the stage-1 program's structurally."""
    from milnce_tpu.analysis.memplan import _entries

    return tuple(_entries()) + ("train_step_curriculum@s1",)


@functools.lru_cache(maxsize=None)
def _numerics_traced(name: str):
    """(closed_jaxpr, labels, mesh) for one audited entry."""
    from milnce_tpu.analysis.memplan import (_STEP_ARGNAMES, _entries,
                                             _traced_entry,
                                             arg_leaf_labels)

    if name == "train_step_curriculum@s1":
        import jax
        import numpy as np

        from milnce_tpu.analysis.trace_invariants import (_FRAMES, _SIZE,
                                                          _TINY, _WORDS,
                                                          _setup)
        from milnce_tpu.train.step import make_train_step

        model, opt, mesh, state, _batch = _setup()
        step = make_train_step(model, opt, mesh, donate=False)
        b = 2 * len(jax.devices())
        rng = np.random.default_rng(0)
        args = (state,
                rng.integers(0, 255, (b, 2 * _FRAMES, _SIZE, _SIZE, 3),
                             dtype=np.uint8),
                rng.integers(0, _TINY["vocab_size"],
                             (b, _WORDS)).astype(np.int32),
                np.zeros((b,), np.float32))
        return (jax.make_jaxpr(step)(*args),
                arg_leaf_labels(args, _STEP_ARGNAMES), "8x1 (data)")
    closed, labels, _donated = _traced_entry(name)
    return closed, labels, _entries()[name].mesh


def audit_entry(name: str) -> NumericsAudit:
    closed, labels, mesh = _numerics_traced(name)
    audit = audit_jaxpr(closed, labels=labels, entry=name)
    audit.mesh = mesh
    return audit


def check_entry_names(entries) -> None:
    """A typo'd entry filter must fail loudly, not audit zero entries
    and pass vacuously (the memplan/stage_probe scope discipline)."""
    if entries is None:
        return
    unknown = set(entries) - set(entry_names())
    if unknown:
        raise ValueError(
            f"unknown numerics entries: {sorted(unknown)} (registered: "
            f"{', '.join(entry_names())})")


def audit_all(entries=None) -> dict:
    """name -> NumericsAudit for the registered entries (or a subset)."""
    check_entry_names(entries)
    audits: dict = {}
    for name in entry_names():
        if entries is not None and name not in entries:
            continue
        audits[name] = audit_entry(name)
    return audits


# Registered low-precision accumulations (GL016): entry -> tuple of
# site labels that are DELIBERATE.  Empty on the f32 tree — the bf16
# what-if is where sites appear, and NUMERICS.md names them.
EXPECTED_GL016 = {}

# Registered unguarded-exp sites (GL017 jaxpr half): entry -> count.
# Absent entry = expected 0.  Each nonzero registration is an audited
# decision, same discipline as a re-pin.  Currently empty: every exp in
# the registered programs bottoms out at a subtraction or a bounded-
# domain producer — including sdtw_3's deliberately max-unguarded
# negative term (losses/dtw_losses.py), whose operand chain reaches the
# pairwise-distance subtraction and so reads as domain-bounded here;
# the AST half carries its audited inline suppression instead.
EXPECTED_UNGUARDED_EXP = {}

# Pinned per-entry dtype census (GL018): short dtype -> program buffer
# bytes.  Like EXPECTED_PEAK_BYTES: never changes SILENTLY — a
# deliberate precision change re-pins in the same commit.  Derived by
# ``python scripts/precision_audit.py`` (prints the re-pin dict on
# drift).  Reading the milnce step: everything numeric is f32 on the
# CPU entry config (the tiny entries trace the f32 model — bf16
# placement is the what-if axis), u8 is the raw video batch, bool the
# finite-guard / mask plumbing, i32 the token ids and step counters.
EXPECTED_DTYPE_CENSUS = {
    "train_step_milnce": {
        "i32": 580, "f32": 64258704, "u8": 196608, "bool": 216534},
    "train_step_milnce_guarded": {
        "i32": 596, "f32": 70595796, "u8": 196608, "bool": 744209},
    # 4-way elastic-resume layout: same program, 2 clips/chip — u8 video
    # doubles per chip, f32 shrinks (fewer psum partials), casts as 8-way
    "train_step_milnce@4way": {
        "i32": 420, "f32": 64253488, "u8": 98304, "bool": 216502},
    "train_step_sdtw3": {
        "i32": 1948, "f32": 67741732, "u8": 196608, "bool": 233142},
    "grad_cache_step_milnce": {
        "i32": 620, "f32": 64757200, "u8": 221184, "bool": 109366},
    "train_step_milnce_chunked": {
        "i32": 732, "f32": 64271024, "u8": 196608, "bool": 216556},
    "milnce_loss_dense": {"f32": 17632532, "i32": 2824, "bool": 329216},
    "milnce_loss_chunked": {"f32": 3516708, "i32": 6280, "bool": 84928},
    "train_step_milnce_2d": {
        "i32": 600, "f32": 49570192, "u8": 196608, "bool": 216534},
    "grad_cache_2d": {
        "i32": 640, "f32": 50068688, "u8": 221184, "bool": 109366},
    "serve_text_embed@b0": {"f32": 2120192, "i32": 220, "bool": 5},
    "serve_text_embed@b1": {"f32": 2121664, "i32": 440, "bool": 10},
    "serve_video_embed@b0": {"f32": 4646720, "u8": 98304},
    "serve_video_embed@b1": {"f32": 7143872, "u8": 196608},
    # the shard's scan + top-k is ONE pallas_call since PR 38: its two
    # (Q, k) outputs count here, its body (the scores, the pad mask, the
    # running best-k) does not — the census stops at a kernel, as at
    # grouped_matmul's
    "serve_index_topk": {"f32": 3008, "i32": 1480, "bool": 24},
    "serve_index_topk@gen": {"f32": 3520, "i32": 1480, "bool": 24},
    "serve_pool_text_embed@b0": {"f32": 2121664, "i32": 160, "bool": 10},
    "serve_pool_video_embed@b1": {"f32": 12138176, "u8": 49152},
    # quantized edge engine (ISSUE 19): the i8 bucket IS the resident
    # weight tree (21 quantized leaves of the tiny model), f32 covers
    # the dequant copies + activations.  GL016-clean by construction:
    # int8 is a STORAGE dtype here — every dot_general runs on the
    # dequantized f32 operands, so no low-precision accumulator exists
    # for the rule to fire on (the ISSUE 19 w8/f32-accum contract)
    "serve_quant_text_embed@b1": {
        "f32": 2124308, "i8": 524992, "i32": 440, "bool": 10},
    "serve_quant_video_embed@b1": {
        "f32": 9241364, "i8": 524992, "u8": 196608},
    "train_step_curriculum@s1": {
        "i32": 580, "f32": 81928848, "u8": 393216, "bool": 430550},
}

# Pinned per-entry cast inventory (GL018): "src->dst @ location" -> n.
# An appearing cast is a new precision boundary (HBM + accuracy both
# care); a vanishing one is a silently demoted accumulator.  The
# recurring boundaries, named: ``u8->f32 @ video`` is the input
# normalization (the ONE place raw frames widen), ``bool->f32 @ eq``
# the masked-mean denominators, ``i32->f32 @ .../count`` the schedule
# step feeding the learning rate, ``f32->f32 @ max`` weak-type
# canonicalization at the loss clamps, and the ``@ nest-boundary``
# routes are casts whose source enters through a scan/grad-cache body
# invar (the microbatch slices in grad-cache entries).
EXPECTED_CASTS = {
    "train_step_milnce": {
        "u8->f32 @ video": 1, "bool->f32 @ eq": 4,
        "i32->f32 @ state/opt_state/hyperparams_states/learning_rate/count": 1,
        "f32->f32 @ max": 2,
        "i32->f32 @ jit": 2},
    "train_step_milnce_guarded": {
        "u8->f32 @ video": 1, "bool->f32 @ eq": 4,
        "i32->f32 @ state/opt_state/hyperparams_states/learning_rate/count": 1,
        "f32->f32 @ max": 2,
        "i32->f32 @ jit": 2, "bool->i32 @ not": 1},
    "train_step_milnce@4way": {
        "u8->f32 @ video": 1, "bool->f32 @ eq": 4,
        "i32->f32 @ state/opt_state/hyperparams_states/learning_rate/count": 1,
        "f32->f32 @ max": 2,
        "i32->f32 @ jit": 2},
    "train_step_sdtw3": {
        "u8->f32 @ video": 1, "bool->f32 @ eq": 4,
        "i32->i32 @ nest-boundary": 12, "f32->f32 @ nest-boundary": 18,
        "i32->f32 @ state/opt_state/hyperparams_states/learning_rate/count": 1,
        "f32->f32 @ max": 2, "i32->f32 @ jit": 2},
    "grad_cache_step_milnce": {
        "u8->f32 @ nest-boundary": 2, "bool->f32 @ eq": 4,
        "i32->f32 @ state/opt_state/hyperparams_states/learning_rate/count": 1,
        "f32->f32 @ max": 2,
        "i32->f32 @ jit": 2},
    "train_step_milnce_chunked": {
        "u8->f32 @ video": 1, "bool->f32 @ eq": 3,
        "i32->f32 @ nest-boundary": 4, "f32->f32 @ nest-boundary": 4,
        "i32->f32 @ state/opt_state/hyperparams_states/learning_rate/count": 1,
        "f32->f32 @ max": 2,
        "i32->f32 @ jit": 2},
    "milnce_loss_dense": {"bool->f32 @ eq": 3},
    "milnce_loss_chunked": {
        "f32->f32 @ nest-boundary": 4, "bool->f32 @ eq": 2},
    "train_step_milnce_2d": {
        "u8->f32 @ video": 1, "bool->f32 @ eq": 4,
        "i32->f32 @ state/opt_state/hyperparams_states/learning_rate/count": 1,
        "f32->f32 @ max": 2,
        "i32->f32 @ jit": 2},
    "grad_cache_2d": {
        "u8->f32 @ nest-boundary": 2, "bool->f32 @ eq": 4,
        "i32->f32 @ state/opt_state/hyperparams_states/learning_rate/count": 1,
        "f32->f32 @ max": 2,
        "i32->f32 @ jit": 2},
    "serve_text_embed@b0": {},
    "serve_text_embed@b1": {},
    "serve_video_embed@b0": {"u8->f32 @ video": 1},
    "serve_video_embed@b1": {"u8->f32 @ video": 1},
    # the weak-typed -inf of the pad mask's `where` went into the kernel
    # with the mask (PR 38): no cast is left in the top-k program
    "serve_index_topk": {},
    "serve_index_topk@gen": {},
    "serve_pool_text_embed@b0": {},
    "serve_pool_video_embed@b1": {"u8->f32 @ video": 1},
    # quant entries: exactly ONE named i8->f32 route per quantized leaf
    # — the dequant boundary inventory.  A vanished route is a weight
    # silently left f32 in the artifact; an extra one is a leaf the
    # readiness rule stopped protecting.  Both towers dequantize the
    # FULL tree (the jit entry binds the whole variables arg; XLA DCEs
    # the unused tower's convs post-trace, but the traced program —
    # what this pass audits — carries every route).
    "serve_quant_text_embed@b1": dict.fromkeys([
        f"i8->f32 @ variables/params/{k}" for k in (
            "conv1/conv/kernel", "conv_2b/conv/kernel",
            "conv_2c/conv_spatial/kernel", "conv_2c/conv_temporal/kernel",
            "fc/kernel", "gating/fc/kernel",
            "mixed_3b/conv_b0/conv/kernel",
            "mixed_3b/conv_b1_a/conv/kernel",
            "mixed_3b/conv_b1_b/conv_spatial/kernel",
            "mixed_3b/conv_b1_b/conv_temporal/kernel",
            "mixed_3b/conv_b2_a/conv/kernel",
            "mixed_3b/conv_b2_b/conv_spatial/kernel",
            "mixed_3b/conv_b2_b/conv_temporal/kernel",
            "mixed_3b/conv_b3_b/conv/kernel",
            "mixed_3b/gating_b0/fc/kernel",
            "mixed_3b/gating_b1/fc/kernel",
            "mixed_3b/gating_b2/fc/kernel",
            "mixed_3b/gating_b3/fc/kernel",
            "text_module/fc1/kernel", "text_module/fc2/kernel",
            "text_module/word_embd/embedding")], 1),
    "serve_quant_video_embed@b1": dict.fromkeys(["u8->f32 @ video"] + [
        f"i8->f32 @ variables/params/{k}" for k in (
            "conv1/conv/kernel", "conv_2b/conv/kernel",
            "conv_2c/conv_spatial/kernel", "conv_2c/conv_temporal/kernel",
            "fc/kernel", "gating/fc/kernel",
            "mixed_3b/conv_b0/conv/kernel",
            "mixed_3b/conv_b1_a/conv/kernel",
            "mixed_3b/conv_b1_b/conv_spatial/kernel",
            "mixed_3b/conv_b1_b/conv_temporal/kernel",
            "mixed_3b/conv_b2_a/conv/kernel",
            "mixed_3b/conv_b2_b/conv_spatial/kernel",
            "mixed_3b/conv_b2_b/conv_temporal/kernel",
            "mixed_3b/conv_b3_b/conv/kernel",
            "mixed_3b/gating_b0/fc/kernel",
            "mixed_3b/gating_b1/fc/kernel",
            "mixed_3b/gating_b2/fc/kernel",
            "mixed_3b/gating_b3/fc/kernel",
            "text_module/fc1/kernel", "text_module/fc2/kernel",
            "text_module/word_embd/embedding")], 1),
    "train_step_curriculum@s1": {
        "u8->f32 @ video": 1, "bool->f32 @ eq": 4,
        "i32->f32 @ state/opt_state/hyperparams_states/learning_rate/count": 1,
        "f32->f32 @ max": 2,
        "i32->f32 @ jit": 2},
}


def _check_gl016(name: str, audit: NumericsAudit) -> CheckResult:
    allowed = set(EXPECTED_GL016.get(name, ()))
    bad = [s for s in audit.gl016_sites if s not in allowed]
    return CheckResult(
        name, "GL016-low-precision-accum", not bad,
        "" if not bad else
        "; ".join(bad[:4]) + " — accumulate in f32 "
        "(preferred_element_type / astype) or register the site in "
        "EXPECTED_GL016")


def _check_gl017(name: str, audit: NumericsAudit) -> CheckResult:
    want = EXPECTED_UNGUARDED_EXP.get(name, 0)
    got = len(audit.exp_sites)
    ok = got == want
    return CheckResult(
        name, "GL017-exp-domain", ok,
        "" if ok else
        f"{got} unguarded exp site(s), {want} registered: "
        f"{'; '.join(audit.exp_sites[:4])} — subtract the max before "
        "exp, or register the audited count in EXPECTED_UNGUARDED_EXP")


def _check_gl018_census(name: str, audit: NumericsAudit) -> CheckResult:
    want = EXPECTED_DTYPE_CENSUS.get(name)
    if want is None:
        return CheckResult(name, "GL018-dtype-census", False,
                           f"entry unpinned — add EXPECTED_DTYPE_CENSUS"
                           f"[{name!r}] = {audit.census}")
    ok = audit.census == want
    if ok:
        return CheckResult(name, "GL018-dtype-census", True)
    diff = []
    for k in sorted(set(want) | set(audit.census)):
        if want.get(k) != audit.census.get(k):
            diff.append(f"{k}: pinned {want.get(k, 0)} B, traced "
                        f"{audit.census.get(k, 0)} B")
    return CheckResult(
        name, "GL018-dtype-census", False,
        "; ".join(diff) + " — precision placement moved; if intended, "
        "re-pin EXPECTED_DTYPE_CENSUS")


def _check_gl018_casts(name: str, audit: NumericsAudit) -> CheckResult:
    want = EXPECTED_CASTS.get(name)
    if want is None:
        return CheckResult(name, "GL018-cast-inventory", False,
                           f"entry unpinned — add EXPECTED_CASTS"
                           f"[{name!r}] = {audit.casts}")
    ok = audit.casts == want
    if ok:
        return CheckResult(name, "GL018-cast-inventory", True)
    diff = []
    for k in sorted(set(want) | set(audit.casts)):
        if want.get(k) != audit.casts.get(k):
            diff.append(f"`{k}`: pinned {want.get(k, 0)}, traced "
                        f"{audit.casts.get(k, 0)}")
    return CheckResult(
        name, "GL018-cast-inventory", False,
        "; ".join(diff[:6]) + " — a dtype boundary appeared or "
        "vanished; if intended, re-pin EXPECTED_CASTS")


def _check_residency(name: str, audit: NumericsAudit) -> CheckResult:
    bad = audit.residency_violations
    return CheckResult(
        name, "f32-residency", not bad,
        "" if not bad else "; ".join(bad[:4]))


def run_numerics_checks(entries=None, audits=None) -> list:
    """graftlint Pass 5: GL016 + GL017(jaxpr) + GL018 + the
    f32-residency audit over every registered entry.  Builder failures
    become failing results, like every other pass."""
    check_entry_names(entries)
    results: list = []
    if audits is None:
        audits = {}
    for name in entry_names():
        if entries is not None and name not in entries:
            continue
        try:
            if name not in audits:
                audits[name] = audit_entry(name)
            audit = audits[name]
            results.append(_check_gl016(name, audit))
            results.append(_check_gl017(name, audit))
            results.append(_check_gl018_census(name, audit))
            results.append(_check_gl018_casts(name, audit))
            results.append(_check_residency(name, audit))
        except Exception as exc:                     # pragma: no cover
            results.append(CheckResult(name, "numerics-build", False,
                                       f"{type(exc).__name__}: {exc}"))
    return results


# --------------------------------------------------------------------------
# what-if: the bf16 decision, statically
# --------------------------------------------------------------------------

def what_if_audit(**kw) -> NumericsAudit:
    """Audit the train step at a hypothetical operating point (sibling
    of memplan.what_if_step, same traced program): ``dtype='bfloat16'``
    answers "which reductions lose their f32 accumulator, which casts
    appear, does the loss chain stay f32" before anyone flips the model
    dtype on a chip."""
    from milnce_tpu.analysis.memplan import what_if_program

    closed, labels, _donated, entry, mesh = what_if_program(**kw)
    audit = audit_jaxpr(closed, labels=labels, entry=entry)
    audit.mesh = mesh
    return audit
