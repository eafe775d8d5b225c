"""graftlint Pass 4: static HBM planner — jaxpr live-range memory analysis.

The binding constraint of the original MIL-NCE run was fitting the
32-frame S3D step into TPU v3 HBM, and this repo's own PERF.md records a
>10% throughput cliff at batch 192 whose diagnosis cost a chip session.
This pass makes per-chip peak device bytes a STATIC property, checked on
the hermetic CPU mesh like every other trace invariant: every registered
entry's closed jaxpr is walked with buffer live-range analysis and the
result is pinned, so a memory regression (a rematerialized activation, a
donation that silently stopped taking effect, an optimizer state that
doubled) lands as a failing tier-1 check — not as an OOM three weeks
later at batch 192 on a v5e.

The model (known approximations are documented in ANALYSIS.md):

- **liveness**: a buffer is live from the equation that defines it to
  its last use; entry arguments live for the whole program unless
  donated (donated inputs free at their last use — XLA's buffer
  donation, modeled); outputs live to the end.
- **peak**: for each equation, bytes live while it executes = live set
  + the equation's own transient (outputs being materialized for plain
  primitives; the recursive peak of the body for scan/cond/while; the
  body peak minus the already-counted operands for jit / shard_map /
  custom_vjp nests, so a buffer crossing a nest boundary is counted
  once).
- **sharding-aware**: a value sharded over mesh axes contributes
  ``bytes / prod(axis sizes)`` per chip.  Inside ``shard_map`` bodies
  shapes are already per-shard; at the jit level the divisors are read
  off the shard_map equation's ``in_specs``/``out_specs`` — i.e. from
  the entry's committed PartitionSpecs, the same specs the sharding-map
  hash in bench records is built from.
- **donation-aware**: donated argument leaves free at last use, and a
  donated leaf with no same-shape/dtype output to alias (or one the
  program keeps live to the end) is a GL014 finding — donation that
  cannot take effect.

Three rules ride on the planner (rule catalogue: analysis/rules.py):

- **GL013 peak-budget-regression**: per-entry per-chip peak bytes are
  pinned in ``EXPECTED_PEAK_BYTES`` within ``PEAK_TOLERANCE``, exactly
  like pinned collective counts — a deliberate change re-pins the
  number in the same commit.
- **GL014 ineffective-or-missing-donation**: a large aliasable arg not
  donated on a grad-bearing entry, or a donated leaf whose buffer
  cannot be reused; findings name the buffer and its bytes.  The audit
  also verifies that each grad-bearing factory's production build
  (``donate=True``) really REQUESTS the donation from ``jax.jit``.
- **GL015 top-contributor-drift**: the top-3 peak contributors per
  entry are pinned BY NAME (``EXPECTED_TOP_CONTRIBUTORS``) so a
  silently rematerialized activation shows up as a named diff, not a
  mystery byte delta.

Everything runs under ``JAX_PLATFORMS=cpu`` on the same 8-virtual-device
mesh as Pass 2; jax imports live inside functions so astlint stays
importable without jax.  ``scripts/mem_plan.py`` is the CLI (MEMPLAN.md,
``--check``, ``--what-if`` operating-point prediction).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field

from milnce_tpu.analysis.trace_invariants import CheckResult

# Relative tolerance for the GL013 peak pin: wide enough to absorb
# jaxpr-level drift across jax point releases (a fused primitive more or
# less), far tighter than the >10% batch-cliff class it exists to catch.
PEAK_TOLERANCE = 0.10

# GL014 "large" floor: an aliasable-but-undonated arg smaller than this
# costs less than the finding costs attention.  64 KiB mirrors the FSDP
# threshold's reasoning (sharding_map.DEFAULT_FSDP_MIN_SIZE in elements).
GL014_MIN_BYTES = 64 * 1024


# --------------------------------------------------------------------------
# live-range analysis over a (possibly nested) jaxpr
# --------------------------------------------------------------------------

@dataclass
class MemPlan:
    """Per-entry result of the live-range walk (all byte counts are
    PER-CHIP: sharded values divided by their mesh-axis extents)."""
    entry: str
    peak_bytes: int
    arg_bytes: int                       # entry args resident per chip
    out_bytes: int                       # entry outputs per chip
    contributors: list = field(default_factory=list)  # [(label, bytes)] desc
    donated: tuple = ()                  # labels of donated arg leaves
    mesh: str = ""

    def top(self, k: int = 3) -> tuple:
        return tuple(label for label, _ in self.contributors[:k])


def aval_bytes(aval) -> int:
    """Device bytes of one (unsharded) abstract value."""
    import numpy as np

    size = getattr(aval, "size", None)
    dtype = getattr(aval, "dtype", None)
    if size is None or dtype is None:
        return 0
    return int(size) * int(np.dtype(dtype).itemsize)


def _is_literal(v) -> bool:
    from jax.extend.core import Literal

    return isinstance(v, Literal)


def _is_dropvar(v) -> bool:
    return type(v).__name__ == "DropVar"


def _spec_divisor(spec, axis_sizes: dict) -> int:
    """shard_map ``in_specs``/``out_specs`` entry (a PartitionSpec) ->
    the per-chip divisor prod(axis sizes).  Trailing-None-normalized
    specs (sharding_map._dim_spec) and un-normalized ones land on the
    same divisor here — only sharded dims name an axis."""
    d = 1
    for axes in spec:
        if axes is None:
            continue
        axes = axes if isinstance(axes, (tuple, list)) else (axes,)
        for a in axes:
            d *= int(axis_sizes.get(a, 1))
    return d


def _nested(eqn):
    """(kind, [sub-jaxprs]) for equations that carry a body.

    ``call`` bodies run once with the operands (jit / custom_vjp /
    remat): their peak overlaps the operands already live outside.
    ``loop`` bodies run repeatedly over fresh slices (scan / while);
    ``branch`` picks one of several (cond)."""
    p, prm = eqn.primitive.name, eqn.params
    if p == "jit":
        return "call", [prm["jaxpr"]]
    if p in ("closed_call", "core_call", "remat", "remat2", "checkpoint"):
        j = prm.get("jaxpr") or prm.get("call_jaxpr")
        return "call", [j] if j is not None else []
    if p in ("custom_vjp_call", "custom_jvp_call", "custom_vjp_call_jaxpr",
             "custom_lin"):
        j = prm.get("call_jaxpr") or prm.get("fun_jaxpr")
        return "call", [j] if j is not None else []
    if p == "shard_map":
        return "shard_map", [prm["jaxpr"]]
    if p == "scan":
        return "loop", [prm["jaxpr"]]
    if p == "while":
        return "loop", [prm["cond_jaxpr"], prm["body_jaxpr"]]
    if p == "cond":
        return "branch", list(prm["branches"])
    return "", []


def _open(j):
    return j.jaxpr if hasattr(j, "jaxpr") else j


def _div_prepass(jaxpr, invar_div):
    """Per-chip divisor map for one jaxpr level, BEFORE liveness runs —
    the initial live set (args + consts) must already be counted at
    per-chip size or an 8-way-sharded batch would inflate the entry
    peak 8x at step zero.  Divisors come from shard_map
    ``in_specs``/``out_specs`` (the committed specs) and propagate
    through ``call``-kind bodies in BOTH directions: a jit-level state
    arg that only a nested shard_map shards (jit(shard_map(step)) — the
    entry shape) still counts per-chip at the jit level.  Returns
    ``(div_map, outvar_divs, invar_divs)``."""
    jaxpr = _open(jaxpr)
    div: dict = {}
    for i, v in enumerate(jaxpr.invars):
        div[v] = invar_div[i] if invar_div else 1
    for eqn in jaxpr.eqns:
        kind, bodies = _nested(eqn)
        if kind == "shard_map":
            sizes = dict(getattr(eqn.params["mesh"], "shape", {}) or {})
            for v, spec in zip(eqn.invars, eqn.params["in_specs"]):
                if not _is_literal(v):
                    div[v] = max(div.get(v, 1), _spec_divisor(spec, sizes))
            for v, spec in zip(eqn.outvars, eqn.params["out_specs"]):
                div[v] = _spec_divisor(spec, sizes)
        elif kind == "call" and bodies:
            sub = [1 if _is_literal(v) else div.get(v, 1)
                   for v in eqn.invars]
            _, out_divs, in_divs = _div_prepass(bodies[0], sub)
            for v, d in zip(eqn.invars, in_divs):
                if not _is_literal(v):
                    div[v] = max(div.get(v, 1), d)
            for v, d in zip(eqn.outvars, out_divs):
                div[v] = d
    return (div, [div.get(v, 1) for v in jaxpr.outvars],
            [div.get(v, 1) for v in jaxpr.invars])


def analyze_jaxpr(closed_jaxpr, *, donated=None, labels=None) -> MemPlan:
    """Live-range walk of an entry's closed jaxpr -> :class:`MemPlan`.

    ``donated``: bool per flattened invar (True = freeable at last use);
    ``labels``: name per flattened invar (tree paths — the contributor
    attribution GL015 pins).  Intermediates are labeled
    ``"<primitive> <aval>"`` so a rematerialized activation is namable.
    """
    jaxpr = _open(closed_jaxpr)
    n = len(jaxpr.invars)
    donated = list(donated) if donated is not None else [False] * n
    labels = list(labels) if labels is not None else [f"arg{i}"
                                                     for i in range(n)]
    pinned = [not d for d in donated]
    peak, snap, arg_b, out_b = _walk(jaxpr, None, pinned, labels)
    agg: dict[str, int] = {}
    for label, nbytes in snap:
        agg[label] = agg.get(label, 0) + nbytes
    contributors = sorted(agg.items(), key=lambda kv: (-kv[1], kv[0]))
    return MemPlan(entry="", peak_bytes=peak, arg_bytes=arg_b,
                   out_bytes=out_b, contributors=contributors,
                   donated=tuple(l for l, d in zip(labels, donated) if d))


def _walk(jaxpr, invar_div, pinned, labels):
    """One level of the analysis.  Returns ``(peak, snapshot, arg_bytes,
    out_bytes)`` — snapshot is the flat [(label, bytes)] of everything
    live at the peak instant, across nest levels."""
    jaxpr = _open(jaxpr)
    div, out_divs, _in_divs = _div_prepass(jaxpr, invar_div)
    lab: dict = {}
    for v, name in zip(jaxpr.invars, labels or []):
        lab[v] = name
    for v in jaxpr.constvars:
        div.setdefault(v, 1)
        lab[v] = f"const {v.aval.str_short()}"

    def per_chip(v) -> int:
        return -(-aval_bytes(v.aval) // div.get(v, 1))

    last: dict = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if not _is_literal(v):
                last[v] = i
    outset = {v for v in jaxpr.outvars if not _is_literal(v)}
    for v in outset:
        last[v] = len(jaxpr.eqns)
    pinset = {v for v, p in zip(jaxpr.invars, pinned or []) if p}

    live: dict = {}
    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        live[v] = per_chip(v)
    arg_bytes = sum(live[v] for v in jaxpr.invars)
    peak = sum(live.values())
    snap = [(lab.get(v, "?"), b) for v, b in live.items()]

    for i, eqn in enumerate(jaxpr.eqns):
        kind, bodies = _nested(eqn)
        out_bytes_eqn = sum(per_chip(v) for v in eqn.outvars
                            if not _is_dropvar(v))
        if not bodies:
            # in-place reuse: an operand DYING at this equation whose
            # shape/dtype matches an output lends it its buffer — what
            # XLA's buffer assignment does for any dead intermediate,
            # and what donation extends to entry args (a donated state
            # updating in place is exactly this rule firing at the
            # optimizer's add)
            pool: dict = {}
            for v in {x for x in eqn.invars if not _is_literal(x)}:
                if last.get(v) == i and v not in pinset and v in live:
                    key = (tuple(v.aval.shape), str(v.aval.dtype))
                    pool[key] = pool.get(key, 0) + 1
            reuse = 0
            for v in eqn.outvars:
                if _is_dropvar(v):
                    continue
                key = (tuple(v.aval.shape), str(v.aval.dtype))
                if pool.get(key, 0) > 0:
                    pool[key] -= 1
                    reuse += per_chip(v)
            out_bytes_eqn = max(0, out_bytes_eqn - reuse)
        transient, inner_snap = out_bytes_eqn, []
        if bodies:
            sub_labels = [("lit" if _is_literal(v)
                           else lab.get(v, f"{eqn.primitive.name} operand"))
                          for v in eqn.invars]
            sub_pin = [(not _is_literal(v)) and v in pinset
                       for v in eqn.invars]
            if kind in ("call", "shard_map"):
                # body peak counts the operands again (they ARE the body
                # invars — same buffers); subtract the overlap so a
                # value crossing the nest boundary is counted once.  The
                # body's in-flight outputs stand in for the eqn outputs,
                # which only join the outer live set at completion.
                sub_div = None
                if kind == "call":
                    sub_div = [1 if _is_literal(v) else div.get(v, 1)
                               for v in eqn.invars]
                p2, s2, _a, _o = _walk(bodies[0], sub_div, sub_pin,
                                       sub_labels)
                overlap = sum(live.get(v, 0) for v in
                              {x for x in eqn.invars if not _is_literal(x)}
                              & set(live))
                transient, inner_snap = max(0, p2 - overlap), s2
            else:   # loop / branch: body runs over fresh slices; stacked
                    # eqn outputs fill DURING execution, so they stay in
                    # the transient alongside the body peak
                best, best_snap = 0, []
                for body in bodies:
                    binv = _open(body).invars
                    body_labels = [
                        f"{eqn.primitive.name} body {v.aval.str_short()}"
                        for v in binv]
                    p2, s2, _a, _o = _walk(body, None, [False] * len(binv),
                                           body_labels)
                    if p2 >= best:
                        best, best_snap = p2, s2
                # consts AND the carry overlap the body's view of them:
                # the carry is ONE buffer threaded through iterations
                # (scan reuses it in place), never a per-iteration copy
                n_over = int(eqn.params.get("num_consts", 0)) + int(
                    eqn.params.get("num_carry", 0))
                overlap = sum(live.get(v, 0)
                              for v in eqn.invars[:n_over]
                              if not _is_literal(v) and v in live)
                transient = out_bytes_eqn + max(0, best - overlap)
                inner_snap = best_snap

        cur = sum(live.values()) + transient
        if cur > peak:
            peak = cur
            snap = [(lab.get(v, "?"), b) for v, b in live.items()]
            if bodies:
                snap += inner_snap
            else:
                snap += [(f"{eqn.primitive.name} {v.aval.str_short()}",
                          per_chip(v)) for v in eqn.outvars
                         if not _is_dropvar(v)]

        # completion: outputs join the live set, dead operands free
        for v in eqn.outvars:
            if _is_dropvar(v):
                continue
            live[v] = per_chip(v)
            lab[v] = f"{eqn.primitive.name} {v.aval.str_short()}"
        for v in {x for x in eqn.invars if not _is_literal(x)}:
            if last.get(v) == i and v not in pinset and v in live:
                del live[v]
        for v in eqn.outvars:
            if (not _is_dropvar(v) and last.get(v, -1) <= i
                    and v not in outset and v in live):
                del live[v]          # dead output (DCE'd downstream)
        cur = sum(live.values())
        if cur > peak:
            peak = cur
            snap = [(lab.get(v, "?"), b) for v, b in live.items()]

    out_bytes = sum(-(-aval_bytes(v.aval) // d)
                    for v, d in zip(jaxpr.outvars, out_divs)
                    if not _is_literal(v))
    return peak, snap, arg_bytes, out_bytes


# --------------------------------------------------------------------------
# entry planning
# --------------------------------------------------------------------------

def arg_leaf_labels(args, argnames) -> list:
    """Flattened-leaf labels for an entry's positional args — the tree
    paths GL015 pins (``state/params/conv1/kernel``, ``video``, ...)."""
    import jax

    from milnce_tpu.parallel.sharding_map import _path_str

    flat, _ = jax.tree_util.tree_flatten_with_path(tuple(args))
    out = []
    for path, _leaf in flat:
        idx = getattr(path[0], "idx", 0)
        rest = _path_str(path[1:])
        out.append(argnames[idx] + ("/" + rest if rest else ""))
    return out


def donated_leaf_flags(args, donate_argnums) -> list:
    """bool per flattened leaf: does its top-level positional arg sit in
    ``donate_argnums`` (the entry's TPU donation intent)?"""
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tuple(args))
    want = set(donate_argnums or ())
    return [getattr(path[0], "idx", 0) in want for path, _leaf in flat]


def plan_fn(fn, args, *, argnames, donate_argnums=(), entry="",
            mesh="") -> "MemPlan":
    """Trace ``fn(*args)`` and run the live-range walk with the entry's
    donation intent applied (the production build's donation, even
    though the entry itself is built donate=False so the audit can
    re-trace it)."""
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    plan = analyze_jaxpr(
        closed,
        donated=donated_leaf_flags(args, donate_argnums),
        labels=arg_leaf_labels(args, argnames))
    plan.entry = entry
    plan.mesh = mesh
    return plan


def donation_findings(fn, args, *, argnames, donate_argnums, grad_bearing,
                      min_bytes: int = GL014_MIN_BYTES) -> list:
    """GL014: (a) donated leaves that cannot alias any output
    (no same-shape/dtype output left to claim, or the input is itself
    kept live to the end), (b) large aliasable args NOT donated on a
    grad-bearing entry.  Each finding names the buffer and its bytes."""
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    return _donation_findings_jaxpr(
        closed, arg_leaf_labels(args, argnames),
        donated_leaf_flags(args, donate_argnums), grad_bearing,
        min_bytes=min_bytes)


def _donation_findings_jaxpr(closed, labels, donated, grad_bearing,
                             min_bytes: int = GL014_MIN_BYTES) -> list:
    jaxpr = _open(closed)
    # multiset of output (shape, dtype) available for aliasing
    pool: dict = {}
    for v in jaxpr.outvars:
        if _is_literal(v):
            continue
        key = (tuple(v.aval.shape), str(v.aval.dtype))
        pool[key] = pool.get(key, 0) + 1
    passthrough = {v for v in jaxpr.outvars if not _is_literal(v)}
    findings = []
    for v, label, don in zip(jaxpr.invars, labels, donated):
        key = (tuple(v.aval.shape), str(v.aval.dtype))
        nbytes = aval_bytes(v.aval)
        if don:
            if v in passthrough:
                findings.append(
                    f"donated `{label}` ({nbytes} B) is returned "
                    "unchanged — its buffer stays live to the end, the "
                    "donation cannot take effect")
            elif pool.get(key, 0) > 0:
                pool[key] -= 1
            else:
                findings.append(
                    f"donated `{label}` ({nbytes} B, {key[1]}"
                    f"{list(key[0])}) matches no program output — XLA "
                    "cannot reuse the buffer, the donation is dead "
                    "weight")
        elif (grad_bearing and nbytes >= min_bytes
                and v not in passthrough      # returned unchanged: donating
                and pool.get(key, 0) > 0):    # it could never take effect
            findings.append(
                f"`{label}` ({nbytes} B) aliases an output "
                f"shape/dtype but is not donated — at scale that is "
                "two copies of the buffer across the update")
    return findings


# --------------------------------------------------------------------------
# registered entries + pins (the Pass 4 gate)
# --------------------------------------------------------------------------

_STEP_ARGNAMES = ("state", "video", "text", "start")


@dataclass(frozen=True)
class MemEntry:
    name: str
    build: object                      # () -> (fn, args)
    argnames: tuple = _STEP_ARGNAMES
    donate_argnums: tuple = ()         # the TPU path's donation intent
    grad_bearing: bool = False
    mesh: str = "8x1 (data)"


def _e_train(loss: str = "milnce", guard: bool = False):
    def build(donate: bool = False):
        from milnce_tpu.analysis.trace_invariants import _setup
        from milnce_tpu.config import LossConfig
        from milnce_tpu.train.step import make_train_step

        model, opt, mesh, state, batch = _setup()
        loss_cfg = (None if loss == "milnce"
                    else LossConfig(name=loss, sdtw_backend="scan"))
        step = make_train_step(model, opt, mesh, donate=donate,
                               loss_cfg=loss_cfg, finite_guard=guard)
        return step, (state,) + batch()
    return build


def _e_grad_cache():
    def build(donate: bool = False):
        from milnce_tpu.analysis.trace_invariants import _setup
        from milnce_tpu.config import LossConfig
        from milnce_tpu.train.step import make_grad_cache_step

        model, opt, mesh, state, batch = _setup()
        step = make_grad_cache_step(model, opt, mesh, 2, donate=donate,
                                    loss_cfg=LossConfig(name="milnce"))
        return step, (state,) + batch()
    return build


def _e_train_2d(grad_cache: bool = False):
    def build(donate: bool = False):
        from milnce_tpu.analysis.trace_invariants import _setup_2d
        from milnce_tpu.config import LossConfig
        from milnce_tpu.train.step import (make_grad_cache_step,
                                           make_train_step)

        model, opt, mesh, specs, state, batch = _setup_2d()
        if grad_cache:
            step = make_grad_cache_step(model, opt, mesh, 2, donate=donate,
                                        loss_cfg=LossConfig(name="milnce"),
                                        state_specs=specs,
                                        model_axis="model")
        else:
            step = make_train_step(model, opt, mesh, donate=donate,
                                   state_specs=specs, model_axis="model")
        return step, (state,) + batch()
    return build


def _e_train_4way():
    def build(donate: bool = False):
        from milnce_tpu.analysis.trace_invariants import _setup_4way
        from milnce_tpu.train.step import make_train_step

        model, opt, mesh, state, batch = _setup_4way()
        step = make_train_step(model, opt, mesh, donate=donate)
        return step, (state,) + batch()
    return build


def _e_train_chunked():
    def build(donate: bool = False):
        from milnce_tpu.analysis.trace_invariants import (_chunked_loss_cfg,
                                                          _setup)
        from milnce_tpu.train.step import make_train_step

        model, opt, mesh, state, batch = _setup()
        step = make_train_step(model, opt, mesh, donate=donate,
                               loss_cfg=_chunked_loss_cfg())
        return step, (state,) + batch()
    return build


# Loss-only entries (ISSUE 12): the dense cube vs the chunked stream at
# a shape where the LOSS side dominates the plan — b_local=64, Bg=512,
# K=5, D=16 on the 8-way mesh, so one (B_local, Bg, K) f32 cube is
# 640 KiB/chip against ~200 KiB of gathered embeddings.  The pins prove
# the tentpole's scaling claim structurally: dense peaks at the cubes +
# their AD twins (O(B_local * Bg * K)); chunked peaks at one streamed
# block (O(B_local * chunk)) — GL013 numbers + the GL015 contributor
# names say which buffers those are.
_MILNCE_LOSS_SHAPE = dict(b_global=512, k=5, d=16, chunk=64)


def milnce_loss_plan_program(impl: str, b_global: int, k: int, d: int,
                             chunk: int, backend: str = "scan"):
    """The ONE sharded value-and-grad loss program both the GL013
    entries and scripts/milnce_loss_bench.py's memory column plan —
    shared so the committed BENCH_MILNCE_LOSS.md peaks can never drift
    from the pinned entries' program.  Returns ``(fn, args)`` for
    :func:`plan_fn` (args are abstract — nothing allocates)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from milnce_tpu.analysis.trace_invariants import _setup
    from milnce_tpu.losses.milnce import milnce_loss
    from milnce_tpu.losses.milnce_chunked import milnce_loss_chunked

    _model, _opt, mesh, _state, _batch = _setup()

    def local(v, t):
        if impl == "chunked":
            return milnce_loss_chunked(v, t, axis_name="data",
                                       chunk=chunk, backend=backend)
        return milnce_loss(v, t, axis_name="data")

    def value_and_grads(v, t):
        return jax.value_and_grad(local, argnums=(0, 1))(v, t)

    fn = jax.jit(jax.shard_map(
        value_and_grads, mesh=mesh,
        in_specs=(P("data"), P("data")),
        out_specs=(P(), (P("data"), P("data"))), check_vma=False))
    args = (jax.ShapeDtypeStruct((b_global, d), jnp.float32),
            jax.ShapeDtypeStruct((b_global * k, d), jnp.float32))
    return fn, args


def _e_milnce_loss(impl: str):
    def build():
        return milnce_loss_plan_program(impl, **_MILNCE_LOSS_SHAPE)
    return build


@functools.lru_cache(maxsize=1)
def _serve_engine():
    """Cold engine (precompile=False — planning only needs the traced
    programs, not warmed executables) over the shared tiny setup."""
    import jax

    from milnce_tpu.analysis.trace_invariants import (_FRAMES, _SIZE,
                                                      _WORDS, _setup)
    from milnce_tpu.serving.engine import InferenceEngine

    model, _opt, mesh, state, _batch = _setup()
    varz = {"params": state.params, "batch_stats": state.batch_stats}
    ndev = len(jax.devices())
    engine = InferenceEngine(model, varz, mesh, text_words=_WORDS,
                             video_shape=(_FRAMES, _SIZE, _SIZE, 3),
                             max_batch=2 * ndev, precompile=False)
    return engine, varz


def _e_serve(entry: str, bucket_idx: int):
    def build():
        import numpy as np

        from milnce_tpu.analysis.trace_invariants import _FRAMES, _SIZE, _WORDS

        engine, varz = _serve_engine()
        fn = engine.jit_entries()[entry]
        b = engine.buckets[bucket_idx]
        x = (np.zeros((b, _WORDS), np.int32) if entry == "text"
             else np.zeros((b, _FRAMES, _SIZE, _SIZE, 3), np.uint8))
        return fn, (varz, x)
    return build


@functools.lru_cache(maxsize=1)
def _serve_pool_engine():
    """Cold SINGLE-DEVICE replica engine — the pool's CPU test shape
    (serving/pool.py: one replica per device group, single-device groups
    on the CPU backend).  The per-chip plan of a replica entry must
    charge exactly ONE replica's footprint: params are per-replica
    copies but each lives on its own device group, so N replicas never
    stack bytes on a chip (a divisor-of-1 shard_map on the replica's own
    mesh, NOT the full test mesh's 8-way division)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from milnce_tpu.analysis.lockrt import make_lock
    from milnce_tpu.analysis.trace_invariants import (_FRAMES, _SIZE,
                                                      _WORDS, _setup)
    from milnce_tpu.serving.engine import InferenceEngine

    model, _opt, _mesh, state, _batch = _setup()
    varz = {"params": state.params, "batch_stats": state.batch_stats}
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    engine = InferenceEngine(
        model, varz, mesh, text_words=_WORDS,
        video_shape=(_FRAMES, _SIZE, _SIZE, 3), max_batch=4, min_bucket=2,
        precompile=False,
        dispatch_lock=make_lock("serving.replica0.dispatch"))
    return engine, varz


def _e_pool_serve(entry: str, bucket_idx: int):
    def build():
        import numpy as np

        from milnce_tpu.analysis.trace_invariants import _FRAMES, _SIZE, _WORDS

        engine, varz = _serve_pool_engine()
        fn = engine.jit_entries()[entry]
        b = engine.buckets[bucket_idx]
        x = (np.zeros((b, _WORDS), np.int32) if entry == "text"
             else np.zeros((b, _FRAMES, _SIZE, _SIZE, 3), np.uint8))
        return fn, (varz, x)
    return build


@functools.lru_cache(maxsize=1)
def _serve_quant_engine():
    """Cold QUANTIZED engine (ISSUE 19): the tiny model's weights int8
    per the readiness rule, behind the same ladder.  The plan prices
    what the edge tier buys — int8 param residency (4x smaller leaves)
    against the in-jit dequantize's transient f32 copies; the pins keep
    that trade visible, so a dequant that started materializing the
    whole f32 tree at once shows up as GL013/GL015 drift."""
    import jax

    from milnce_tpu.analysis.trace_invariants import (_FRAMES, _SIZE,
                                                      _WORDS, _setup)
    from milnce_tpu.quant.quantize import (QuantizedModel,
                                           quantize_variables)
    from milnce_tpu.serving.engine import InferenceEngine

    model, _opt, mesh, state, _batch = _setup()
    varz = {"params": state.params, "batch_stats": state.batch_stats}
    qvarz = quantize_variables(varz)
    ndev = len(jax.devices())
    engine = InferenceEngine(QuantizedModel(model), qvarz, mesh,
                             text_words=_WORDS,
                             video_shape=(_FRAMES, _SIZE, _SIZE, 3),
                             max_batch=2 * ndev, precompile=False)
    return engine, qvarz


def _e_quant_serve(entry: str, bucket_idx: int):
    def build():
        import numpy as np

        from milnce_tpu.analysis.trace_invariants import _FRAMES, _SIZE, _WORDS

        engine, qvarz = _serve_quant_engine()
        fn = engine.jit_entries()[entry]
        b = engine.buckets[bucket_idx]
        x = (np.zeros((b, _WORDS), np.int32) if entry == "text"
             else np.zeros((b, _FRAMES, _SIZE, _SIZE, 3), np.uint8))
        return fn, (qvarz, x)
    return build


def _e_index_topk():
    def build():
        import jax
        import numpy as np

        from milnce_tpu.analysis.trace_invariants import _TINY, _setup
        from milnce_tpu.serving.index import DeviceRetrievalIndex

        _model, _opt, mesh, _state, _batch = _setup()
        ndev = len(jax.devices())
        rng = np.random.default_rng(0)
        corpus = rng.standard_normal(
            (3 * ndev - 2, _TINY["embedding_dim"])).astype(np.float32)
        index = DeviceRetrievalIndex(mesh, corpus, k=3,
                                     query_buckets=(ndev,))
        q = rng.standard_normal((ndev, index.dim)).astype(np.float32)
        fn, operands = index.topk_program()
        return fn, operands + (q,)
    return build


def _e_live_index_topk():
    def build():
        import jax
        import numpy as np

        from milnce_tpu.analysis.trace_invariants import _TINY, _setup
        from milnce_tpu.serving.live_index import LiveRetrievalIndex

        _model, _opt, mesh, _state, _batch = _setup()
        ndev = len(jax.devices())
        rng = np.random.default_rng(0)
        corpus = rng.standard_normal(
            (3 * ndev - 2, _TINY["embedding_dim"])).astype(np.float32)
        # same boot corpus as serve_index_topk, but the LIVE index pads
        # every shard to its capacity RUNG (power of two >= k) — the
        # footprint the planner prices is the rung's, i.e. what a
        # generation costs for the whole life of that rung
        index = LiveRetrievalIndex(mesh, corpus, k=3, query_buckets=(ndev,),
                                   precompile=False)
        try:
            q = rng.standard_normal((ndev, index.dim)).astype(np.float32)
            fn, operands = index.topk_program()
            return fn, operands + (q,)
        finally:
            index.close()
    return build


def _entries() -> dict:
    from milnce_tpu.train.step import STATE_DONATION_ARGNUMS as DON

    return {e.name: e for e in (
        MemEntry("train_step_milnce", _e_train(), donate_argnums=DON,
                 grad_bearing=True),
        MemEntry("train_step_milnce_guarded", _e_train(guard=True),
                 donate_argnums=DON, grad_bearing=True),
        MemEntry("train_step_sdtw3", _e_train(loss="sdtw_3"),
                 donate_argnums=DON, grad_bearing=True),
        MemEntry("grad_cache_step_milnce", _e_grad_cache(),
                 donate_argnums=DON, grad_bearing=True),
        MemEntry("train_step_milnce_chunked", _e_train_chunked(),
                 donate_argnums=DON, grad_bearing=True),
        MemEntry("milnce_loss_dense", _e_milnce_loss("dense"),
                 argnames=("video", "text")),
        MemEntry("milnce_loss_chunked", _e_milnce_loss("chunked"),
                 argnames=("video", "text")),
        MemEntry("train_step_milnce@4way", _e_train_4way(),
                 donate_argnums=DON, grad_bearing=True,
                 mesh="4x1 (data)"),
        MemEntry("train_step_milnce_2d", _e_train_2d(),
                 donate_argnums=DON, grad_bearing=True,
                 mesh="4x2 (data,model)"),
        MemEntry("grad_cache_2d", _e_train_2d(grad_cache=True),
                 donate_argnums=DON, grad_bearing=True,
                 mesh="4x2 (data,model)"),
        MemEntry("serve_text_embed@b0", _e_serve("text", 0),
                 argnames=("variables", "tokens")),
        MemEntry("serve_text_embed@b1", _e_serve("text", 1),
                 argnames=("variables", "tokens")),
        MemEntry("serve_video_embed@b0", _e_serve("video", 0),
                 argnames=("variables", "video")),
        MemEntry("serve_video_embed@b1", _e_serve("video", 1),
                 argnames=("variables", "video")),
        MemEntry("serve_index_topk", _e_index_topk(),
                 argnames=("corpus", "valid", "queries")),
        MemEntry("serve_index_topk@gen", _e_live_index_topk(),
                 argnames=("corpus", "valid", "queries")),
        MemEntry("serve_pool_text_embed@b0", _e_pool_serve("text", 0),
                 argnames=("variables", "tokens"), mesh="1x1 replica"),
        MemEntry("serve_pool_video_embed@b1", _e_pool_serve("video", 1),
                 argnames=("variables", "video"), mesh="1x1 replica"),
        MemEntry("serve_quant_text_embed@b1", _e_quant_serve("text", 1),
                 argnames=("variables", "tokens")),
        MemEntry("serve_quant_video_embed@b1", _e_quant_serve("video", 1),
                 argnames=("variables", "video")),
    )}


# Pinned per-chip peak bytes (GL013) for the tiny entry configs on the
# hermetic CPU meshes.  Like EXPECTED_COLLECTIVES: the invariant is that
# they never change SILENTLY — a deliberate model/step/layout change
# re-pins the number in the same commit.  Derived by
# ``python scripts/mem_plan.py`` (which prints the re-pin dict on drift).
EXPECTED_PEAK_BYTES = {
    "train_step_milnce": 10612424,
    "train_step_milnce_guarded": 16917340,
    "train_step_sdtw3": 10612424,
    "grad_cache_step_milnce": 12448688,
    # chunked MIL-NCE (ISSUE 12): the full chunked step pins IDENTICAL
    # to train_step_milnce — at the tiny entry scale the optimizer
    # moments dominate both, which is itself the no-regression pin (the
    # stream must never ADD memory).  The loss-only pair below isolates
    # the loss side at a shape where the cube dominates: dense peaks at
    # the (B_local, Bg, K) cubes + AD twins (the GL015 names are the
    # [64, 2560] = (B_local, Bg*K) cube ops), chunked at one
    # (B_local, chunk*K) streamed block — O(B_local*Bg*K) ->
    # O(B_local*chunk), 4.1x less per chip at this shape, and the gap
    # widens linearly in Bg/chunk (tests/test_memplan.py pins the
    # strict inequality; PERF.md "Memory-efficient loss" has the
    # Bg=8192 what-if numbers).
    "train_step_milnce_chunked": 10612424,
    "milnce_loss_dense": 2374784,
    "milnce_loss_chunked": 703276,
    # elastic 4-way layout (ISSUE 20): pinned IDENTICAL to the 8-way
    # step — per-chip peak is a function of clips PER CHIP (2 at both
    # layouts: b = 2*ndev shards evenly), so downsizing the mesh halves
    # the global batch, never the per-chip footprint.  That equality is
    # the elastic memory contract: a resume onto fewer chips fits
    # wherever the full mesh fit.
    "train_step_milnce@4way": 10612424,
    "train_step_milnce_2d": 8652104,
    "grad_cache_2d": 11399984,
    "serve_text_embed@b0": 2119092,
    "serve_text_embed@b1": 2119592,
    "serve_video_embed@b0": 2311104,
    "serve_video_embed@b1": 2503616,
    "serve_index_topk": 2436,
    # live index (ISSUE 14): same program, shard rows padded to the
    # capacity RUNG (pow2 >= k: 3 rows/shard -> 4) — the 64-byte delta
    # vs the frozen entry is the rung headroom, i.e. what pre-provisioned
    # growth costs per chip at the tiny scale
    "serve_index_topk@gen": 2500,
    # replica-pool entries (ISSUE 10): per-chip bytes on a replica's OWN
    # single-device mesh.  The pin is the no-double-count property: a
    # pool puts ONE replica per device (group), so a replica's per-chip
    # footprint equals the single-engine entry at the same rows-per-chip
    # (text@b0 here is 2 rows on 1 chip == serve_text_embed@b1's 16 rows
    # over 8 chips — byte-identical), never N-replicas-times-anything
    "serve_pool_text_embed@b0": 2119592,
    "serve_pool_video_embed@b1": 2888640,
    # quantized edge engine (ISSUE 19): int8 residency vs dequant
    # transients, both legible in the numbers.  The text entry drops to
    # ~0.5x the f32 engine's peak (params live as int8; only the text
    # tower's few kernels dequantize, transiently).  The video entry
    # pays ~1.2x: the conv kernels' f32 dequant copies (the GL015 `mul`
    # names) overlap the activation peak — the expected trade (the edge
    # class buys HBM residency and PCIe bytes, not peak-transient)
    "serve_quant_text_embed@b1": 986108,
    "serve_quant_video_embed@b1": 3026132,
}

# Pinned top-3 peak contributors per entry (GL015), by aggregated label:
# args by tree path, intermediates by "primitive aval".  A silently
# rematerialized activation / doubled optimizer moment shows up HERE as
# a named diff even when the byte delta hides inside the GL013
# tolerance.  Re-pin consciously, same commit, like the counts above.
EXPECTED_TOP_CONTRIBUTORS = {
    "train_step_milnce": (
        "state/opt_state/inner_state/inner_state/0/mu/conv_2c/conv_spatial/kernel",
        "state/opt_state/inner_state/inner_state/0/mu/conv_2c/conv_temporal/kernel",
        "state/opt_state/inner_state/inner_state/0/mu/mixed_3b/conv_b1_b/conv_spatial/kernel"),
    "train_step_milnce_guarded": (
        "state/opt_state/inner_state/inner_state/0/mu/conv_2c/conv_spatial/kernel",
        "state/opt_state/inner_state/inner_state/0/mu/conv_2c/conv_temporal/kernel",
        "state/opt_state/inner_state/inner_state/0/mu/mixed_3b/conv_b1_b/conv_spatial/kernel"),
    # 4-way elastic-resume layout: per-chip hot set identical to the
    # 8-way entry — replicated optimizer moments dominate at both
    "train_step_milnce@4way": (
        "state/opt_state/inner_state/inner_state/0/mu/conv_2c/conv_spatial/kernel",
        "state/opt_state/inner_state/inner_state/0/mu/conv_2c/conv_temporal/kernel",
        "state/opt_state/inner_state/inner_state/0/mu/mixed_3b/conv_b1_b/conv_spatial/kernel"),
    "train_step_sdtw3": (
        "state/opt_state/inner_state/inner_state/0/mu/conv_2c/conv_spatial/kernel",
        "state/opt_state/inner_state/inner_state/0/mu/conv_2c/conv_temporal/kernel",
        "state/opt_state/inner_state/inner_state/0/mu/mixed_3b/conv_b1_b/conv_spatial/kernel"),
    "grad_cache_step_milnce": (
        "state/opt_state/inner_state/inner_state/0/mu/conv_2c/conv_spatial/kernel",
        "state/opt_state/inner_state/inner_state/0/mu/conv_2c/conv_temporal/kernel",
        "state/opt_state/inner_state/inner_state/0/mu/mixed_3b/conv_b1_b/conv_spatial/kernel"),
    "train_step_milnce_chunked": (
        "state/opt_state/inner_state/inner_state/0/mu/conv_2c/conv_spatial/kernel",
        "state/opt_state/inner_state/inner_state/0/mu/conv_2c/conv_temporal/kernel",
        "state/opt_state/inner_state/inner_state/0/mu/mixed_3b/conv_b1_b/conv_spatial/kernel"),
    # the loss-only pair: dense's top contributors ARE the similarity
    # cube ([64, 2560] = (B_local, Bg*K) softmax intermediates + the
    # lse-transpose scatter over the (B_local, Bg, K) cube); chunked's
    # are one (B_local, chunk*K) = [64, 320] streamed block — the
    # tentpole's scaling claim, pinned by name
    "milnce_loss_dense": (
        "convert_element_type float32[64,2560]",
        "exp float32[64,2560]",
        "reshape float32[64,2560]"),
    "milnce_loss_chunked": (
        "exp float32[64,320]",
        "reshape float32[8,320,16]",
        "broadcast_in_dim float32[64,320]"),
    "train_step_milnce_2d": (
        "state/opt_state/inner_state/inner_state/0/mu/conv_2c/conv_spatial/kernel",
        "state/opt_state/inner_state/inner_state/0/mu/conv_2c/conv_temporal/kernel",
        "state/opt_state/inner_state/inner_state/0/mu/mixed_3b/conv_b1_b/conv_spatial/kernel"),
    "grad_cache_2d": (
        "scan body float32[1,3,3,64,192]",
        "scan body float32[1,3,3,96,128]",
        "scan body float32[3,1,1,192,192]"),
    "serve_text_embed@b0": (
        "variables/params/conv_2c/conv_spatial/kernel",
        "variables/params/conv_2c/conv_temporal/kernel",
        "variables/params/mixed_3b/conv_b1_b/conv_spatial/kernel"),
    "serve_text_embed@b1": (
        "variables/params/conv_2c/conv_spatial/kernel",
        "variables/params/conv_2c/conv_temporal/kernel",
        "variables/params/mixed_3b/conv_b1_b/conv_spatial/kernel"),
    "serve_video_embed@b0": (
        "variables/params/conv_2c/conv_spatial/kernel",
        "variables/params/conv_2c/conv_temporal/kernel",
        "variables/params/mixed_3b/conv_b1_b/conv_spatial/kernel"),
    "serve_video_embed@b1": (
        "variables/params/conv_2c/conv_spatial/kernel",
        "variables/params/conv_2c/conv_temporal/kernel",
        "variables/params/mixed_3b/conv_b1_b/conv_spatial/kernel"),
    "serve_index_topk": (
        "queries",
        "all_gather float32[8,24]",
        "all_gather int32[8,24]"),
    "serve_index_topk@gen": (
        "queries",
        "all_gather float32[8,24]",
        "all_gather int32[8,24]"),
    "serve_pool_text_embed@b0": (
        "variables/params/conv_2c/conv_spatial/kernel",
        "variables/params/conv_2c/conv_temporal/kernel",
        "variables/params/mixed_3b/conv_b1_b/conv_spatial/kernel"),
    "serve_pool_video_embed@b1": (
        "variables/params/conv_2c/conv_spatial/kernel",
        "variables/params/conv_2c/conv_temporal/kernel",
        "variables/params/mixed_3b/conv_b1_b/conv_spatial/kernel"),
    # quant entries: the top contributors ARE the dequant story — the
    # text peak sits at one kernel's i8->f32 convert beside the int8
    # residents; the video peak at the three largest kernels' scale
    # `mul` outputs (the f32 copies that feed the convs)
    "serve_quant_text_embed@b1": (
        "convert_element_type float32[1,3,3,64,192]",
        "variables/params/conv_2c/conv_spatial/kernel",
        "variables/params/conv_2c/conv_temporal/kernel"),
    "serve_quant_video_embed@b1": (
        "mul float32[1,3,3,64,192]",
        "mul float32[1,3,3,96,128]",
        "mul float32[3,1,1,192,192]"),
}


@functools.lru_cache(maxsize=None)
def _traced_entry(name: str):
    """(closed_jaxpr, labels, donated_flags) for one registered entry —
    cached per process: tracing the step is the expensive half of
    Pass 4, and the GL013/GL015 plan and the GL014 audit walk the SAME
    program."""
    import jax

    spec = _entries()[name]
    fn, args = spec.build()
    return (jax.make_jaxpr(fn)(*args),
            arg_leaf_labels(args, spec.argnames),
            donated_leaf_flags(args, spec.donate_argnums))


def _plan_entry(name: str) -> MemPlan:
    spec = _entries()[name]
    closed, labels, donated = _traced_entry(name)
    plan = analyze_jaxpr(closed, donated=donated, labels=labels)
    plan.entry = name
    plan.mesh = spec.mesh
    return plan


def check_entry_names(entries) -> None:
    """A typo'd entry filter must fail loudly, not plan zero entries
    and pass the gate vacuously (the stage_probe --stages /
    lint-scope discipline)."""
    if entries is None:
        return
    unknown = set(entries) - set(_entries())
    if unknown:
        raise ValueError(
            f"unknown memplan entries: {sorted(unknown)} (registered: "
            f"{', '.join(_entries())})")


def plan_all(entries=None) -> dict:
    """name -> MemPlan for the registered entries (or a subset)."""
    check_entry_names(entries)
    plans: dict = {}
    for name in _entries():
        if entries is not None and name not in entries:
            continue
        plans[name] = _plan_entry(name)
    return plans


def _check_gl013(name: str, plan: MemPlan) -> CheckResult:
    want = EXPECTED_PEAK_BYTES.get(name)
    if want is None:
        return CheckResult(name, "GL013-peak-budget", False,
                           f"entry unpinned — add EXPECTED_PEAK_BYTES"
                           f"[{name!r}] = {plan.peak_bytes}")
    drift = (plan.peak_bytes - want) / want
    ok = abs(drift) <= PEAK_TOLERANCE
    return CheckResult(
        name, "GL013-peak-budget", ok,
        "" if ok else
        f"per-chip peak {plan.peak_bytes} B vs pinned {want} B "
        f"({drift:+.1%}, tolerance ±{PEAK_TOLERANCE:.0%}) — memory "
        "structure changed; if intended, re-pin EXPECTED_PEAK_BYTES")


def _check_gl015(name: str, plan: MemPlan) -> CheckResult:
    want = EXPECTED_TOP_CONTRIBUTORS.get(name)
    if want is None:
        return CheckResult(name, "GL015-top-contributors", False,
                           f"entry unpinned — add EXPECTED_TOP_CONTRIBUTORS"
                           f"[{name!r}] = {plan.top()}")
    got = plan.top(len(want))
    ok = got == tuple(want)
    return CheckResult(
        name, "GL015-top-contributors", ok,
        "" if ok else
        f"top contributors drifted: expected {tuple(want)}, planned "
        f"{got} — a renamed entry here is a re-materialized or "
        "re-shaped peak buffer; if intended, re-pin "
        "EXPECTED_TOP_CONTRIBUTORS")


def traced_donated_invar_count(fn, args) -> int:
    """Flattened invars the traced program actually marks donated —
    read off the top-level jit equation's ``donated_invars``, i.e.
    what the factory REALLY passed to ``jax.jit``, not what a registry
    claims it passes."""
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    total = 0
    for eqn in _open(closed).eqns:
        if eqn.primitive.name == "jit":
            total += sum(bool(d) for d in
                         eqn.params.get("donated_invars", ()))
    return total


@functools.lru_cache(maxsize=None)
def _donation_wired(name: str):
    """(donated_invars_traced, donated_leaves_expected) for a
    grad-bearing entry's PRODUCTION build (donate=True).

    This is the half of GL014 the registry cannot vouch for: the
    entry's factory must actually WIRE the donation intent into
    ``jax.jit``.  We build with ``donate=True`` and count
    ``donated_invars`` in the traced jit — a factory that dropped its
    ``donate_argnums=`` plumbing traces zero donated invars here and
    fails the check, while the plain registry round-trip would have
    stayed green."""
    spec = _entries()[name]
    fn, args = spec.build(donate=True)
    traced = traced_donated_invar_count(fn, args)
    expected = sum(donated_leaf_flags(args, spec.donate_argnums))
    return traced, expected


def _check_gl014(name: str, spec: MemEntry) -> list:
    """The donation audit: jaxpr-level effectiveness findings plus the
    wiring half — every grad-bearing entry's factory must pass the
    request to ``jax.jit`` (verified against the TRACED program, not
    the registry's claim)."""
    out = []
    closed, labels, donated = _traced_entry(name)
    found = _donation_findings_jaxpr(closed, labels, donated,
                                     spec.grad_bearing)
    out.append(CheckResult(
        name, "GL014-donation", not found,
        "; ".join(found[:4]) if found else ""))
    if spec.grad_bearing:
        traced, expected = _donation_wired(name)
        ok = expected > 0 and traced == expected
        out.append(CheckResult(
            name, "GL014-donation-requested", bool(ok),
            "" if ok else
            f"production build (donate=True) traces {traced} donated "
            f"invars, expected {expected} (the {spec.donate_argnums} "
            "state tree) — the factory dropped its donate_argnums "
            "plumbing"))
    return out


def run_memplan_checks(entries=None, plans=None) -> list:
    """graftlint Pass 4: GL013 + GL014 + GL015 over every registered
    entry, plus the instrumented-step identity (the obs span wrapper
    must not change the memory plan any more than it may change the
    collectives).  Builder failures become failing results."""
    check_entry_names(entries)
    results: list = []
    specs = _entries()
    if plans is None:
        plans = {}
    for name, spec in specs.items():
        if entries is not None and name not in entries:
            continue
        try:
            if name not in plans:
                plans[name] = _plan_entry(name)
            plan = plans[name]
            results.append(_check_gl013(name, plan))
            results.append(_check_gl015(name, plan))
            results.extend(_check_gl014(name, spec))
        except Exception as exc:                     # pragma: no cover
            results.append(CheckResult(name, "memplan-build", False,
                                       f"{type(exc).__name__}: {exc}"))
    if (entries is None and "train_step_milnce" in plans):
        # the instrumented step is the SAME program behind a host-side
        # span — its plan must be byte-identical to the plain step's
        try:
            from milnce_tpu.analysis.trace_invariants import _setup
            from milnce_tpu.obs import spans as obs_spans
            from milnce_tpu.train.step import make_train_step

            model, opt, mesh, state, batch = _setup()
            step = make_train_step(model, opt, mesh, donate=False)
            rec = obs_spans.SpanRecorder()

            def instrumented(s, video, text, start):
                with rec.span("step"):
                    return step(s, video, text, start)

            from milnce_tpu.train.step import STATE_DONATION_ARGNUMS
            iplan = plan_fn(instrumented, (state,) + batch(),
                            argnames=_STEP_ARGNAMES,
                            donate_argnums=STATE_DONATION_ARGNUMS,
                            entry="train_step_milnce_instrumented")
            same = iplan.peak_bytes == plans["train_step_milnce"].peak_bytes
            results.append(CheckResult(
                "train_step_milnce_instrumented", "GL013-identical-plan",
                same, "" if same else
                f"instrumented peak {iplan.peak_bytes} B != plain "
                f"{plans['train_step_milnce'].peak_bytes} B — the span "
                "wrapper changed the traced program"))
        except Exception as exc:                     # pragma: no cover
            results.append(CheckResult(
                "train_step_milnce_instrumented", "memplan-build", False,
                f"{type(exc).__name__}: {exc}"))
    return results


# --------------------------------------------------------------------------
# what-if prediction (operating points the CPU can only trace, not run)
# --------------------------------------------------------------------------

def what_if_program(*, batch: int, frames: int, size: int, words: int = 20,
                    k: int = 5, dtype: str = "bfloat16",
                    grad_accum: int = 1, mesh_axes=None,
                    preset: str = "full", fsdp_min_size=None,
                    loss_impl: str = "dense",
                    milnce_chunk: int = 0) -> tuple:
    """Trace the train step at a (possibly TPU-scale) operating point
    on the CPU: the model is built at the requested config, the state
    comes from ``jax.eval_shape`` (no bytes allocated), and
    ``make_jaxpr`` over ShapeDtypeStructs gives the exact program the
    operating point would compile — tracing is abstract, so a
    batch-256 32f@224 program costs seconds of host time and zero
    device memory.  ``mesh_axes`` like ``{'data': 4, 'model': 2}``
    needs ``prod(sizes)`` visible devices (scripts/mem_plan.py forces
    the virtual-CPU count to match).

    Returns ``(closed_jaxpr, labels, donated, entry_desc, mesh_desc)``
    — the shared what-if substrate: Pass 4 (what_if_step) runs the
    live-range walk over it, Pass 5 (numerics.what_if_audit) the
    dtype-flow walk, over the SAME traced program."""
    import jax
    import jax.numpy as jnp

    from milnce_tpu.config import full_preset, tiny_preset
    from milnce_tpu.models.build import build_model
    from milnce_tpu.parallel.mesh import build_mesh
    from milnce_tpu.train.schedule import build_schedule
    from milnce_tpu.train.state import build_optimizer, create_train_state
    from milnce_tpu.train.step import (STATE_DONATION_ARGNUMS,
                                       make_grad_cache_step,
                                       make_train_step)

    cfg = full_preset() if preset == "full" else tiny_preset()
    cfg.model.dtype = dtype
    # loss-impl axis (ISSUE 12): predict the SAME operating point under
    # the dense cube vs the chunked stream — the dense-vs-chunked
    # crossover at the Bg=8192 recipe is a what-if verdict pair, no chip
    # needed (PERF.md "Memory-efficient loss", BENCH_MILNCE_LOSS.md)
    cfg.loss.milnce_impl = loss_impl
    cfg.loss.milnce_chunk = int(milnce_chunk)
    if loss_impl == "dense" and milnce_chunk:
        raise ValueError(
            "--milnce-chunk only shapes the chunked/auto impls — pass "
            "--loss-impl chunked (a dense what-if never reads it)")
    loss_cfg = cfg.loss if loss_impl != "dense" else None
    mesh_axes = dict(mesh_axes or {"data": len(jax.devices())})
    model_axis = None
    for ax, n in mesh_axes.items():
        if ax == "data":
            continue
        model_axis = ax
        cfg.parallel.model_axis = ax
        cfg.parallel.model_parallel_size = int(n)
    need = math.prod(mesh_axes.values())
    have = len(jax.devices())
    if need != have:
        # EXACT match, not <=: build_mesh folds every visible device
        # into the grid, so 8 devices under a requested data=2,model=2
        # would silently become a 4x2 mesh — divisors doubled, per-chip
        # peak halved, and the refusal gate waving through a config
        # that OOMs on the real 2x2 topology
        raise ValueError(
            f"what-if mesh {mesh_axes} needs exactly {need} visible "
            f"devices, got {have} — scripts/mem_plan.py sets "
            "xla_force_host_platform_device_count to match; in-process "
            "callers must request a mesh whose product equals the "
            "device count")
    model = build_model(cfg.model)
    optimizer = build_optimizer(cfg.optim, build_schedule(cfg.optim, 1000))
    mesh = build_mesh(cfg.parallel)

    def init_fn(key):
        variables = model.init(
            key, jnp.zeros((2, frames, size, size, 3), jnp.float32),
            jnp.zeros((2 * k, words), jnp.int32))
        return create_train_state(variables, optimizer)

    state = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    state_specs = None
    if model_axis:
        from milnce_tpu.parallel.sharding_map import state_partition_specs

        kw = {} if fsdp_min_size is None else {"min_size": fsdp_min_size}
        state_specs = state_partition_specs(state, mesh, model_axis, **kw)
    if grad_accum > 1:
        step = make_grad_cache_step(model, optimizer, mesh, grad_accum,
                                    donate=False, loss_cfg=loss_cfg,
                                    state_specs=state_specs,
                                    model_axis=model_axis)
    else:
        step = make_train_step(model, optimizer, mesh, donate=False,
                               loss_cfg=loss_cfg, state_specs=state_specs,
                               model_axis=model_axis)
    args = (state,
            jax.ShapeDtypeStruct((batch, frames, size, size, 3), jnp.uint8),
            jax.ShapeDtypeStruct((batch * k, words), jnp.int32),
            jax.ShapeDtypeStruct((batch,), jnp.float32))
    mesh_desc = "x".join(f"{n}" for n in mesh_axes.values()) + (
        f" ({','.join(mesh_axes)})")
    impl_tag = "" if loss_impl == "dense" else f", loss={loss_impl}"
    entry_desc = (f"what_if(batch={batch}, {frames}f@{size}, "
                  f"{dtype}, ga={grad_accum}{impl_tag})")
    return (jax.make_jaxpr(step)(*args),
            arg_leaf_labels(args, _STEP_ARGNAMES),
            donated_leaf_flags(args, STATE_DONATION_ARGNUMS),
            entry_desc, mesh_desc)


def what_if_step(*, batch: int, frames: int, size: int, **kw) -> MemPlan:
    """Predict the per-chip peak of the train step at an operating
    point — the live-range walk over :func:`what_if_program`'s trace
    (flags documented there; scripts/mem_plan.py is the CLI)."""
    closed, labels, donated, entry_desc, mesh_desc = what_if_program(
        batch=batch, frames=frames, size=size, **kw)
    plan = analyze_jaxpr(closed, donated=donated, labels=labels)
    plan.entry = entry_desc
    plan.mesh = mesh_desc
    return plan


def budget_verdict(plan: MemPlan, hbm_gib: float) -> tuple:
    """(fits, message) against a per-chip HBM budget; the refusal names
    the top-3 contributors so the fix is actionable without a chip."""
    budget = int(hbm_gib * 2 ** 30)
    fits = plan.peak_bytes <= budget
    top = ", ".join(f"{label} ({b / 2**20:.1f} MiB)"
                    for label, b in plan.contributors[:3])
    msg = (f"{plan.entry} on {plan.mesh}: predicted per-chip peak "
           f"{plan.peak_bytes / 2**30:.3f} GiB "
           f"{'fits' if fits else 'EXCEEDS'} the {hbm_gib:g} GiB budget"
           f"; top contributors: {top}")
    return fits, msg


def preflight_fn_peak(fn, *args) -> int:
    """Per-chip predicted peak of an arbitrary jitted/traceable callable
    — the stage_probe autotune pre-flight (no donation, no sharding
    assumptions beyond what the program carries)."""
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    return analyze_jaxpr(closed).peak_bytes
