"""graftlint rule catalogue.

Every rule exists because the corresponding pothole has already cost (or
would silently cost) real TPU throughput in this codebase; ANALYSIS.md
carries the long-form rationale and a worked example per rule.  Rules are
addressed by ID (``GL001``) or name (``host-sync-hot-loop``) — both work
in the suppression syntax::

    x = float(loss)  # graftlint: disable=GL001(display-cadence fetch)

A suppression must carry a non-empty reason; a bare ``disable=GL001`` is
itself a finding (GL000) so exceptions stay *documented*, never silent.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Rule:
    id: str
    name: str
    summary: str
    rationale: str
    example: str
    fix: str


_RULE_LIST = (
    Rule(
        id="GL000",
        name="bad-suppression",
        summary="malformed or stale graftlint suppression / annotation "
                "comment",
        rationale="A suppression without a reason (or naming an unknown "
                  "rule) silences findings without documenting why; the "
                  "whole point of the inline syntax is that every audited "
                  "exception carries its audit.  A STALE suppression — one "
                  "whose rule no longer fires on that line — is the same "
                  "rot in reverse: the audited-exceptions table in LINT.md "
                  "claims an exception that no longer exists, and a later "
                  "real finding on that line would be silently absorbed.  "
                  "Ditto a `# guarded-by:` annotation naming a lock the "
                  "module doesn't declare.",
        example="x = float(loss)  # graftlint: disable=GL001",
        fix="write `# graftlint: disable=GL001(<why this sync is safe>)`; "
            "delete suppressions whose rule stopped firing (or re-audit "
            "why you expected it to); fix typo'd guarded-by lock names",
    ),
    Rule(
        id="GL001",
        name="host-sync-hot-loop",
        summary="host-blocking call reachable from the training hot loop",
        rationale="float()/int()/.item()/np.asarray()/jax.device_get() on "
                  "a device value blocks the host until the device "
                  "catches up, defeating the async dispatch pipeline "
                  "device_prefetch exists to enable — the reference loses "
                  "throughput to exactly this (loss.item() per batch).",
        example="loss_val = float(loss)  # inside the per-batch loop",
        fix="accumulate on device; transfer only at n_display cadence "
            "(and suppress that audited fetch with a reason)",
    ),
    Rule(
        id="GL002",
        name="traced-python-flow",
        summary="Python if/for/while on a traced value inside jitted code",
        rationale="Branching on a tracer either crashes at trace time "
                  "(ConcretizationTypeError) or — via `static_argnums` "
                  "promotion or weak-type coincidence — silently builds a "
                  "new XLA program per value: a recompilation storm.",
        example="if x > 0:  # x is a traced array",
        fix="use lax.cond/lax.select/jnp.where, or hoist the decision to "
            "build time (shapes and config are static)",
    ),
    Rule(
        id="GL003",
        name="jit-missing-donate",
        summary="jax.jit of a train-step-shaped function without "
                "donate_argnums",
        rationale="A train step that updates a TrainState without "
                  "donating it keeps TWO copies of params+opt_state live "
                  "across the update — at real scale that is the "
                  "difference between fitting the batch and OOM, and XLA "
                  "cannot reuse the input buffers in place.",
        example="step = jax.jit(train_step)",
        fix="jax.jit(train_step, donate_argnums=(0,)) — donate the state "
            "argument that the step consumes and returns",
    ),
    Rule(
        id="GL004",
        name="f64-literal-drift",
        summary="array construction that lands in float64 under x64 "
                "(or anywhere)",
        rationale="np.zeros()/jnp.asarray(0.5) without an explicit dtype "
                  "default to float64 (numpy always; jax under "
                  "jax_enable_x64).  An f64 operand silently upcasts "
                  "every downstream op — 2x HBM traffic and off the MXU "
                  "fast path — and H2D transfers double in size.",
        example="pad = jnp.asarray(0.5)  # f64 under x64",
        fix="pass dtype= explicitly (np.float32, or the model's compute "
            "dtype)",
    ),
    Rule(
        id="GL005",
        name="unsynced-walltime",
        summary="wall-clock timing without a device sync",
        rationale="JAX dispatch is async: time.time() deltas around a "
                  "jitted call measure enqueue latency, not device work. "
                  "Every headline number in BENCH_NOTES.md exists because "
                  "naive timing once reported 11.5 ms for a 5 us kernel.",
        example="t0 = time.time(); f(x); dt = time.time() - t0",
        fix="jax.block_until_ready(result) before reading the clock (or "
            "materialize the value on host, utils/timing.py protocol)",
    ),
    Rule(
        id="GL006",
        name="print-under-trace",
        summary="print() inside jit-traced code",
        rationale="print in traced code fires once at trace time (showing "
                  "tracers, not values) and never again — it reads like "
                  "per-step logging but is neither per-step nor values; "
                  "with impure callbacks it can also pin a host sync.",
        example="print('loss', loss)  # inside the jitted step",
        fix="jax.debug.print for traced values; host-side logging belongs "
            "outside the step at display cadence",
    ),
    Rule(
        id="GL007",
        name="swallowed-broad-except",
        summary="broad `except` that drops the error on the floor",
        rationale="A bare/`except Exception:` handler that neither "
                  "re-raises nor records the caught exception (uses the "
                  "bound name, logs with exc_info) swallows failures "
                  "silently — at pod scale that is how a 90%-corrupt "
                  "dataset 'trains' green and how a flaky checkpoint "
                  "store loses an epoch without a log line.",
        example="except Exception:\n    pass",
        fix="re-raise, bind and use the exception (log/record it), pass "
            "exc_info to a logging call, or suppress with a reason: "
            "# graftlint: disable=GL007(<why swallowing is correct here>)",
    ),
    Rule(
        id="GL008",
        name="obs-under-trace",
        summary="metrics/span recording reachable inside jit-traced code",
        rationale="Registry counters and span recorders are HOST I/O "
                  "(locks, ring appends, line-buffered file writes — "
                  "milnce_tpu/obs/).  Under jit they fire exactly once at "
                  "trace time with tracer values: what reads like per-step "
                  "telemetry records garbage once and then never again, "
                  "and routing it through a callback instead pins a host "
                  "sync into the step.  Recording belongs OUTSIDE the "
                  "traced function, at the existing host boundary "
                  "(display cadence / the dispatch site).",
        example="with REC.span('inner'):  # inside the jitted step body\n"
                "    loss = loss_fn(params)\n"
                "METRICS.inc()             # ditto",
        fix="move the .inc()/.observe()/.span()/.event() call outside the "
            "traced function (train/loop.py feeds the registry from the "
            "display-cadence fetch); genuinely trace-time-only setup gets "
            "# graftlint: disable=GL008(<why this is trace-time setup>)",
    ),
    Rule(
        id="GL009",
        name="phantom-mesh-axis",
        summary="with_sharding_constraint naming an axis absent from "
                "the mesh",
        rationale="A PartitionSpec axis name that no mesh declares does "
                  "not error — GSPMD just treats the dimension as "
                  "unconstrained and REPLICATES it.  A typo'd "
                  "`P('modle')` in a traced step therefore traces, "
                  "compiles, and runs... with every 'sharded' tensor "
                  "silently full-size on every chip: the exact failure "
                  "the 2-D FSDP path exists to avoid, invisible until "
                  "someone reads an HBM profile.  (The runtime twin of "
                  "this check is sharding_map.build_param_specs, which "
                  "raises on a phantom model_axis.)",
        example="x = jax.lax.with_sharding_constraint(x, P('modle'))",
        fix="name only axes the mesh declares (this repo's canonical "
            "axes are 'data' and 'model' — ParallelConfig; the lint "
            "also accepts axes named by a Mesh(...) construction or an "
            "axis_name= kwarg in the same module); a deliberate "
            "foreign-mesh constraint gets "
            "# graftlint: disable=GL009(<which mesh declares it>)",
    ),
    Rule(
        id="GL010",
        name="unguarded-shared-state",
        summary="shared mutable attribute accessed outside its lock in a "
                "thread-shared class",
        rationale="The serving/obs layers are a thread mesh: batcher "
                  "worker, HTTP request threads, data readers and the "
                  "train loop share per-class state behind ad-hoc locks. "
                  "A write outside the attribute's guard (or with no "
                  "guard at all) is a data race — lost counter "
                  "increments, dict-changed-size crashes mid-/healthz, "
                  "the exact bugs three of the last four PRs fixed by "
                  "hand after review.  Lock-free READS of a guarded "
                  "attribute are equally racy unless the attribute is "
                  "write-once in __init__ (the audited tokenizer "
                  "pattern: publish-then-read-only is safe under the "
                  "GIL's reference semantics).",
        example="self._calls[key] = self._calls.get(key, 0) + 1  "
                "# no lock; called from worker AND request threads",
        fix="take the guard (`with self._lock:`) around every access; "
            "declare the guard explicitly with `# guarded-by: _lock` on "
            "the __init__ assignment when inference can't see it; a "
            "deliberate lock-free read of a write-once attribute is "
            "already exempt — anything else needs a reasoned "
            "suppression",
    ),
    Rule(
        id="GL011",
        name="lock-order-cycle",
        summary="cycle in the static lock-acquisition order graph",
        rationale="If thread 1 takes A then B while thread 2 takes B "
                  "then A, some interleaving deadlocks — whether or not "
                  "today's tests hit it.  The lint builds the "
                  "acquisition graph (lock held -> lock acquired, "
                  "through same-module calls and across modules via "
                  "imported module-level locks like "
                  "DEVICE_DISPATCH_LOCK) and fails on any cycle, so a "
                  "deadlock-shaped ordering is a tier-1 failure at "
                  "review time, not a wedged pod at 3am.  The runtime "
                  "twin (analysis/lockrt.SanitizedLock) enforces the "
                  "same discipline on live threads.",
        example="# thread 1: with A: with B: ...\n"
                "# thread 2: with B: with A: ...",
        fix="pick ONE global order for the locks involved and acquire "
            "in that order everywhere (narrow critical sections until "
            "nesting disappears is even better); a provably-safe "
            "ordering the analysis can't see gets "
            "# graftlint: disable=GL011(<why no interleaving deadlocks>)",
    ),
    Rule(
        id="GL012",
        name="blocking-under-lock",
        summary="blocking call (future.result/join/wait/open/sleep or "
                "device dispatch) while holding a lock",
        rationale="A lock held across a blocking call stalls EVERY "
                  "contender for the full wait: request threads pile up "
                  "behind one file open, one future, one device "
                  "dispatch.  Worse, blocking on work that needs another "
                  "lock-holder to finish (future.result under a lock "
                  "the worker also takes) is a deadlock with extra "
                  "steps.  Device dispatch is exempt ONLY under locks "
                  "whose name contains 'dispatch' — serializing device "
                  "work is DEVICE_DISPATCH_LOCK's entire job; anything "
                  "else blocking under it still fires.",
        example="with self._lock:\n    row = fut.result()",
        fix="move the blocking work outside the critical section (copy "
            "state under the lock, block after release — the "
            "kill_inflight_decoders pattern); a deliberate "
            "block-under-lock gets "
            "# graftlint: disable=GL012(<why contenders may wait>)",
    ),
    Rule(
        id="GL013",
        name="peak-budget-regression",
        summary="per-entry per-chip peak device bytes drifted from the "
                "pinned budget (static HBM planner)",
        rationale="Fitting the 32-frame step into HBM was the original "
                  "run's binding constraint, and our own PERF.md records "
                  "a >10% batch cliff whose diagnosis cost a chip "
                  "session.  The Pass 4 planner (analysis/memplan.py) "
                  "computes each entry's per-chip peak bytes from jaxpr "
                  "live ranges — sharding- and donation-aware — and pins "
                  "it like a collective count: a rematerialized "
                  "activation, a doubled optimizer moment or a lost "
                  "donation lands as a failing tier-1 check, not as an "
                  "OOM weeks later on the chip.",
        example="EXPECTED_PEAK_BYTES['train_step_milnce'] drifts +30%",
        fix="find the buffer in the GL015 contributor diff / MEMPLAN.md; "
            "if the growth is intended, re-pin EXPECTED_PEAK_BYTES in "
            "the same commit (entry-level rule — inline suppressions "
            "don't apply)",
    ),
    Rule(
        id="GL014",
        name="ineffective-or-missing-donation",
        summary="donated buffer that cannot be reused, or a large "
                "aliasable arg left undonated on a grad-bearing entry",
        rationale="donate_argnums is the difference between one and two "
                  "copies of params+opt_state across the update — at "
                  "real scale, the difference between fitting the batch "
                  "and OOM (GL003's rationale, enforced at the jaxpr "
                  "level where it is checkable).  A donation whose "
                  "buffer matches no program output (or is returned "
                  "unchanged) is dead weight that reads like a "
                  "protection; an undonated large aliasable arg is the "
                  "regression GL003 cannot see once jit sites hide "
                  "behind factories.  The audit also verifies that "
                  "each factory's production build REQUESTS the "
                  "donation from jax.jit.",
        example="jax.jit(step, donate_argnums=(1,))  # arg 1 is returned "
                "unchanged",
        fix="donate the consumed state (train/step.py "
            "STATE_DONATION_ARGNUMS is the declared intent), or drop a "
            "donation that cannot take effect; entry-level rule — "
            "re-register the intent in analysis/memplan.py, inline "
            "suppressions don't apply",
    ),
    Rule(
        id="GL015",
        name="top-contributor-drift",
        summary="an entry's top-3 peak-memory contributors changed "
                "identity (pinned by name)",
        rationale="A peak regression inside the GL013 tolerance can "
                  "still change WHAT occupies the peak — a silently "
                  "rematerialized activation, an f32 upcast of a bf16 "
                  "buffer, an optimizer moment that stopped sharding.  "
                  "Pinning the top-3 contributor NAMES (arg tree paths "
                  "/ 'primitive aval' labels) turns that into a "
                  "readable diff instead of a mystery byte delta — the "
                  "same reasoning as pinning collective multisets "
                  "rather than just their sum.",
        example="'conv_general_dilated f32[...]' replaces "
                "'state/params/conv_2c/...' at the peak",
        fix="explain the new occupant (MEMPLAN.md names its bytes); if "
            "intended, re-pin EXPECTED_TOP_CONTRIBUTORS in the same "
            "commit (entry-level rule — inline suppressions don't "
            "apply)",
    ),
    Rule(
        id="GL016",
        name="low-precision-accumulation",
        summary="add-based reduction / dot_general accumulation / psum "
                "whose accumulator dtype is bf16/f16 at reduction "
                "extent >= threshold",
        rationale="bf16 has an 8-bit mantissa: summing N same-sign "
                  "terms loses ~log2(N) of it, so a 256-term reduction "
                  "keeps EFFECTIVELY zero fractional bits.  The MXU "
                  "accumulates f32 natively — a bf16 accumulator is "
                  "never a speed win, only a missing "
                  "preferred_element_type=f32 (or an upcast dropped "
                  "from a loss/psum chain).  Pass 5 "
                  "(analysis/numerics.py) walks each entry's jaxpr and "
                  "fires on every low-precision accumulation whose "
                  "reduced extent crosses the threshold, so the bf16 "
                  "what-if shows exactly which reductions must keep an "
                  "f32 accumulator before anyone flips the model dtype.",
        example="jnp.sum(x_bf16, axis=0)  # extent 4096, bf16 "
                "accumulator",
        fix="accumulate in f32: preferred_element_type=jnp.float32 on "
            "the dot, or .astype(jnp.float32) before the sum/psum "
            "(entry-level rule — a deliberate low-precision "
            "accumulation is re-registered in analysis/numerics.py, "
            "inline suppressions don't apply)",
    ),
    Rule(
        id="GL017",
        name="unstabilized-exp-domain",
        summary="exp without a max-subtraction guard, or a reduce-sum "
                "division without eps, in a loss module",
        rationale="exp overflows f32 at x>88 and bf16 at x>88 with far "
                  "coarser spacing; every softmax/logsumexp in the "
                  "losses must subtract a running or global max before "
                  "exponentiating (the online-softmax identity keeps "
                  "this free), and every normalization that divides by "
                  "a reduced sum needs an eps or max() floor.  The "
                  "AST half of Pass 5 pattern-matches exp/division "
                  "sites in losses/; the jaxpr half confirms the "
                  "subtraction actually reaches the exp operand.  A "
                  "deliberately-unguarded site (e.g. reference parity "
                  "with the paper's unstabilized sum) carries an "
                  "audited reason.",
        example="neg = jnp.exp(pairwise).sum(axis=1)",
        fix="subtract the row max (or reuse the logsumexp/online-"
            "softmax guard) before exp; floor sum denominators with "
            "eps or jnp.maximum; a deliberate site gets "
            "# graftlint: disable=GL017(<why the domain is bounded>)",
    ),
    Rule(
        id="GL018",
        name="dtype-boundary-drift",
        summary="an entry's dtype census (buffer bytes by dtype) or "
                "cast inventory (named convert_element_type sites) "
                "drifted from the pin",
        rationale="Mixed precision only stays correct if every "
                  "f32<->bf16 boundary is deliberate: an appearing "
                  "cast is a new upconversion eating HBM (GL015's f32 "
                  "BatchNorm finding), a vanishing cast is a loss "
                  "accumulator silently demoted.  Pass 5 pins each "
                  "entry's census and cast inventory the way Pass 2 "
                  "pins collective multisets — drift lands as a "
                  "readable named diff in tier-1, not as a loss curve "
                  "divergence three days into a run.",
        example="'f32->bf16 @ convert_element_type(state/params/...)' "
                "vanishes from train_step_milnce",
        fix="explain the moved boundary (NUMERICS.md names every "
            "cast); if intended, re-pin EXPECTED_DTYPE_CENSUS / "
            "EXPECTED_CASTS in the same commit (entry-level rule — "
            "inline suppressions don't apply)",
    ),
)

RULES: dict[str, Rule] = {r.id: r for r in _RULE_LIST}
RULES_BY_NAME: dict[str, Rule] = {r.name: r for r in _RULE_LIST}


def resolve_rule(token: str) -> Rule | None:
    """Accept either a rule ID ('GL001') or name ('host-sync-hot-loop')."""
    return RULES.get(token) or RULES_BY_NAME.get(token)
