"""The jitted distributed train step.

One SPMD program replaces the reference's whole per-batch runtime
(TrainOneBatch, main_distributed.py:226-241): H2D copy + ``/255``
normalize + forward both towers + NCCL all-gather + MIL-NCE + DDP
all-reduce backward + Adam/SGD + scheduler step all fuse into a single
``shard_map``-ped XLA computation over the data mesh axis:

- batch arrives **uint8** and is normalized on device (parity with
  main_distributed.py:227-230; uint8 transfer = 4x less host->HBM
  traffic);
- global negatives: ``lax.all_gather`` inside the loss
  (milnce_tpu.losses.milnce) — the collective rides ICI;
- gradient reduction: explicit ``lax.psum`` (what DDP's bucketed
  all-reduce does implicitly, main_distributed.py:91);
- BatchNorm running stats are ``pmean``-merged across shards each step
  (the reference keeps per-GPU stats and checkpoints rank-0's,
  README.md:13 — merging is the same cost and strictly less arbitrary);
- the LR schedule is a pure function of ``state.step``
  (utils.py:26-38), no separate scheduler object.

2-D ``(data, model)`` mesh (ROADMAP item 2, SNIPPETS.md [1]-[3]): pass
``state_specs`` (a TrainState of PartitionSpec from
``parallel.sharding_map.state_partition_specs``) plus ``model_axis`` and
the step goes FSDP: the batch shards over BOTH axes (every chip is a
data shard — global-batch semantics are identical to the 1-D mesh, so
local BN needs no sync), large params arrive as model-axis shards and
are all_gathered per leaf right before the forward, and the grad
reduction runs per leaf — ``psum_scatter`` over the model axis (the
reduce-scatter half of the FSDP pair) + ``psum`` over data for sharded
leaves, a plain both-axes ``psum`` for replicated ones.  Per-leaf
reductions are independent collectives, so XLA's latency-hiding
scheduler can overlap each with the remainder of the backward instead
of draining into one terminal fused psum (``overlap_grad_reduce``).
The optimizer update then runs on the LOCAL shards: Adam moments for a
sharded kernel never materialize beyond ``1/model_parallel_size`` per
chip.  Collective counts for both 2-D steps are pinned in
analysis/trace_invariants.py (``train_step_milnce_2d``,
``grad_cache_2d``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from milnce_tpu.losses.milnce_chunked import build_milnce_loss
from milnce_tpu.resilience import faults
from milnce_tpu.train.state import TrainState

# The train-step donation contract, in ONE place: argument 0 (the
# TrainState) is consumed and returned, so its buffers are donated, on
# every backend (the CPU tests run the donated program too).  The
# graftlint Pass 4 donation audit (analysis/memplan.py GL014) reads this
# as the declared intent — a step factory that stops donating the
# state, or a new large aliasable argument left undonated, fails there.
STATE_DONATION_ARGNUMS = (0,)


def _apply_grad_poison(grads, step):
    """Device-side ``grad.nonfinite`` fault site: when armed at BUILD
    time, multiply the reduced gradients by NaN on scheduled optimizer
    steps (``state.step + 1`` is the 1-based occurrence index — see
    resilience/faults.py).  The schedule is baked into the trace as pure
    jnp ops on ``state.step``: deterministic, no host sync, and adds
    nothing at all when disarmed."""
    spec = faults.device_schedule("grad.nonfinite")
    if spec is None:
        return grads
    n = step + 1
    if spec.mode == "all":
        hit = jnp.bool_(True)
    elif spec.mode == "every":
        hit = (n % spec.every) == 0
    else:
        hit = jnp.any(n == jnp.asarray(spec.at, jnp.int32))
    poison = jnp.where(hit, jnp.float32(jnp.nan), jnp.float32(1.0))
    return jax.tree_util.tree_map(lambda g: g * poison.astype(g.dtype), grads)


def _all_finite(tree):
    """Scalar bool: every leaf of ``tree`` is all-finite.  Computed on
    the already-reduced (replicated) gradients, so no collective is
    needed and every shard reaches the same verdict."""
    ok = jnp.bool_(True)
    for leaf in jax.tree_util.tree_leaves(tree):
        ok = ok & jnp.all(jnp.isfinite(leaf))
    return ok


def _select_tree(ok, new, old):
    """Leaf-wise ``jnp.where(ok, new, old)`` — the skip-update select of
    the finite guard (params / opt_state / batch_stats keep their
    pre-step values on a non-finite gradient)."""
    return jax.tree_util.tree_map(lambda n, o: jnp.where(ok, n, o), new, old)


def _gather_params(params, param_specs, model_axis):
    """FSDP gather: local model-axis shards -> full parameters, one
    ``all_gather`` per SHARDED leaf (replicated leaves pass through).
    Sits right before the forward so XLA can overlap each gather with
    compute on already-gathered layers."""
    from milnce_tpu.parallel import sharding_map as smap

    def gather(leaf, spec):
        d = smap.sharded_dim(spec, model_axis)
        if d is None:
            return leaf
        return lax.all_gather(leaf, model_axis, axis=d, tiled=True)

    return smap.map_with_specs(gather, params, param_specs)


def _reduce_grads_2d(grads, param_specs, data_axis, model_axis,
                     mesh_size: int, mean: bool, overlap: bool):
    """Cross-mesh gradient reduction for the 2-D step: full per-device
    grads -> fully-reduced LOCAL-shard grads.

    Sharded leaf (model@d): ``psum_scatter`` over the model axis along d
    (each chip keeps only ITS shard of the summed grad — the
    reduce-scatter half of the FSDP pair; its transpose-twin all_gather
    sits in :func:`_gather_params`) then ``psum`` over data.  Replicated
    leaf: one psum over both axes.  ``mean=True`` (the DTW family's
    pmean semantics) divides by the total mesh size after summing.

    ``overlap=True`` emits the replicated-leaf psums per leaf too, so
    every reduction is an independent collective the scheduler can
    overlap with the rest of the backward; ``overlap=False`` fuses the
    replicated subset into one terminal tree psum (the 1-D step's
    pinned shape) — sharded leaves are per-leaf either way, their
    scatter dimension differs."""
    from milnce_tpu.parallel import sharding_map as smap

    leaves, treedef = jax.tree_util.tree_flatten(grads)
    specs = smap.spec_leaves(param_specs)
    assert len(leaves) == len(specs), (len(leaves), len(specs))
    out: list = [None] * len(leaves)
    fused_idx: list = []
    for i, (g, sp) in enumerate(zip(leaves, specs)):
        d = smap.sharded_dim(sp, model_axis)
        if d is not None:
            g = lax.psum_scatter(g, model_axis, scatter_dimension=d,
                                 tiled=True)
            out[i] = lax.psum(g, data_axis)
        elif overlap:
            out[i] = lax.psum(g, (data_axis, model_axis))
        else:
            fused_idx.append(i)
    if fused_idx:
        fused = lax.psum(tuple(leaves[i] for i in fused_idx),
                         (data_axis, model_axis))
        for i, g in zip(fused_idx, fused):
            out[i] = g
    if mean:
        out = [g / mesh_size for g in out]
    return treedef.unflatten(out)


def _uniform_finite_verdict(ok, model_axis):
    """The finite guard's verdict must be CLUSTER-UNIFORM, and on the
    2-D mesh each model column inspects only ITS shard of the reduced
    grads — a NaN landing in one column's shard would skip the update
    there and apply it elsewhere, silently desyncing the replicas.  One
    scalar psum over the model axis makes every column see every
    column's verdict.  (The data axis needs nothing: post-psum grads
    are identical along it.)"""
    bad = lax.psum((~ok).astype(jnp.float32), model_axis)
    return bad == 0


def _sequence_loss(loss_cfg, v_seq, t_seq, start, data_axis):
    """DTW-family losses on mesh-gathered sequence embeddings.

    The fork's losses score the FULL gathered batch on every rank
    (loss.py:20-134 after the all-gather at train.py:217-219); we gather
    over the mesh axis and compute the identical replicated loss."""
    from milnce_tpu.losses.dtw_losses import (cdtw_batch_loss, sdtw_3_loss,
                                              sdtw_cidm_loss,
                                              sdtw_negative_loss)

    v_all = lax.all_gather(v_seq, data_axis, axis=0, tiled=True)
    t_all = lax.all_gather(t_seq, data_axis, axis=0, tiled=True)
    start_all = lax.all_gather(start, data_axis, axis=0, tiled=True)
    common = dict(backend=getattr(loss_cfg, "sdtw_backend", "scan"),
                  dist=getattr(loss_cfg, "sdtw_dist", ""),
                  bandwidth=getattr(loss_cfg, "sdtw_bandwidth", 0))
    if loss_cfg.sdtw_gamma is not None:
        # None = each loss function's own reference-default gamma
        # (cdtw 1e-5, sdtw_* 0.1 — encoded in their signatures)
        common["gamma"] = loss_cfg.sdtw_gamma
    dispatch = {
        "cdtw": lambda: cdtw_batch_loss(v_all, t_all, **common),
        "sdtw_cidm": lambda: sdtw_cidm_loss(
            v_all, t_all, start_all, sigma=loss_cfg.cidm_sigma,
            lam=loss_cfg.cidm_lambda, **common),
        "sdtw_negative": lambda: sdtw_negative_loss(v_all, t_all, **common),
        "sdtw_3": lambda: sum(sdtw_3_loss(
            v_all, t_all,
            pair_chunk=getattr(loss_cfg, "sdtw_pair_chunk", 0), **common)),
    }
    # one source of truth: a loss added here without a KNOWN_LOSSES entry
    # (or vice versa) fails loudly at first trace, not per-name
    assert set(dispatch) == set(KNOWN_LOSSES) - {"milnce"}, (
        "sequence-loss dispatch and KNOWN_LOSSES diverged")
    return dispatch[loss_cfg.name]()


KNOWN_LOSSES = ("milnce", "cdtw", "sdtw_cidm", "sdtw_negative", "sdtw_3")


def _check_loss_name(loss_cfg) -> str:
    """Reject a bad loss name at step-BUILD time: inside the traced step
    the error would only surface after a full model trace (and on a real
    cluster, after an expensive XLA compile)."""
    name = getattr(loss_cfg, "name", "milnce")
    if name not in KNOWN_LOSSES:
        raise ValueError(f"unknown loss {name!r} (expected one of "
                         f"{', '.join(KNOWN_LOSSES)})")
    return name


def make_grad_cache_step(model, optimizer, mesh: Mesh,
                         micro_batches: int, data_axis: str = "data",
                         donate: bool = True, loss_cfg=None,
                         finite_guard: bool = False, state_specs=None,
                         model_axis=None, overlap_grad_reduce: bool = True):
    """Two-pass embedding-cache train step (GradCache-style) for every
    batch-contrastive loss: MIL-NCE and the DTW family.

    Contrastive losses don't decompose across plain gradient-accumulation
    microbatches — every clip must score against EVERY other clip in the
    effective batch.  The reference solved this with hardware (global
    batch 8192 across 64 TPUs, README.md:98-105); this step solves it in
    one SPMD program so the same recipe runs on any mesh size:

    1. embed all M microbatches under ``lax.scan`` (activations for one
       microbatch live at a time);
    2. compute the mesh-global loss and its gradient w.r.t. the CACHED
       embeddings — cheap: pooled (B, D) for MIL-NCE, sequence
       (B, T', D) for the DTW family (T' = temporal extent after the
       trunk, 8 frames -> 4);
    3. re-forward each microbatch seeding its VJP with the cached
       embedding gradients, accumulating parameter gradients.

    Cost: one extra forward (the pass-2 re-forward) — the same trade
    ``remat`` makes, but at 1/M activation memory with exact full-batch
    negatives/alignment pairs.  Each microbatch computes its own
    BatchNorm statistics, so a microbatch behaves exactly like an extra
    data-parallel shard with local BN (the reference's semantics,
    README.md:13): ``M microbatches x N chips == 1 microbatch x M*N
    chips`` to float tolerance (pinned in tests/test_train.py for both
    loss families).

    Gradient reduction follows make_train_step: ``psum`` for MIL-NCE
    (per-shard partial sums), ``pmean`` for the DTW family (the gathered
    loss is replicated on every shard, so the all_gather transpose
    already accumulates a mesh-size factor into the embedding grads).

    The cross-mesh reduction happens ONCE per optimizer step, AFTER the
    pass-2 scan has accumulated all M microbatches' local parameter
    grads — never per microbatch (a reduction inside the scan body
    would pay the collective M times for the same bytes: the ~25%
    ga=8 throughput hole BENCH_NOTES.md records).  The property is
    pinned structurally: the ``scan-reduction-free`` trace invariant
    asserts no collective primitive in any scan body of this program
    (analysis/trace_invariants.py).  With ``state_specs``/``model_axis``
    the same program runs FSDP on the 2-D mesh (module docstring):
    params gather once BEFORE pass 1, both scans run on the gathered
    tree, and the once-per-step reduction becomes the per-leaf
    psum_scatter+psum of :func:`_reduce_grads_2d`.
    """
    assert micro_batches > 1, "use make_train_step for micro_batches=1"
    loss_name = _check_loss_name(loss_cfg)
    # impl selection (dense cube / chunked stream / auto) resolves at
    # BUILD time from LossConfig; 'dense' (and loss_cfg=None) keeps the
    # traced program byte-identical to the pre-chunked step
    milnce_fn = build_milnce_loss(loss_cfg) if loss_name == "milnce" else None
    mesh_size = _check_2d_args(mesh, data_axis, model_axis, state_specs)
    fsdp = model_axis is not None
    batch_axes = (data_axis, model_axis) if fsdp else data_axis
    compute_dtype = jnp.dtype(getattr(model, "dtype", jnp.float32))

    def local_step(state: TrainState, video_u8, text_ids, start):
        b = video_u8.shape[0]
        assert b % micro_batches == 0, (b, micro_batches)
        bm = b // micro_batches
        k_rows = text_ids.shape[0] // b
        vids = video_u8.reshape((micro_batches, bm) + video_u8.shape[1:])
        txts = text_ids.reshape((micro_batches, bm * k_rows)
                                + text_ids.shape[1:])
        # FSDP: gather the full params ONCE, outside both scans — a
        # gather inside a scan body would re-ship every sharded kernel
        # per microbatch (and break the scan-reduction-free invariant)
        full_params = (_gather_params(state.params, state_specs.params,
                                      model_axis)
                       if fsdp else state.params)

        def fwd(params, batch_stats, vu8, tids):
            video = vu8.astype(compute_dtype) / jnp.asarray(255, compute_dtype)
            mode = {} if loss_name == "milnce" else {"mode": "sequence"}
            return model.apply({"params": params, "batch_stats": batch_stats},
                               video, tids, train=True,
                               mutable=["batch_stats"], **mode)

        # pass 1: embed every microbatch, cache embeddings only
        def embed_one(_, xs):
            vu8, tids = xs
            (v, t), mutated = fwd(full_params, state.batch_stats, vu8, tids)
            return None, (v, t, mutated["batch_stats"])

        _, (v_mb, t_mb, stats_mb) = lax.scan(embed_one, None, (vids, txts))
        # (M, bm, ...) -> (b, ...): pooled (b, D) or sequence (b, T', D)
        v_local = v_mb.reshape((b,) + v_mb.shape[2:])
        t_local = t_mb.reshape((b * k_rows,) + t_mb.shape[2:])

        # loss + gradients w.r.t. the cached embeddings (mesh-global
        # negatives/pairs exactly as the single-pass step)
        if loss_name == "milnce":
            def loss_of(v, t):
                return milnce_fn(v, t, batch_axes)
        else:
            def loss_of(v, t):
                t_seq = t.reshape(b, -1, t.shape[-1])      # (B, K, D)
                return _sequence_loss(loss_cfg, v, t_seq, start, batch_axes)

        loss, (g_v, g_t) = jax.value_and_grad(
            loss_of, argnums=(0, 1))(v_local, t_local)

        # pass 2: re-forward each microbatch, seed its VJP with the
        # cached embedding grads, accumulate LOCAL parameter grads —
        # the cross-mesh reduction stays outside the scan (docstring)
        g_v_mb = g_v.reshape((micro_batches, bm) + g_v.shape[1:])
        g_t_mb = g_t.reshape((micro_batches, bm * k_rows) + g_t.shape[1:])

        def grad_one(acc, xs):
            vu8, tids, gv, gt = xs

            def f(params):
                (v, t), _ = fwd(params, state.batch_stats, vu8, tids)
                return v, t

            _, vjp = jax.vjp(f, full_params)
            (g,) = vjp((gv, gt))
            return jax.tree_util.tree_map(jnp.add, acc, g), None

        zero = jax.tree_util.tree_map(jnp.zeros_like, full_params)
        grads, _ = lax.scan(grad_one, zero, (vids, txts, g_v_mb, g_t_mb))

        if fsdp:
            grads = _reduce_grads_2d(grads, state_specs.params, data_axis,
                                     model_axis, mesh_size,
                                     mean=loss_name != "milnce",
                                     overlap=overlap_grad_reduce)
        else:
            reduce = lax.psum if loss_name == "milnce" else lax.pmean
            grads = reduce(grads, data_axis)
        grads = _apply_grad_poison(grads, state.step)
        # merge BN stats over microbatches then shards: a microbatch is a
        # virtual shard, so mean-of-means matches the M*N-chip run
        new_stats = jax.tree_util.tree_map(
            lambda x: lax.pmean(jnp.mean(x, axis=0), batch_axes), stats_mb)
        updates, new_opt = optimizer.update(grads, state.opt_state,
                                            state.params)
        new_params = optax.apply_updates(state.params, updates)
        if finite_guard:    # same skip-update semantics as make_train_step
            ok = _all_finite(grads)
            if fsdp:
                ok = _uniform_finite_verdict(ok, model_axis)
            new_params = _select_tree(ok, new_params, state.params)
            new_opt = _select_tree(ok, new_opt, state.opt_state)
            new_stats = _select_tree(ok, new_stats, state.batch_stats)
            return TrainState(step=state.step + 1, params=new_params,
                              batch_stats=new_stats,
                              opt_state=new_opt), loss, (~ok).astype(jnp.int32)
        return TrainState(step=state.step + 1, params=new_params,
                          batch_stats=new_stats, opt_state=new_opt), loss

    state_spec = state_specs if fsdp else P()
    batch_spec = P(batch_axes)
    tail = (P(), P()) if finite_guard else (P(),)
    sharded = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(state_spec, batch_spec, batch_spec, batch_spec),
        out_specs=(state_spec,) + tail,
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=STATE_DONATION_ARGNUMS
                   if donate else ())


def _check_2d_args(mesh: Mesh, data_axis: str, model_axis, state_specs):
    """Build-time validation of the 2-D knobs: a phantom axis or a
    missing spec tree must fail HERE, not as a silent replication (the
    failure mode GL009 and sharding_map.build_param_specs also guard)."""
    if (model_axis is None) != (state_specs is None):
        raise ValueError(
            "2-D step needs BOTH model_axis and state_specs (build the "
            "spec tree with parallel.sharding_map.state_partition_specs)")
    if model_axis is None:
        return None
    for ax in (data_axis, model_axis):
        if ax not in mesh.axis_names:
            raise ValueError(
                f"step axis {ax!r} absent from mesh axes {mesh.axis_names}")
    import math

    return math.prod(mesh.shape.values())


def make_train_step(model, optimizer, mesh: Mesh, data_axis: str = "data",
                    donate: bool = True, loss_cfg=None, inner_steps: int = 1,
                    finite_guard: bool = False, state_specs=None,
                    model_axis=None, overlap_grad_reduce: bool = True):
    """Build the jitted train step.

    Returns ``step_fn(state, video_u8, text_ids, start) -> (state, loss)``:
    ``video_u8`` (B, T, H, W, 3) uint8, ``text_ids`` (B*K, W) int32,
    ``start`` (B,) float32 clip start-times (used by the CIDM loss; pass
    zeros otherwise) — all sharded on dim 0; ``state`` replicated.

    ``finite_guard=True`` folds a per-step all-finite gradient check into
    the jitted program and returns ``(state, loss, skipped)`` instead: a
    non-finite gradient keeps params/opt_state/batch_stats at their
    pre-step values via ``jnp.where`` (``skipped`` int32 1) — no host
    sync, no new collectives (pinned by the trace invariants).  The step
    counter still advances: it tracks batches CONSUMED, which the
    mid-epoch resume math relies on.

    Loss selection (LossConfig.name): 'milnce' scores pooled embeddings
    with per-shard partial sums psum'd inside the loss, so gradients are
    combined with ``psum``.  The DTW family scores the gathered batch
    identically on every shard (replicated loss), so gradients are
    combined with ``pmean`` — psum would overcount by the mesh size.

    ``inner_steps > 1`` runs that many optimizer steps on the SAME batch
    inside one XLA program (``lax.scan``) per dispatch.  Benchmark use
    only: it amortizes per-dispatch host latency so the measurement
    reflects device throughput.

    ``state_specs``/``model_axis``/``overlap_grad_reduce``: the 2-D
    FSDP path (module docstring).  ``state_specs=None`` keeps the 1-D
    program byte-identical to before — its pinned collective counts
    never move.
    """
    loss_name = _check_loss_name(loss_cfg)
    milnce_fn = build_milnce_loss(loss_cfg) if loss_name == "milnce" else None
    mesh_size = _check_2d_args(mesh, data_axis, model_axis, state_specs)
    fsdp = model_axis is not None
    # the loss axes: on the 2-D mesh every chip is a data shard (the
    # batch shards over BOTH axes), so negatives gather and grads reduce
    # over the combined axes — global-batch semantics match the 1-D mesh
    # of the same device count exactly, local BN included
    batch_axes = (data_axis, model_axis) if fsdp else data_axis
    # normalize straight into the model's compute dtype: a bf16 model casts
    # the video to bf16 at conv1 anyway (Conv3D promote_dtype), so an f32
    # intermediate would only add HBM traffic on the largest activation
    compute_dtype = jnp.dtype(getattr(model, "dtype", jnp.float32))

    def local_step(state: TrainState, video_u8, text_ids, start):
        video = video_u8.astype(compute_dtype) / jnp.asarray(255, compute_dtype)
        full_params = (_gather_params(state.params, state_specs.params,
                                      model_axis)
                       if fsdp else state.params)

        def loss_fn(params):
            variables = {"params": params, "batch_stats": state.batch_stats}
            if loss_name == "milnce":
                (v_embd, t_embd), mutated = model.apply(
                    variables, video, text_ids, train=True,
                    mutable=["batch_stats"])
                loss = milnce_fn(v_embd, t_embd, batch_axes)
            else:
                (v_seq, t_embd), mutated = model.apply(
                    variables, video, text_ids, mode="sequence", train=True,
                    mutable=["batch_stats"])
                b = video.shape[0]
                t_seq = t_embd.reshape(b, -1, t_embd.shape[-1])  # (B, K, D)
                loss = _sequence_loss(loss_cfg, v_seq, t_seq, start,
                                      batch_axes)
            return loss, mutated["batch_stats"]

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(full_params)
        if fsdp:
            grads = _reduce_grads_2d(grads, state_specs.params, data_axis,
                                     model_axis, mesh_size,
                                     mean=loss_name != "milnce",
                                     overlap=overlap_grad_reduce)
        else:
            reduce = lax.psum if loss_name == "milnce" else lax.pmean
            grads = reduce(grads, data_axis)
        grads = _apply_grad_poison(grads, state.step)
        new_stats = jax.tree_util.tree_map(
            lambda x: lax.pmean(x, batch_axes), new_stats)
        updates, new_opt = optimizer.update(grads, state.opt_state,
                                            state.params)
        new_params = optax.apply_updates(state.params, updates)
        if finite_guard:
            ok = _all_finite(grads)
            if fsdp:
                ok = _uniform_finite_verdict(ok, model_axis)
            new_params = _select_tree(ok, new_params, state.params)
            new_opt = _select_tree(ok, new_opt, state.opt_state)
            new_stats = _select_tree(ok, new_stats, state.batch_stats)
            new_state = TrainState(step=state.step + 1, params=new_params,
                                   batch_stats=new_stats, opt_state=new_opt)
            return new_state, loss, (~ok).astype(jnp.int32)
        new_state = TrainState(step=state.step + 1, params=new_params,
                               batch_stats=new_stats, opt_state=new_opt)
        return new_state, loss

    if inner_steps > 1:
        def local_loop(state, video_u8, text_ids, start):
            def body(st, _):
                out = local_step(st, video_u8, text_ids, start)
                return out[0], out[1:]

            state, outs = lax.scan(body, state, None, length=inner_steps)
            if finite_guard:
                return state, outs[0][-1], outs[1].sum()
            return state, outs[0][-1]

        local_fn = local_loop
    else:
        local_fn = local_step

    state_spec = state_specs if fsdp else P()
    batch_spec = P(batch_axes)
    tail = (P(), P()) if finite_guard else (P(),)
    sharded = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(state_spec, batch_spec, batch_spec, batch_spec),
        out_specs=(state_spec,) + tail,
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=STATE_DONATION_ARGNUMS
                   if donate else ())


def make_video_embed_fn(model, mesh: Mesh, data_axis: str = "data",
                        mixed5c: bool = False):
    """Jitted no-grad video-embedding extractor (counterpart of the
    reference eval loops' batched forwards, eval_msrvtt.py:61-66,
    eval_hmdb.py:75).  video_u8 sharded on dim 0; returns sharded embeds."""

    def local(variables, video_u8):
        dt = jnp.dtype(getattr(model, "dtype", jnp.float32))
        video = video_u8.astype(dt) / jnp.asarray(255, dt)
        return model.apply(variables, video, None, mode="video",
                           mixed5c=mixed5c)

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(), P(data_axis)),
        out_specs=P(data_axis), check_vma=False))


def make_text_embed_fn(model, mesh: Mesh, data_axis: str = "data"):
    """Jitted sentence tower: text_ids sharded on dim 0 -> sharded embeds.
    A language-model tower is its own program — ``text_lm_tower``
    (``model.text_lm``), ``text_hybrid_tower`` (``model.text_hybrid``) or
    ``text_dlm_tower`` (``model.text_dlm``) — and returns beside the embeddings its layers' counters, name -> int32
    scalar over all the data shards (the tower's ``counter_names(dims)``
    where it has one, else its ``COUNTER_NAMES``)."""
    kind = next((k for k in ("text_lm", "text_hybrid", "text_dlm")
                 if getattr(model, k, None) is not None), None)
    if kind is not None:
        import importlib

        from milnce_tpu.models.text_lm import COUNTERS, sum_counters

        tower_module = importlib.import_module(f"milnce_tpu.models.{kind}")
        names = (tower_module.counter_names(getattr(model, kind))
                 if hasattr(tower_module, "counter_names")
                 else tower_module.COUNTER_NAMES)

        def tower(variables, text_ids):
            emb, sown = model.apply(variables, None, text_ids, mode="text",
                                    mutable=[COUNTERS])
            return emb, {
                name: (jax.lax.pmax if name.endswith("_max")
                       else jax.lax.psum)(value, data_axis)
                for name, value in sum_counters(sown, names).items()}

        # the jitted program's name in a trace: ``jit_<name>``
        tower.__name__ = tower.__qualname__ = f"{kind}_tower"
        return jax.jit(jax.shard_map(
            tower, mesh=mesh, in_specs=(P(), P(data_axis)),
            out_specs=(P(data_axis), P()), check_vma=False))

    def local(variables, text_ids):
        return model.apply(variables, None, text_ids, mode="text")

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(), P(data_axis)),
        out_specs=P(data_axis), check_vma=False))
