"""Sequence-parallel soft-DTW: the DP wavefront sharded over the mesh.

The reference caps soft-DTW at sequence length 1024 (CUDA block limit,
soft_dtw_cuda.py:318-320) and runs one GPU per pair.  The single-chip
Pallas kernel (softdtw_pallas.py) already removes the cap; this module
removes the single-CHIP limit: the anti-diagonal wavefront itself is
distributed over the mesh, so one alignment's memory and per-diagonal
compute scale 1/P with the device count — soft-DTW as a first-class
long-context primitive (SURVEY §5 long-context note).

Decomposition (row-sharded wavefront):

- the (B, N, M) cost matrix is sharded over N (device p owns rows
  [p*K, (p+1)*K));
- the DP recurrence R[i, j] = D[i-1, j-1] + softmin(R[i-1, j-1],
  R[i-1, j], R[i, j-1]) walks anti-diagonals exactly like the scan
  golden (softdtw.py:52-91), but each diagonal is now a DISTRIBUTED
  vector sharded the same way;
- the only cross-device dependency is the ``i-1`` shift: each step,
  every device sends its LAST row's value to its right neighbor — one
  (B, 2) ``ppermute`` over ICI per diagonal (the halo exchange);
- the final R[N, M] lives on one device and is ``psum``-broadcast.

The backward pass is plain JAX AD: ``ppermute``/``scan``/``where`` all
have transpose rules, so ``jax.grad`` of a shard_map'ed call yields the
sharded E-matrix gradient with the reverse halo exchange inserted by
XLA — no hand-written VJP needed (the reference hand-codes its backward
kernel, soft_dtw_cuda.py:79-112).

Wall-clock per diagonal is O(N/P) vector work + one ICI hop, N+M-1
diagonals total.  For the alignment shapes this framework trains on,
the single-chip kernels are faster (no per-step collective); use this
when one sequence's DP table outgrows a chip — lengths of 10^5+ frames.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from milnce_tpu.ops.softdtw import (BIG, check_bandwidth, skew_cost,
                                    softmin3)


def _softdtw_sp_local(D_local: jax.Array, n: int, m: int, gamma,
                      axis_name: str, bandwidth: int = 0) -> jax.Array:
    """Shard-local body (call inside shard_map; D row-sharded on dim 1).

    Returns the (B,) soft-DTW values, identical on every shard."""
    bsz, k, _ = D_local.shape
    p_count = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    row_offset = idx * k                       # global D-row of local row 0
    g_rows = row_offset + jnp.arange(k)        # global D-row ids (= i-1)
    gamma = jnp.asarray(gamma, D_local.dtype)

    n_diags = n + m - 1
    d_skew = skew_cost(D_local, n_diags, row_offset)       # (B, Q, K)

    fwd_perm = [(s, s + 1) for s in range(p_count - 1)]

    def shift_in(x, fill):
        """y[r] = x[r-1] with the left neighbor's last row crossing the
        shard boundary; device 0's row 0 gets scalar `fill` (the i=0
        border)."""
        recv = lax.ppermute(x[:, -1:], axis_name, fwd_perm)   # (B, 1)
        first = jnp.where(idx == 0, jnp.broadcast_to(fill, recv.shape), recv)
        return jnp.concatenate([first, x[:, :-1]], axis=1)

    # Buffers hold interior rows only (buffer row r <-> padded DP row
    # i = g_rows[r] + 1); the i=0 border row is synthesized by shift_in.
    init = jnp.full((bsz, k), BIG, D_local.dtype)

    def step(carry, inputs):
        r_mm, r_m = carry                      # diagonals p-2, p-1
        cost_row, p = inputs
        # R[0, j] on diag p-2 is R[0, p-2]: 0 iff p == 2, else BIG
        fill_mm = jnp.where(p == 2, 0.0, BIG).astype(D_local.dtype)
        prev_diag = shift_in(r_mm, fill_mm)
        prev_up = shift_in(r_m, jnp.asarray(BIG, D_local.dtype))
        prev_left = r_m
        interior = cost_row + softmin3(prev_diag, prev_up, prev_left, gamma)
        i_glob = g_rows[None, :] + 1
        j_glob = p - i_glob
        valid = (j_glob >= 1) & (j_glob <= m) & (i_glob <= n)
        if bandwidth > 0:                      # soft_dtw_cuda.py:66
            valid &= jnp.abs(i_glob - j_glob) <= bandwidth
        r_new = jnp.where(valid, interior, BIG)
        return (r_m, r_new), None

    diag_ids = jnp.arange(2, n + m + 1)
    (_, r_last), _ = lax.scan(step, (init, init),
                              (d_skew.transpose(1, 0, 2), diag_ids))

    # R[N, M] sits at buffer row with g_rows == N-1 on one device
    local_val = jnp.sum(jnp.where(g_rows[None, :] == n - 1, r_last, 0.0),
                        axis=1)
    return lax.psum(local_val, axis_name)


@functools.lru_cache(maxsize=32)
def _build_sp_fn(mesh: Mesh, axis_name: str, n: int, m: int,
                 bandwidth: int):
    """One jitted distributed-scan program per (mesh, shape, bandwidth);
    gamma stays a traced argument so sweeping it never recompiles."""

    def local(D_local, gamma):
        return _softdtw_sp_local(D_local, n=n, m=m, gamma=gamma,
                                 axis_name=axis_name, bandwidth=bandwidth)

    return jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, axis_name, None), P()),
        out_specs=P(), check_vma=False))


def softdtw_seq_parallel(D: jax.Array, gamma: float, mesh: Mesh,
                         axis_name: str = "data",
                         bandwidth: int = 0) -> jax.Array:
    """Distributed soft-DTW of (B, N, M) costs over ``mesh[axis_name]``.

    Rows are padded to a multiple of the axis size and sharded; returns
    (B,) replicated values.  Differentiable (plain JAX AD through the
    shard_map program).  Computes and returns float32 regardless of the
    input dtype: the BIG-sentinel border arithmetic needs f32 range
    (bfloat16 saturates), unlike the in-dtype scan golden."""
    bsz, n, m = D.shape
    check_bandwidth(n, m, int(bandwidth))
    p_count = mesh.shape[axis_name]
    k = -(-n // p_count)
    D_pad = jnp.pad(D.astype(jnp.float32), ((0, 0), (0, k * p_count - n),
                                            (0, 0)))
    fn = _build_sp_fn(mesh, axis_name, n, m, int(bandwidth))
    return fn(jax.device_put(
        D_pad, NamedSharding(mesh, P(None, axis_name, None))),
        jnp.float32(gamma))
