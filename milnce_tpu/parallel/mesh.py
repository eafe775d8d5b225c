"""Device mesh + multi-host bootstrap.

TPU-native replacement for the reference's distributed runtime
(main_distributed.py:35-75, train.py:37-66): no UDP self-IP discovery, no
hardcoded node IP lists, no per-GPU ``mp.spawn`` — one process per host
calls :func:`initialize_distributed` (a thin wrapper over
``jax.distributed.initialize``) and every chip joins a named
``jax.sharding.Mesh``.  Collectives ride ICI within a slice and DCN
across slices; the GSPMD partitioner places them — there is no backend
flag to pick (the reference's ``--dist-backend nccl``, args.py:46).
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from milnce_tpu.config import ParallelConfig


def _multihost_tpu_env() -> bool:
    """True on a multi-host Cloud TPU slice: more than one worker in the
    TPU runtime's worker list means this process must join a
    jax.distributed cluster before touching devices.

    Decided from the environment ALONE (``TPU_WORKER_HOSTNAMES``, which
    a pod VM's TPU env file exports).  Asking the instance metadata
    instead is an HTTP lookup that jax retries six times with 60 s
    limits: on a host with no metadata server a single-host run would
    stall there before it ever touched a device, and it must reach its
    first device in seconds.  A pod process started from a shell that
    has not sourced the env file passes ``coordinator_address``."""
    return "," in os.environ.get("TPU_WORKER_HOSTNAMES", "")


def initialize_distributed(cfg: ParallelConfig) -> None:
    """Multi-host process bootstrap.

    - ``platform`` set: pin the jax backend first — 'cpu' for hermetic
      runs on accelerator hosts, 'tpu' so that JAX itself refuses to
      start where there is no chip;
    - explicit ``coordinator_address``: classic bring-up (any platform);
    - no address but a multi-host TPU slice in the environment: bare
      ``jax.distributed.initialize()`` — coordinator, process count and
      id all come from the TPU runtime, zero flags (contrast the
      reference's hand-maintained 10-IP list, train.py:48);
    - single host: no-op, ``jax.devices()`` already sees every chip.
    """
    if cfg.platform:
        jax.config.update("jax_platforms", cfg.platform)
    if cfg.coordinator_address:
        jax.distributed.initialize(
            coordinator_address=cfg.coordinator_address,
            num_processes=cfg.num_processes,
            process_id=cfg.process_id,
        )
    elif cfg.platform and cfg.platform != "tpu":
        # pinned off the TPU: a hermetic single-process run on an
        # accelerator host must NOT auto-join the pod's jax.distributed
        # cluster (it would block at the coordinator barrier waiting for
        # workers that were never launched)
        pass
    elif _multihost_tpu_env():
        jax.distributed.initialize()


def build_mesh(cfg: ParallelConfig,
               devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """1-D data mesh by default; optional trailing model axis when
    ``model_parallel_size > 1`` (S3D is small — DP is the workhorse, as in
    the reference, SURVEY.md §2.3 — but the mesh is ready for TP)."""
    devs = np.asarray(devices if devices is not None else jax.devices())
    if cfg.model_axis and cfg.model_parallel_size > 1:
        assert devs.size % cfg.model_parallel_size == 0
        grid = devs.reshape(-1, cfg.model_parallel_size)
        return Mesh(grid, (cfg.data_axis, cfg.model_axis))
    return Mesh(devs, (cfg.data_axis,))


def describe_devices(mesh: Mesh) -> str:
    """Platform, device kind and count of the devices a mesh is built
    over — the first log line of the trainer and of the server, so that
    a run that landed on the CPU says so where a reader looks first."""
    dev = mesh.devices.flat[0]
    return (f"platform={dev.platform} device_kind={dev.device_kind!r} "
            f"devices={mesh.devices.size}")


def batch_sharding(mesh: Mesh, axis: str = "data") -> NamedSharding:
    """Shard the leading (batch) dim over the data axis."""
    return NamedSharding(mesh, P(axis))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def make_flag_reducer(mesh: Mesh, overlap: bool = False):
    """Cluster-wide OR of per-process boolean flags (e.g. "I received
    SIGTERM"): each process contributes one element per local device of
    a mesh-sharded vector; the jitted sum is a collective every worker
    executes identically, so all of them see the same answer at the same
    step — the primitive behind cooperative preemption (one worker
    exiting unilaterally would wedge the rest inside their next
    collective).

    The reduction program is AOT-compiled here (compilation is pure XLA,
    no communicator setup), so callers that need to align processes
    before the first collective executes (Gloo CPU transports have a
    hard 30 s setup timeout) can barrier between building and first use.

    ``overlap=False`` (default): each call blocks the host on
    ``float(reduce(f))`` — the verdict reflects the flags passed to THIS
    call, at the cost of stalling the async-dispatch pipeline at every
    sync boundary (ADVICE r4).  ``overlap=True`` pipelines instead: each
    call enqueues this boundary's reduction and returns the PREVIOUS
    boundary's verdict (False on the first call), so the host never
    waits on an unfinished collective — detection latency grows by one
    boundary (worst case 2 x preempt_sync_steps steps; budget the grace
    window accordingly).  Both modes are cluster-uniform: every process
    runs the same sequence, so all see the same verdict at the same
    boundary."""
    import jax.numpy as jnp

    sharding = NamedSharding(mesh, P(mesh.axis_names))
    reduce = jax.jit(lambda f: f.sum()).lower(
        jax.ShapeDtypeStruct((jax.device_count(),), jnp.float32,
                             sharding=sharding)).compile()
    pending = []                        # overlap mode: last enqueued result

    def any_flagged(local_flag: bool) -> bool:
        per_dev = np.full((jax.local_device_count(),), float(local_flag),
                          np.float32)
        f = jax.make_array_from_process_local_data(sharding, per_dev)
        if not overlap:
            return float(reduce(f)) > 0.0
        out = reduce(f)                 # enqueue; don't materialize yet
        verdict = float(pending.pop()) > 0.0 if pending else False
        pending.append(out)
        return verdict

    return any_flagged


def broadcast_str(value: str, max_len: int = 64) -> str:
    """Every process returns PROCESS 0's ``value`` (utf-8, truncated to
    ``max_len`` bytes).  The cluster-uniform run-id primitive: the obs
    run context must carry ONE id across a pod (aggregation refuses a
    mixed-run merge), and per-process clocks/pids can't produce that.
    One-time init cost, before the steady-state transfer guard arms;
    single-process is a pass-through."""
    if jax.process_count() == 1:
        return value
    from jax.experimental import multihost_utils

    buf = np.zeros((max_len,), np.uint8)
    raw = value.encode("utf-8")[:max_len]
    buf[:len(raw)] = np.frombuffer(raw, np.uint8)
    out = np.asarray(multihost_utils.broadcast_one_to_all(buf))
    return bytes(out[out != 0]).decode("utf-8")


def replicate_to_mesh(tree, mesh: Mesh):
    """Re-replicate host-local arrays (e.g. an Orbax restore committed to
    one device) over a possibly MULTI-HOST mesh.

    ``jax.device_put(x, NamedSharding(mesh, P()))`` raises on multi-host
    CPU/TPU backends without DCN transfer flags ("does not support
    cross-host device transfers") — but a replicated target needs no
    transfer at all: every process already holds the full value, so the
    global array is assembled from process-local data.  Single-process
    keeps the plain device_put fast path.  (Found by the 4-process
    cluster test resuming a checkpoint — tests/test_multihost.py.)"""
    sh = NamedSharding(mesh, P())
    if jax.process_count() == 1:
        return jax.device_put(tree, sh)
    return jax.tree_util.tree_map(
        lambda x: jax.make_array_from_process_local_data(
            sh, np.asarray(x)), tree)


def shard_batch(mesh: Mesh, batch, axis: str = "data"):
    """Device-put a host batch (pytree of arrays) sharded on dim 0."""
    sh = NamedSharding(mesh, P(axis))
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh), batch)
