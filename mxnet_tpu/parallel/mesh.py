"""Device-mesh utilities.

The reference's device topology handling (per-GPU engine workers, CUDA P2P
rings in CommDevice, PS key sharding across servers) collapses into one
``jax.sharding.Mesh``: ICI collectives replace P2P rings, GSPMD replaces
key sharding. Mesh axes follow the scaling-book convention:

- ``dp``: data parallel (batch dim)
- ``tp``: tensor parallel (hidden/feature dims)
- ``pp``: pipeline stages (inter-layer, the reference's ctx_group model
  parallelism)
- ``sp``: sequence/context parallel (ring attention)
"""
from __future__ import annotations

import os
import time as _time_mod

import numpy as np

from .. import telemetry as _tm

try:
    from ..resilience import fault as _fault
except ImportError:  # standalone import by path (tools helpers)
    _fault = None

_H_COLLECTIVE_SECONDS = _tm.histogram(
    "parallel.collective_seconds",
    "Host-observed latency of explicit cross-process collectives "
    "(labelled by op: barrier / allreduce_sum / broadcast)")


def device_count():
    import jax

    return jax.device_count()


#: Elastic world size (tools/watchdog.py --elastic exports it per
#: attempt): cap the mesh to the first N devices instead of all of
#: jax.devices(), so a restart after a replica loss can rebuild a
#: smaller mesh on the same host topology without a new launch config.
ENV_WORLD = "MXTPU_WORLD_SIZE"


def world_size(default=0):
    """The supervisor-imposed world size, or ``default`` when unset or
    malformed. 0 means "use every visible device"."""
    try:
        return max(0, int(os.environ.get(ENV_WORLD, default)))
    except (TypeError, ValueError):
        return max(0, int(default))


def host_count(default=1):
    """How many host processes share the input dataset — the sharding
    divisor for the streaming input pipeline's chunk shards
    (``io_pipeline``). Resolution order: ``MXTPU_NUM_HOSTS`` (explicit
    supervisor override, the host-level sibling of :data:`ENV_WORLD`),
    ``DMLC_NUM_WORKER`` (launcher convention), then
    ``jax.process_count()`` when jax is already up — never imported
    here, so a data-only process stays backend-free."""
    for name in ("MXTPU_NUM_HOSTS", "DMLC_NUM_WORKER"):
        raw = os.environ.get(name)
        if raw:
            try:
                return max(1, int(raw))
            except ValueError:
                pass
    import sys

    if "jax" in sys.modules:
        try:
            return max(1, int(sys.modules["jax"].process_count()))
        except Exception:  # noqa: BLE001 — backend not initialized yet
            pass
    return max(1, int(default))


def host_rank(default=0):
    """This process's rank within :func:`host_count` (same resolution
    order: ``MXTPU_HOST_RANK``, ``DMLC_RANK``, ``jax.process_index()``)."""
    for name in ("MXTPU_HOST_RANK", "DMLC_RANK"):
        raw = os.environ.get(name)
        if raw:
            try:
                return max(0, int(raw))
            except ValueError:
                pass
    import sys

    if "jax" in sys.modules:
        try:
            return max(0, int(sys.modules["jax"].process_index()))
        except Exception:  # noqa: BLE001
            pass
    return max(0, int(default))


def make_mesh(dp=None, tp=1, pp=1, sp=1, ep=1, devices=None):
    """Create a Mesh with axes (dp, tp, pp, sp, ep). dp defaults to
    whatever is left after tp*pp*sp*ep. With ``devices=None`` the mesh
    spans ``jax.devices()``, truncated to :data:`ENV_WORLD` when the
    supervisor imposed an elastic world size."""
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
        world = world_size()
        if world:
            devices = devices[:min(world, len(devices))]
    n = len(devices)
    if dp is None:
        assert n % (tp * pp * sp * ep) == 0, (
            "devices (%d) not divisible by tp*pp*sp*ep (%d)"
            % (n, tp * pp * sp * ep)
        )
        dp = n // (tp * pp * sp * ep)
    need = dp * tp * pp * sp * ep
    assert need <= n, "mesh %dx%dx%dx%dx%d needs %d devices, have %d" % (
        dp, tp, pp, sp, ep, need, n
    )
    dev_array = np.asarray(devices[:need]).reshape(dp, tp, pp, sp, ep)
    return Mesh(dev_array, ("dp", "tp", "pp", "sp", "ep"))


def dp_sharding(mesh):
    """Batch-sharded NamedSharding (leading axis over dp)."""
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec("dp"))


def replicated_sharding(mesh):
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec())


def barrier(tag="mxnet-tpu-barrier"):
    """Cross-PROCESS barrier (the TPU stand-in for ps::Postoffice::Barrier).

    Every process in the distributed runtime must reach this call before
    any returns — enforced by the coordination service via
    ``sync_global_devices``, which hard-fails (rather than silently
    passing) if a peer is gone. Single-process jobs return immediately:
    within one process XLA's program order already serializes."""
    import jax

    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        with _tm.span("mesh.barrier", tag=tag):
            t0 = _time_mod.perf_counter()
            multihost_utils.sync_global_devices(tag)
            _H_COLLECTIVE_SECONDS.observe(
                _time_mod.perf_counter() - t0, op="barrier")


_ALLREDUCE_CACHE = {}
_REDUCE_SCATTER_CACHE = {}
_ALL_GATHER_CACHE = {}


def _collective_preamble():
    """Shared guard for explicit host collectives: the fault-injection
    hook. Collectives are never retried (peers issue them in lockstep),
    so delay is the only injectable fault — see allreduce_sum for the
    full rationale."""
    if _fault is not None and _fault.configured():
        _fault.fire("collective")


def allreduce_sum(value):
    """Sum a host value across ALL processes; returns numpy on each.

    The explicit (non-compiled) cross-worker reduction behind KVStore
    dist push — the TPU-native replacement for the reference's
    ps::KVWorker::ZPush + server-side merge (kvstore_dist_server.h
    DataHandleEx sync path). The compiled training path never calls
    this: there gradients sync as in-step psum over ICI/DCN.

    Implemented as a real XLA reduction over a device axis spanning all
    processes — O(N) on the wire and in host memory, unlike an
    allgather-then-sum which is O(P*N) per push and would dominate at
    real model sizes. Each process stages its contribution on its first
    local device (other local devices contribute zeros), XLA sums over
    the axis, and the replicated result is read back locally."""
    import jax

    value = np.asarray(value)
    if jax.process_count() <= 1:
        return value
    # MXTPU_FAULT_INJECT delay_collective_ms: the slow/hung-peer class
    # the watchdog's progress staleness signal must catch. Collectives
    # are never retried (peers issue them in lockstep; re-entering one a
    # peer already left deadlocks the mesh), so delay is the only
    # injectable fault here.
    _collective_preamble()
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    nloc = jax.local_device_count()
    key = (value.shape, value.dtype.str, nloc)
    if key not in _ALLREDUCE_CACHE:
        mesh = Mesh(np.asarray(jax.devices()), ("proc",))
        in_sharding = NamedSharding(mesh, P("proc"))
        out_sharding = NamedSharding(mesh, P())
        fn = jax.jit(lambda x: jnp.sum(x, axis=0),
                     out_shardings=out_sharding)
        _ALLREDUCE_CACHE[key] = (in_sharding, fn)
    in_sharding, fn = _ALLREDUCE_CACHE[key]
    # exact sum: the value rides row 0, the other local rows are zeros
    with _tm.span("mesh.allreduce_sum", nbytes=value.nbytes):
        t0 = _time_mod.perf_counter()
        local = np.zeros((nloc,) + value.shape, value.dtype)
        local[0] = value
        garr = jax.make_array_from_process_local_data(in_sharding, local)
        out = np.asarray(fn(garr).addressable_data(0))
        _H_COLLECTIVE_SECONDS.observe(
            _time_mod.perf_counter() - t0, op="allreduce_sum")
    return out


def reduce_scatter_sum(value):
    """Sum a host value across ALL processes and return only THIS
    process's contiguous row-shard of the result.

    The first phase of the sharded weight update (arXiv:2004.13336, the
    ZeRO-1 pattern): instead of every worker receiving the full summed
    gradient (allreduce_sum) and redundantly applying the full optimizer
    update, each worker receives rows ``[rank*R/P, (rank+1)*R/P)`` of the
    sum, updates only that shard, and publishes it back via
    :func:`all_gather`. ``value.shape[0]`` must divide evenly by the
    process count — callers pad (kvstore.GradBucketer rounds flat
    buckets up). Single-process jobs get the whole sum back, so callers
    never special-case.

    Same staging scheme as allreduce_sum (value rides local row 0, other
    local device rows are zeros, XLA sums over the process-spanning
    device axis), but the output stays sharded over that axis so each
    process only reads back its own rows — the readback is O(N/P)
    instead of O(N)."""
    import jax

    value = np.asarray(value)
    nproc = jax.process_count()
    if nproc <= 1:
        return value
    assert value.ndim >= 1 and value.shape[0] % nproc == 0, (
        "reduce_scatter_sum: leading dim %r not divisible by %d processes"
        % (value.shape, nproc))
    _collective_preamble()
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    nloc = jax.local_device_count()
    key = (value.shape, value.dtype.str, nloc)
    if key not in _REDUCE_SCATTER_CACHE:
        mesh = Mesh(np.asarray(jax.devices()).reshape(nproc, nloc),
                    ("proc", "loc"))
        in_sharding = NamedSharding(mesh, P(("proc", "loc")))
        # sum over the staging axis; keep the result row-sharded over
        # processes so each one materializes only its own rows
        out_sharding = NamedSharding(mesh, P("proc"))
        fn = jax.jit(lambda x: jnp.sum(x, axis=0),
                     out_shardings=out_sharding)
        _REDUCE_SCATTER_CACHE[key] = (in_sharding, fn)
    in_sharding, fn = _REDUCE_SCATTER_CACHE[key]
    with _tm.span("mesh.reduce_scatter_sum", nbytes=value.nbytes):
        t0 = _time_mod.perf_counter()
        local = np.zeros((nloc,) + value.shape, value.dtype)
        local[0] = value
        garr = jax.make_array_from_process_local_data(in_sharding, local)
        out = fn(garr)
        # result is sharded over "proc" and replicated over "loc": every
        # local device holds this process's full row-shard — read one
        mine = np.asarray(out.addressable_shards[0].data)
        _H_COLLECTIVE_SECONDS.observe(
            _time_mod.perf_counter() - t0, op="reduce_scatter_sum")
    return mine


def all_gather(value):
    """Concatenate equal-shaped per-process shards along axis 0; every
    process receives the full result (inverse of reduce_scatter_sum —
    the publish phase of the sharded weight update: each worker
    contributes its updated weight shard, all receive the full vector).

    Single-process jobs return the value unchanged."""
    import jax

    value = np.asarray(value)
    nproc = jax.process_count()
    if nproc <= 1:
        return value
    _collective_preamble()
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    nloc = jax.local_device_count()
    key = (value.shape, value.dtype.str, nloc)
    if key not in _ALL_GATHER_CACHE:
        mesh = Mesh(np.asarray(jax.devices()).reshape(nproc, nloc),
                    ("proc", "loc"))
        in_sharding = NamedSharding(mesh, P(("proc", "loc")))
        out_sharding = NamedSharding(mesh, P())
        # local rows beyond row 0 are zeros; summing within each
        # process's block recovers that process's contribution exactly,
        # then blocks concatenate in process order
        def _gather(x):
            blocks = x.reshape((nproc, nloc) + value.shape)
            per_proc = jnp.sum(blocks, axis=1)  # (nproc,) + value.shape
            return per_proc.reshape((nproc * value.shape[0],)
                                    + value.shape[1:])

        fn = jax.jit(_gather, out_shardings=out_sharding)
        _ALL_GATHER_CACHE[key] = (in_sharding, fn)
    in_sharding, fn = _ALL_GATHER_CACHE[key]
    with _tm.span("mesh.all_gather", nbytes=value.nbytes):
        t0 = _time_mod.perf_counter()
        local = np.zeros((nloc,) + value.shape, value.dtype)
        local[0] = value
        garr = jax.make_array_from_process_local_data(in_sharding, local)
        out = np.asarray(fn(garr).addressable_data(0))
        _H_COLLECTIVE_SECONDS.observe(
            _time_mod.perf_counter() - t0, op="all_gather")
    return out


def broadcast_from_root(value):
    """Broadcast a host value from process 0 to every process.

    KVStore dist init semantics: the reference's kv.init writes rank 0's
    value to the servers and every worker pulls it, so all workers start
    from identical weights regardless of local seeding."""
    import jax

    value = np.asarray(value)
    if jax.process_count() <= 1:
        return value
    from jax.experimental import multihost_utils

    t0 = _time_mod.perf_counter()
    out = np.asarray(multihost_utils.broadcast_one_to_all(value))
    _H_COLLECTIVE_SECONDS.observe(
        _time_mod.perf_counter() - t0, op="broadcast")
    return out


def init_distributed(coordinator_address=None, num_processes=None,
                     process_id=None):
    """Join the multi-host JAX runtime (the worker-side counterpart of
    tools/launch.py — the TPU replacement for the reference's
    DMLC_PS_ROOT_URI bootstrap, kvstore.h InitPSEnv).

    Reads JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID
    (as set by tools/launch.py) when args are omitted; a single-process
    job is a no-op. Safe to call twice.
    """
    import os

    import jax

    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS")
    num_processes = int(num_processes or os.environ.get(
        "JAX_NUM_PROCESSES", 1))
    process_id = int(process_id if process_id is not None
                     else os.environ.get("JAX_PROCESS_ID", 0))
    if num_processes <= 1 or coordinator_address is None:
        return False
    if jax.distributed.is_initialized():
        return True
    # The CPU backend needs an explicit collectives implementation
    # for cross-process psum/allgather (without it they silently
    # reduce over local devices only — tested, not hypothetical).
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes, process_id=process_id)
    except RuntimeError as e:
        # a second call raises "distributed.initialize should only be
        # called once."
        if "once" in str(e).lower():
            return True
        raise
    return True
