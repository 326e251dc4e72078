"""KVStore: key-value parameter synchronization.

Parity: reference ``python/mxnet/kvstore.py`` + ``src/kvstore/``
(KVStoreLocal, CommCPU/CommDevice, KVStoreDist over ps-lite). TPU-native
redesign per SURVEY.md §5.8: the parameter-server tier is deleted —

- ``local``/``device``: single-process multi-device reduce. The reference
  reduces on pinned CPU (CommCPU) or on one GPU with P2P (CommDevice);
  here values on accelerator devices are summed where they live and XLA
  inserts the transfers (ICI on a multi-chip host).
- ``dist_sync``/``dist_device_sync``/``dist_async``: multi-process modes.
  In a multi-host JAX setup gradients sync via psum over ICI/DCN inside
  the compiled step (see mxnet_tpu.parallel); this class keeps the
  reference's worker-facing API (rank/num_workers/barrier/set_optimizer)
  so training scripts run unmodified.

The key scheduling idea the reference encodes — push/pull are async engine
ops with priority = -param_index so front layers' syncs jump the queue and
overlap the rest of the train loop (SURVEY.md §5.8 "the key scheduling
idea to preserve"; reference src/kvstore/comm.h kCPUPrioritized +
python/mxnet/kvstore.py push(priority)) — is preserved two ways:

- fused path (ShardedTrainStep): sync happens inside the compiled step;
  XLA's latency-hiding scheduler owns the overlap.
- executor path (THIS class): push/pull are scheduled on the
  communication engine (engine.comm()) with the caller's priority and a
  per-key dependency Var, so the python thread returns immediately, the
  host reduce / cross-process allreduce / optimizer update runs on comm
  workers, and the next forward only waits for the specific weights it
  reads (NDArray engine-var discipline). Cross-process ops additionally
  chain on one Var so every rank issues collectives in the same order —
  a hard correctness requirement for collective-based allreduce that the
  reference's server tier never had to face (priority therefore cannot
  reorder DIST ops, only local ones).

MXNET_KVSTORE_ASYNC=0 restores the fully synchronous path (and
MXNET_ENGINE_TYPE=NaiveEngine makes every engine synchronous, same as
the reference's debug toggle).
"""
from __future__ import annotations

import os
import pickle
import threading
import time

import numpy as _np

from . import engine as _engine
from . import ndarray as nd
from . import optimizer as opt
from . import telemetry as _tm
from .base import MXNetError, bucket_bytes_env as _env_bucket_bytes
from .ndarray import NDArray
from .resilience import fault as _fault
from .resilience import retry as _retry

_M_PUSH_BYTES = _tm.counter(
    "kvstore.push_bytes", "Bytes pushed into the kvstore")
_M_PULL_BYTES = _tm.counter(
    "kvstore.pull_bytes", "Bytes pulled out of the kvstore")
_H_PUSH_SECONDS = _tm.histogram(
    "kvstore.push_seconds", "Latency of the engine-side push body "
    "(reduce + updater), per key")
_H_PULL_SECONDS = _tm.histogram(
    "kvstore.pull_seconds", "Latency of the engine-side pull body, per key")
_H_ALLREDUCE_SECONDS = _tm.histogram(
    "kvstore.allreduce_seconds", "Cross-process allreduce+update stage "
    "latency (dist stores)")
_H_BUCKET_BYTES = _tm.histogram(
    "kvstore.bucket_bytes", "Payload bytes per coalesced gradient bucket "
    "(kvstore GradBucketer flushes and fused flat-update plan buckets)")
# same name mesh.py uses for cross-process collectives — the registry
# dedupes by name, so local reduces and gloo/jax collectives land in one
# anatomy 'collective' phase
_H_COLLECTIVE_SECONDS = _tm.histogram(
    "parallel.collective_seconds",
    "Wall time inside collective operations, by op")


def _nbytes(vals):
    return sum(int(v.size) * _np.dtype(v.dtype).itemsize for v in vals)


class _PendingPush(object):
    """One deferred dist stage-2 entry: the cross-process reduce+apply
    for a key whose local reduce (stage 1) is already in flight."""

    __slots__ = ("priority", "seq", "key", "upd_key", "box", "shape",
                 "dtype", "nbytes", "apply_fn")

    def __init__(self, priority, seq, key, upd_key, box, snap0, apply_fn):
        self.priority = priority
        self.seq = seq
        self.key = key
        self.upd_key = upd_key
        self.box = box  # filled by stage 1 on a comm worker
        self.shape = tuple(snap0.shape)
        self.dtype = _np.dtype(snap0.dtype)
        self.nbytes = int(snap0.size) * self.dtype.itemsize
        self.apply_fn = apply_fn


class GradBucketer(object):
    """Deferred-reduce queue for dist stores (tentpole part 2: bucketed,
    overlapped gradient collectives).

    The reference overlaps communication by making each key's push an
    engine op with priority=-index; our dist stage 2 additionally rides
    ONE chain var so every rank issues collectives in identical order —
    which used to mean strict CALL order, priority ignored. This class
    restores the priority discipline AND amortizes collective fixed
    cost: stage-2 entries accumulate here (caller thread, deterministic),
    and a flush (a) sorts them higher-priority-first, (b) packs them
    into size-capped same-dtype flat buckets (``MXTPU_BUCKET_BYTES``,
    default 4 MiB; 0 = one collective per key, the legacy shape), and
    (c) issues ONE collective per bucket, carving per-key views back out
    for the updater. Composition happens on the caller's thread from
    (priority, push order, shapes) alone — all ranks run the same
    script, so all ranks build identical buckets, preserving the
    lockstep collective order the chain var enforces.

    Flush triggers: accumulated bytes reach the cap; any pull (the pull
    must order after its key's deferred update); barrier / updater
    change / optimizer-state IO (quiescence points).

    Dtype-aware: buckets group by the pushed grad dtype and the byte cap
    counts ACTUAL itemsize (a bf16 model packs 2x the keys per bucket an
    fp32 model does). ``MXTPU_BUCKET_REDUCE_DTYPE=float32`` upcasts
    low-precision buckets for the cross-worker sum only — see
    _bucket_allreduce_apply."""

    def __init__(self, bucket_bytes):
        self.bucket_bytes = bucket_bytes
        self.pending = []
        self.pending_bytes = 0
        self._seq = 0

    def add(self, priority, key, upd_key, box, snap0, apply_fn):
        self.pending.append(_PendingPush(
            priority, self._seq, key, upd_key, box, snap0, apply_fn))
        self._seq += 1
        self.pending_bytes += self.pending[-1].nbytes
        return self.pending_bytes >= max(self.bucket_bytes, 1)

    def drain(self):
        """Priority-ordered (then FIFO) bucket composition; returns a
        list of same-dtype entry lists, each capped at bucket_bytes."""
        entries = self.pending
        self.pending = []
        self.pending_bytes = 0
        entries.sort(key=lambda e: (-e.priority, e.seq))
        buckets = []
        cur, cur_bytes = [], 0
        for e in entries:
            if cur and (cur[0].dtype != e.dtype
                        or cur_bytes + e.nbytes > self.bucket_bytes):
                buckets.append(cur)
                cur, cur_bytes = [], 0
            cur.append(e)
            cur_bytes += e.nbytes
        if cur:
            buckets.append(cur)
        return buckets


def _ctype_key_value(keys, vals):
    if isinstance(keys, (int, str)):
        keys = [keys]
        vals = [vals]
    out = []
    for k, v in zip(keys, vals):
        if isinstance(v, NDArray):
            v = [v]
        out.append((k, list(v)))
    return out


class KVStore(object):
    def __init__(self, kv_type="local"):
        self.type = kv_type
        self._store = {}
        self._updater = None
        self._barrier_count = 0
        self._heartbeat = None
        self._key_vars = {}  # key -> engine Var (per-key push/pull order)
        self._update_lock = threading.Lock()  # updater/store mutation
        self._dist_chain = None  # lazily: serializes cross-process ops
        self._bucketer = GradBucketer(_env_bucket_bytes())
        if os.environ.get("MXNET_KVSTORE_ASYNC", "1") == "0":
            self._comm = _engine.NaiveEngine()
        else:
            self._comm = _engine.comm()
        # Multi-process distributed rank/size come from the JAX runtime
        # itself once a dist store is requested (the env names are only
        # the pre-init fallback): trusting env alone let round-2 report
        # a size the runtime never actually had.
        self._rank = int(os.environ.get(
            "DMLC_RANK", os.environ.get("JAX_PROCESS_ID", 0)))
        self._size = int(os.environ.get(
            "DMLC_NUM_WORKER", os.environ.get("JAX_NUM_PROCESSES", 1)))
        if "dist" in kv_type:
            import jax

            from .parallel import init_distributed

            # The reference joins the PS cluster at kvstore creation
            # (KVStore::InitPSEnv); the analog is joining the JAX
            # distributed runtime here, so scripts that only ever call
            # mx.kv.create('dist_sync') work unmodified under launch.py.
            init_distributed()
            env_size = self._size
            self._rank = jax.process_index()
            self._size = jax.process_count()
            if env_size > 1 and self._size == 1:
                raise MXNetError(
                    "kvstore %s: launcher env promises %d workers but this "
                    "process never joined a distributed JAX runtime "
                    "(missing/unreachable coordinator?) — refusing to "
                    "silently train un-synchronized" % (kv_type, env_size))
            # Liveness (SURVEY §5.3): under a launcher-provided run dir,
            # heartbeat so peers/watchdogs can see this worker is alive
            # (reference: Van heartbeats to the scheduler).
            from .parallel import heartbeat as _hb

            if _hb.run_dir() is not None:
                self._heartbeat = _hb.HeartbeatWriter(
                    _hb.run_dir(), self._rank).start()

    # ------------------------------------------------------------------
    def init(self, key, value):
        for k, vals in _ctype_key_value(key, value):
            if k in self._store:
                raise MXNetError("key %s already initialized" % str(k))
            v = vals[0]
            if self._is_dist:
                # Reference dist init: rank 0's value lands on the
                # servers and every worker pulls it — all workers start
                # identical whatever their local seeding did.
                from .parallel import mesh as _mesh

                v = nd.array(_mesh.broadcast_from_root(v.asnumpy()),
                             ctx=v.context, dtype=v.dtype)
            self._store[k] = v.copy()

    def _key_var(self, k):
        var = self._key_vars.get(k)
        if var is None:
            var = self._comm.new_variable()
            self._key_vars[k] = var
        return var

    def push(self, key, value, priority=0):
        """Reduce value(s) into the store; updater applies if set.

        Parity: KVStoreLocal::Push (kvstore_local.h) — merged = sum over
        the per-device list (Comm::Reduce), then updater(key, merged,
        stored) or plain store write. The whole body is an ASYNC comm-
        engine op (write on the key's Var, priority honored for local
        stores), so the caller's thread keeps dispatching — the overlap
        the reference gets from engine-scheduled kvstore ops."""
        self._comm.raise_pending()  # surface earlier async-op failures
        if self._heartbeat is not None:
            # progress beat from the hot path: a rank wedged in a
            # collective stops marking progress even though its liveness
            # daemon keeps beating (parallel/heartbeat.py)
            self._heartbeat.progress()
        for k, vals in _ctype_key_value(key, value):
            if k not in self._store:
                raise MXNetError("key %s not initialized" % str(k))
            # Resolved on the CALLER's thread: _str_key assigns updater
            # indices in first-seen order, which must be the script's
            # deterministic push order, not the workers' race order.
            upd_key = k if isinstance(k, int) else self._str_key(k)
            # Snapshot the jax arrays now — they are immutable values, so
            # the body is immune to the trainer overwriting the grad
            # NDArrays (next backward) before the op runs.
            snap = [NDArray(v._data) for v in vals]
            if _tm.enabled():
                _M_PUSH_BYTES.inc(_nbytes(snap))

            def _apply(merged, k, upd_key):
                with self._update_lock:
                    if self._updater is not None:
                        stored = self._store[k]
                        if stored.context != merged.context:
                            # parity kvstore_local.h Push ("if merged is
                            # on gpu, we may need copy weight from cpu
                            # to gpu"): init() stores the weight where
                            # arg_params live (the host), the reduce
                            # lands on the chip; the stored weight
                            # follows it there, once, so the updater
                            # never mixes backends
                            stored = stored.as_in_context(merged.context)
                            self._store[k] = stored
                        self._updater(upd_key, merged, stored)
                    else:
                        merged.copyto(self._store[k])

            if not self._is_dist:
                # Single-process data-parallel: the cross-device reduce
                # below IS this run's collective, so it must be visible
                # to the anatomy 'collective' phase (the fleet view
                # attributes skew through it). The fault point fires on
                # the CALLER's thread and is timed into the same metric
                # — an injected delay_collective_ms therefore lands in
                # the collective phase, not smeared into dispatch.
                tc = time.perf_counter()
                _fault.fire("collective", key=k, local=True)
                _H_COLLECTIVE_SECONDS.observe(
                    time.perf_counter() - tc, op="local_reduce")

                def _do_push(snap=snap, k=k, upd_key=upd_key):
                    t0 = time.perf_counter()

                    def _reduce_body():
                        _fault.fire("kv_push", key=k)
                        return self._reduce(snap)

                    # Retry covers the reduce only — it reads immutable
                    # snapshots, so a re-run is exact. The updater is
                    # applied once, after a successful reduce (retrying
                    # through a half-applied update would double-step
                    # momentum).
                    merged = _retry.call(_reduce_body, name="kv.push")
                    _H_COLLECTIVE_SECONDS.observe(
                        time.perf_counter() - t0, op="local_reduce")
                    _apply(merged, k, upd_key)
                    _H_PUSH_SECONDS.observe(time.perf_counter() - t0)

                self._comm.push(_do_push, mutable_vars=[self._key_var(k)],
                                priority=priority, name="push:%s" % k)
                continue
            # DIST: two pipelined stages, the reference's Reduce -> server
            # push structure (kvstore_local.h Comm::Reduce, then the
            # merge_buf_ sum of kvstore_dist_server.h:163-200 minus the
            # server tier). Stage 1 (per-key var): local multi-device
            # reduce + host fetch — runs CONCURRENTLY across keys.
            # Stage 2 (key var + ONE chain var): gloo allreduce + update.
            # The chain makes every rank issue collectives in schedule
            # order — a hard correctness requirement for collective
            # allreduce (no server to absorb reordering), so priority
            # cannot reorder dist collectives; it still orders stage 1.
            # The pipeline win: key k+1's local reduce/fetch overlaps
            # key k's cross-process allreduce.
            box = {}

            def _local_reduce(snap=snap, box=box, k=k):
                try:
                    t0 = time.perf_counter()

                    def _reduce_body():
                        _fault.fire("kv_push", key=k)
                        return self._reduce(snap)

                    # Retryable: purely local, reads immutable snapshots.
                    # Stage 2's collective is NOT retried — see below.
                    merged = _retry.call(_reduce_body, name="kv.push")
                    _H_PUSH_SECONDS.observe(time.perf_counter() - t0)
                    box["host"] = merged.asnumpy()
                    box["ctx"] = merged.context
                    box["dtype"] = merged.dtype
                except BaseException as e:  # noqa: BLE001
                    # stage 2 must still ENTER the collective (peers are
                    # already committed to it — bailing here would wedge
                    # every other rank in gloo); it contributes zeros
                    # and the error surfaces on the caller's thread via
                    # raise_pending at the next kvstore call.
                    box["error"] = e
                    raise

            self._comm.push(_local_reduce,
                            mutable_vars=[self._key_var(k)],
                            priority=priority, name="reduce:%s" % k)
            # Stage 2 is DEFERRED into the bucketer (not enqueued yet):
            # later pushes can coalesce into the same collective, and the
            # drain order is priority-sorted rather than call-ordered.
            if self._bucketer.add(priority, k, upd_key, box, snap[0],
                                  _apply):
                self._flush_buckets()

    def _flush_buckets(self):
        """Drain the deferred-reduce queue: enqueue one engine op per
        coalesced bucket (priority-ordered composition — see
        GradBucketer). Runs on the caller's thread, so bucket contents
        and collective order are identical on every rank."""
        if not self._bucketer.pending:
            return
        if self._dist_chain is None:
            self._dist_chain = self._comm.new_variable()
        two_phase = os.environ.get("MXTPU_BUCKET_TWO_PHASE", "0") != "0"
        for entries in self._bucketer.drain():

            def _bucket_allreduce_apply(entries=entries,
                                        two_phase=two_phase):
                # Deliberately NO retry around this op: every rank
                # issues collectives in lockstep on the chain var, and a
                # rank re-entering an allreduce its peers already left
                # deadlocks the mesh. Collective failure is process-
                # fatal by design — recovery is watchdog restart +
                # checkpoint resume (resilience/checkpoint.py).
                import jax

                from .parallel import mesh as _mesh

                t0 = time.perf_counter()
                dtype = entries[0].dtype
                sizes = [int(_np.prod(e.shape)) if e.shape else 1
                         for e in entries]
                offsets = _np.cumsum([0] + sizes[:-1])
                flat = _np.zeros(int(sum(sizes)), dtype=dtype)
                for e, off, n in zip(entries, offsets, sizes):
                    # a failed stage 1 still contributes (zeros) to the
                    # collective — peers are already committed to it;
                    # its error surfaces via raise_pending
                    if "error" not in e.box:
                        flat[off:off + n] = e.box.pop("host").ravel()
                # MXTPU_BUCKET_REDUCE_DTYPE upcasts a low-precision
                # bucket for the SUM only (e.g. float32 accumulation of
                # bf16 grads: a W-worker sum in bf16 loses ~log2(W) of
                # bf16's 8 mantissa bits). Wire bytes go back up to the
                # accumulation width; the carve-back below re-casts each
                # key to its own dtype, so the updater sees the same
                # dtypes either way.
                rdt = os.environ.get("MXTPU_BUCKET_REDUCE_DTYPE")
                if rdt:
                    rdt = _np.dtype(rdt)
                    if rdt != dtype:
                        flat = flat.astype(rdt)
                _H_BUCKET_BYTES.observe(flat.nbytes, path="dist")
                if two_phase:
                    # explicit reduce-scatter + all-gather round trip
                    # (the sharded-update decomposition) instead of one
                    # allreduce; same bytes on a ring, but keeps the
                    # whole bucket path on the primitives the fused
                    # sharded update uses
                    nproc = jax.process_count()
                    padded = -(-flat.size // nproc) * nproc
                    buf = _np.zeros(padded, dtype=flat.dtype)
                    buf[:flat.size] = flat
                    shard = _mesh.reduce_scatter_sum(buf)
                    summed = _mesh.all_gather(shard)[:flat.size]
                else:
                    summed = _mesh.allreduce_sum(flat)
                for e, off, n in zip(entries, offsets, sizes):
                    if "error" in e.box:
                        continue
                    merged = nd.array(
                        summed[off:off + n].reshape(e.shape),
                        ctx=e.box.pop("ctx"), dtype=e.box.pop("dtype"))
                    e.apply_fn(merged, e.key, e.upd_key)
                _H_ALLREDUCE_SECONDS.observe(time.perf_counter() - t0)

            mutable = [self._dist_chain]
            seen = set()
            for e in entries:
                var = self._key_var(e.key)
                if id(var) not in seen:  # same key pushed twice
                    seen.add(id(var))
                    mutable.append(var)
            name = ("push:%s" % entries[0].key if len(entries) == 1
                    else "push_bucket:%s" % "+".join(
                        str(e.key) for e in entries))
            self._comm.push(_bucket_allreduce_apply,
                            mutable_vars=mutable,
                            priority=max(e.priority for e in entries),
                            name=name)

    def pull(self, key, out=None, priority=0):
        """Broadcast stored value to out array(s) (Comm::Broadcast).
        Async like push: reads the key's Var (so it orders after the
        in-flight push of the same key), writes the out arrays' Vars;
        any reader of those NDArrays (executor forward, asnumpy) drains
        automatically."""
        assert out is not None
        self._comm.raise_pending()
        if self._heartbeat is not None:
            self._heartbeat.progress()
        # a pull must order after its key's deferred update: drain the
        # bucketer BEFORE enqueueing (buckets mix keys, so drain all)
        self._flush_buckets()
        for k, outs in _ctype_key_value(key, out):
            if k not in self._store:
                raise MXNetError("key %s not initialized" % str(k))
            if _tm.enabled():
                _M_PULL_BYTES.inc(_nbytes(outs))

            def _do_pull(k=k, outs=outs):
                import jax

                def _body():
                    t0 = time.perf_counter()
                    _fault.fire("kv_pull", key=k)
                    stored = self._store[k]
                    for o in outs:
                        # direct _data write, NOT copyto: copyto drains
                        # the target's engine var, which is held by THIS
                        # op — calling it here would self-deadlock
                        if _tm.enabled():
                            nd._note_crossing(stored._data, o._placement)
                        o._data = jax.device_put(stored._data,
                                                 o._placement)
                    _H_PULL_SECONDS.observe(time.perf_counter() - t0)

                # device_put is idempotent (pure read of the stored
                # value, rebind of the out handle), so pulls retry whole.
                _retry.call(_body, name="kv.pull")

            out_vars = []
            seen = set()
            for o in outs:
                var = o._engine_var(self._comm)
                if id(var) not in seen:
                    seen.add(id(var))
                    out_vars.append(var)
            self._comm.push(_do_pull, const_vars=[self._key_var(k)],
                            mutable_vars=out_vars, priority=priority,
                            name="pull:%s" % k)

    def _str_key(self, k):
        """Stable string-key → updater-index mapping (insertion order;
        NOT hash(): that's randomized per process and would break
        optimizer-state save/restore)."""
        if not hasattr(self, "_str_key_map"):
            self._str_key_map = {}
        if k not in self._str_key_map:
            self._str_key_map[k] = len(self._str_key_map)
        return self._str_key_map[k]

    def _reduce(self, vals):
        if len(vals) == 1:
            return vals[0]
        # sum where the first value lives; jax moves the shards over
        # ICI/PCIe as needed (reference: CommCPU pinned-host tree /
        # CommDevice GPU gather)
        merged = vals[0].copy()
        for v in vals[1:]:
            merged += v.as_in_context(merged.context)
        return merged

    # ------------------------------------------------------------------
    def set_updater(self, updater):
        self._flush_buckets()  # deferred pushes use the old updater
        self._comm.wait_for_all()  # in-flight pushes use the old updater
        self._updater = updater

    _set_updater = set_updater

    def set_optimizer(self, optimizer):
        """Parity kvstore.py:226: on dist stores the reference pickles the
        optimizer to the servers; with the PS tier deleted the optimizer
        always runs in-process."""
        if "dist" in self.type and self._size > 1:
            # serialize/deserialize to mirror the reference's server-side
            # transport (and catch unpicklable optimizers early). The
            # bound symbol is transport-hostile (op defs hold lambdas)
            # and already spent: set_lr_mult/set_wd_mult read it at
            # construction, so the wire copy travels without it.
            import copy

            clone = copy.copy(optimizer)  # caller's object untouched
            clone.sym = None
            optimizer = pickle.loads(pickle.dumps(clone))
        self._flush_buckets()
        self._comm.wait_for_all()
        self._optimizer = optimizer
        self._updater = opt.get_updater(optimizer)

    # ------------------------------------------------------------------
    @property
    def rank(self):
        return self._rank

    @property
    def num_workers(self):
        return self._size

    @property
    def _is_dist(self):
        return "dist" in self.type and self._size > 1

    def _barrier(self):
        """Global barrier (reference: ps::Postoffice::Barrier).

        Must hard-fail if a peer is unreachable — a barrier that
        swallows errors silently un-synchronizes exactly the path that
        exists to synchronize (round-1/2 finding, fixed)."""
        if self._heartbeat is not None:
            self._heartbeat.progress()
        self._flush_buckets()  # a barrier implies the queue is drained
        self._comm.wait_for_all()  # a barrier implies local quiescence
        if self._size > 1:
            from .parallel import barrier as _mesh_barrier

            self._barrier_count += 1
            _mesh_barrier("kvstore-barrier-%d" % self._barrier_count)

    def save_optimizer_states(self, fname):
        if self._updater is None:
            raise MXNetError("Cannot save states for distributed training")
        from .resilience.checkpoint import atomic_file

        self._flush_buckets()
        self._comm.wait_for_all()  # states must include in-flight updates
        with atomic_file(fname) as fout:
            fout.write(self._updater.get_states())

    def load_optimizer_states(self, fname):
        if self._updater is None:
            raise MXNetError("Cannot load states for distributed training")
        self._flush_buckets()
        self._comm.wait_for_all()
        with open(fname, "rb") as fin:
            self._updater.set_states(fin.read())

    def get_num_dead_node(self, node_id, timeout=60):
        """Parity kvstore.h:235-244: number of peers whose heartbeat went
        stale. Heartbeats ride the launcher's run dir (parallel/
        heartbeat.py) rather than a scheduler process; outside a
        launched job there is nothing to be dead, so 0."""
        from .parallel import heartbeat as _hb

        directory = _hb.run_dir()
        if directory is None or self._size <= 1:
            return 0
        return len(_hb.dead_nodes(directory, self._size, timeout))

    @property
    def barrier_before_exit(self):
        return True


def create(name="local"):
    """Create a KVStore (parity kvstore.py create). Accepted types mirror
    the reference: local / local_allreduce_cpu / local_allreduce_device /
    device / dist_sync / dist_device_sync / dist_async."""
    if not isinstance(name, str):
        raise TypeError("name must be a string")
    valid = (
        "local", "local_allreduce_cpu", "local_allreduce_device", "device",
        "dist_sync", "dist_device_sync", "dist_async", "dist",
    )
    if name not in valid:
        raise MXNetError("unknown kvstore type %s" % name)
    return KVStore(name)
