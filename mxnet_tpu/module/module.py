"""Module: the primary trainer over one Symbol.

Parity: reference ``python/mxnet/module/module.py`` (708 LoC) — bind
creates a DataParallelExecutorGroup (module.py:381), init_optimizer decides
the kvstore routing (module.py:457-502), update() routes to
_update_params_on_kvstore / _update_params (module.py:553).
"""
from __future__ import annotations

import logging
import time

import numpy as np

from .. import context as ctx_mod
from .. import ndarray as nd
from .. import optimizer as opt
from .. import telemetry as _tm
from ..base import MXNetError
from ..initializer import Uniform, InitDesc
from ..model import (
    _create_kvstore, _initialize_kvstore, _update_params,
    _update_params_on_kvstore, load_checkpoint, save_checkpoint,
)
from .base_module import BaseModule, _check_input_names
from .executor_group import DataParallelExecutorGroup

_H_DISPATCH_HOST = _tm.histogram(
    "module.dispatch_host_seconds",
    "Host wall time to stage inputs + enqueue one fused train step "
    "(the dispatch returns before the device finishes, so this is the "
    "pure per-step host overhead — executor.step_seconds' host "
    "component)")
_H_STAGE_HOST = _tm.histogram(
    "module.stage_host_seconds",
    "Host wall time of the input-STAGING slice of a fused step "
    "(asnumpy + device_put, or the DeviceFeedIter adoption check) — "
    "the component the device-resident feed removes. Kept separate "
    "from dispatch_host_seconds because on the CPU backend the enqueue "
    "itself blocks on donated in-flight buffers (a jax CPU-client "
    "artifact the TPU runtime does not have)")
_M_FEED_HITS = _tm.counter(
    "module.feed_fastpath_hits",
    "Fused-step input arrays adopted directly from a DeviceFeedIter "
    "staging (sharding matched: no asnumpy sync, no per-step "
    "device_put)")
_H_OUTPUT_SYNC = _tm.histogram(
    "module.output_sync_seconds",
    "Host wall time of the metric's update over a fused step's outputs "
    "(update_metric / deferred metric drain): the blocking fetch inside "
    "eval_metric.update and the metric's arithmetic. Under the async "
    "pipeline this is where device compute surfaces on the host thread "
    "— the device-sync leg of the step anatomy (telemetry/anatomy.py)")


def _local_rows(arr):
    """This process's rows of a (possibly multi-process) jax.Array.
    Single-process arrays pass through untouched; for a process-spanning
    mesh each worker's outputs/metrics cover its own data shard
    (reference dist semantics: per-worker metric over the worker's
    partition). Replicated (incl. 0-d) outputs come back as one copy,
    not one per local device."""
    if getattr(arr, "is_fully_addressable", True):
        return arr
    import numpy as _np

    if arr.is_fully_replicated or arr.ndim == 0:
        return _np.asarray(arr.addressable_shards[0].data)
    # batch-sharded: dedupe by shard index (a device may replicate a
    # slice other local devices already hold), then stitch in row order
    by_index = {}
    for s in arr.addressable_shards:
        key = tuple((sl.start, sl.stop) for sl in s.index)
        by_index.setdefault(key, s.data)
    ordered = sorted(by_index.items(),
                     key=lambda kv: (kv[0][0][0] or 0) if kv[0] else 0)
    return _np.concatenate([_np.asarray(d) for _, d in ordered], axis=0)


class Module(BaseModule):
    def __init__(self, symbol, data_names=("data",),
                 label_names=("softmax_label",), logger=logging,
                 context=None, work_load_list=None, fixed_param_names=None,
                 mesh=None, param_specs=None):
        """``mesh``/``param_specs`` extend the reference surface for the
        fused path: pass a multi-axis jax Mesh (dp x tp x ...) and
        per-param PartitionSpecs and the whole train step compiles over
        it — tensor parallelism through the same Module.fit the
        reference drives with ctx lists (SURVEY §2.3: TP is the
        "for free via GSPMD" row)."""
        super().__init__(logger=logger)
        if context is None:
            context = [ctx_mod.current_context()]
        if isinstance(context, ctx_mod.Context):
            context = [context]
        self._context = context
        if mesh is not None and "dp" not in mesh.axis_names:
            raise MXNetError(
                "Module mesh must have a 'dp' axis (the batch dimension "
                "shards over it); got axes %s" % (mesh.axis_names,))
        self._mesh = mesh
        self._param_specs = param_specs
        if work_load_list is None:
            work_load_list = [1] * len(self._context)
        assert len(work_load_list) == len(self._context)
        self._work_load_list = work_load_list

        self._symbol = symbol
        data_names = list(data_names) if data_names is not None else []
        label_names = list(label_names) if label_names is not None else []
        arg_names = symbol.list_arguments()
        input_names = data_names + label_names
        self._param_names = [x for x in arg_names if x not in input_names]
        self._fixed_param_names = list(fixed_param_names or [])
        self._aux_names = symbol.list_auxiliary_states()
        self._data_names = data_names
        self._label_names = label_names
        self._state_names = []
        self._output_names = symbol.list_outputs()
        _check_input_names(symbol, data_names, "data", True)
        _check_input_names(symbol, label_names, "label", False)
        _check_input_names(symbol, self._fixed_param_names, "fixed_param", True)

        self._arg_params = None
        self._aux_params = None
        self._params_dirty = False
        self._optimizer = None
        self._kvstore = None
        self._update_on_kvstore = None
        self._updater = None
        self._preload_opt_states = None
        self._exec_group = None
        self._data_shapes = None
        self._label_shapes = None
        # fused mesh path (kvstore 'device'/'dist_device_sync'): the whole
        # train step — fwd, bwd, psum grad sync, optimizer — is ONE XLA
        # program over a dp Mesh (ShardedTrainStep), replacing the
        # per-device executor + kvstore push/pull hot loop.
        self._fused_trainer = None
        self._fused_owner = None  # module owning the sharded state dicts
        self._fused_params = None
        self._fused_aux = None
        self._fused_opt = None
        self._fused_batch = None
        self._fused_outputs = None
        self._fused_outs_raw = None
        self._monitor = None
        self._fused_t = 0

    @property
    def _exec_params_stale(self):
        """The executor group's weights are out of date (the truth is
        the fused state, else ``_arg_params``): ``_ensure_exec_params``
        fills them before an executor runs. Kept with the arrays, so
        modules that share them share it (``executor_group._Weights``)."""
        return self._exec_group.weights.stale

    @_exec_params_stale.setter
    def _exec_params_stale(self, value):
        self._exec_group.weights.stale = value

    # ------------------------------------------------------------------
    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        """Parity module.py:97."""
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params = args
        mod._aux_params = auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = "%s-%04d.states" % (prefix, epoch)
        return mod

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        """Parity module.py:127. Every file goes through the atomic
        writer (via save_params / save_optimizer_states) so a crash
        mid-save never leaves a truncated artifact in place."""
        from ..resilience.checkpoint import atomic_file

        with atomic_file("%s-symbol.json" % prefix, mode="w") as f:
            f.write(self._symbol.tojson())
        param_name = "%s-%04d.params" % (prefix, epoch)
        self.save_params(param_name)
        logging.info("Saved checkpoint to \"%s\"", param_name)
        if save_optimizer_states:
            state_name = "%s-%04d.states" % (prefix, epoch)
            self.save_optimizer_states(state_name)
            logging.info("Saved optimizer state to \"%s\"", state_name)

    # ------------------------------------------------------------------
    def _reset_bind(self):
        self.binded = False
        self._exec_group = None
        self._data_shapes = None
        self._label_shapes = None
        # a rebind invalidates the compiled fused trainer (shapes/mesh may
        # change, and a monitor installed on the new bind needs the per-op
        # executor path); optimizer + its state survive, reference-style
        self._fused_trainer = None
        self._fused_owner = None
        self._fused_batch = None
        self._fused_outputs = None
        self._fused_outs_raw = None

    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        return self._exec_group.get_output_shapes()

    # ------------------------------------------------------------------
    def get_params(self):
        """Parity module.py:204."""
        assert self.binded and self.params_initialized
        if self._params_dirty:
            self._sync_params_from_devices()
        return (self._arg_params, self._aux_params)

    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False):
        """Parity module.py:227 — per-name initializer dispatch."""
        if self.params_initialized and not force_init:
            return
        assert self.binded, "call bind before initializing the parameters"
        # a forced refill (set_params at an epoch's end) is no set-up
        with (_tm.NULL_SPAN if self.params_initialized
              else _tm.span("module.init_params")):
            self._init_params(initializer, arg_params, aux_params,
                              allow_missing)

    def _init_params(self, initializer, arg_params, aux_params,
                     allow_missing):
        def _impl(name, arr, cache):
            if cache is not None:
                if name in cache:
                    cache_arr = cache[name]
                    if cache_arr is not arr:
                        cache_arr.copyto(arr)
                else:
                    if not allow_missing:
                        raise RuntimeError("%s is not presented" % name)
                    if initializer is not None:
                        initializer(name, arr)
            else:
                if initializer is not None:
                    desc = InitDesc(name, attrs=self._arg_attrs.get(name, {}))
                    initializer(desc, arr)

        self._arg_attrs = self._symbol.attr_dict()
        attrs = self._arg_attrs
        if self._arg_params is None:
            # the initializer writes each whole: no zeros are made first
            param_arrays = [nd.deferred_full(x[0].shape, 0, dtype=x[0].dtype)
                            for x in self._exec_group.param_arrays]
            self._arg_params = dict(zip(self._param_names, param_arrays))
        if self._aux_params is None:
            aux_arrays = [nd.deferred_full(x[0].shape, 0, dtype=x[0].dtype)
                          for x in self._exec_group.aux_arrays]
            self._aux_params = dict(zip(self._aux_names, aux_arrays))
        for name, arr in self._arg_params.items():
            _impl(name, arr, arg_params)
        for name, arr in self._aux_params.items():
            _impl(name, arr, aux_params)
        self.params_initialized = True
        self._params_dirty = False
        # the executor group gets them when an executor first runs
        # (_ensure_exec_params): a fused fit that never evaluates
        # through the executors never sends them
        self._outdate_exec_params()

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        """Parity module.py:323."""
        if force_rebind:
            self._reset_bind()
        if self.binded:
            self.logger.warning("Already binded, ignoring bind()")
            return
        with _tm.span("module.bind"):
            self._bind(data_shapes, label_shapes, for_training,
                       inputs_need_grad, shared_module, grad_req)

    def _bind(self, data_shapes, label_shapes, for_training,
              inputs_need_grad, shared_module, grad_req):
        """Shape and type inference, the executor group and its arrays."""
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.binded = True
        if not for_training:
            assert not inputs_need_grad

        self._data_shapes = [
            x if isinstance(x, tuple) else tuple(x) for x in data_shapes
        ]
        if label_shapes is not None and len(label_shapes) > 0:
            self._label_shapes = [
                x if isinstance(x, tuple) else tuple(x) for x in label_shapes
            ]
        else:
            self._label_shapes = None

        if shared_module is not None:
            assert isinstance(shared_module, Module) and shared_module.binded \
                and shared_module.params_initialized
            shared_group = shared_module._exec_group
        else:
            shared_group = None
        self._exec_group = DataParallelExecutorGroup(
            self._symbol, self._context, self._work_load_list,
            self._data_shapes, self._label_shapes, self._param_names,
            for_training, inputs_need_grad, shared_group, logger=self.logger,
            fixed_param_names=self._fixed_param_names, grad_req=grad_req
        )
        self._total_exec_bytes = 0
        if shared_module is not None:
            self.params_initialized = True
            self._arg_params = shared_module._arg_params
            self._aux_params = shared_module._aux_params
        elif self.params_initialized:
            self._exec_params_stale = True
        if shared_module is not None and shared_module.optimizer_initialized:
            self.borrow_optimizer(shared_module)

    def reshape(self, data_shapes, label_shapes=None):
        """Parity module.py:403. The reference's reshape re-binds
        executors SHARING memory, so weights survive; here rebinding
        allocates fresh executors, so the current weights must be carried
        across explicitly (found by the GAN example: reshaping the
        trained generator for a larger sample batch silently zeroed
        it)."""
        assert self.binded
        if (data_shapes == self._data_shapes
                and label_shapes == self._label_shapes):
            return  # no-op, like exec_group.reshape — skip the transfers
        if self.params_initialized:
            # device truth -> host unconditionally: when this module was
            # bound with shared_module=, the TRAINED values live in the
            # shared device arrays while our host dict may be a stale
            # init snapshot and our own _params_dirty never flipped
            self._sync_params_from_devices()
        self._data_shapes = data_shapes
        self._label_shapes = label_shapes
        self._exec_group.reshape(self._data_shapes, self._label_shapes)
        if self.params_initialized:
            self._exec_group.set_params(self._arg_params, self._aux_params)
            # fresh executors with fresh weights: the next fused update
            # releases them again
            self._exec_params_stale = False

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        """Parity module.py:432 — decides update_on_kvstore routing."""
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring...")
            return
        with _tm.span("module.init_optimizer"):
            self._init_optimizer(kvstore, optimizer, optimizer_params)

    def _init_optimizer(self, kvstore, optimizer, optimizer_params):
        # an explicit mesh IS the device set: its size (not the ctx list,
        # which only hosts the eval executors) decides whether a kvstore
        # is needed at all (reference model.py:40 drops it for 1 device).
        # With a mesh the request is explicit, so even a dp=1 mesh keeps
        # its kvstore (dropping it would bounce the user off the fused
        # path they asked for, with a misleading error).
        if self._mesh is not None and isinstance(kvstore, str):
            from ..kvstore import create as kv_create

            kvstore = kv_create(kvstore)
        (kvstore, update_on_kvstore) = _create_kvstore(
            kvstore, len(self._context), self._arg_params
        )
        batch_size = self._exec_group.batch_size
        if kvstore and "dist" in kvstore.type and "_sync" in kvstore.type:
            batch_size *= kvstore.num_workers
        rescale_grad = 1.0 / batch_size

        if isinstance(optimizer, str):
            idx2name = {}
            if update_on_kvstore:
                idx2name.update(enumerate(self._exec_group.param_names))
            else:
                for k in range(len(self._context)):
                    idx2name.update(
                        {
                            i * len(self._context) + k: n
                            for i, n in enumerate(self._exec_group.param_names)
                        }
                    )
            optimizer_params = dict(optimizer_params)
            if "rescale_grad" not in optimizer_params:
                optimizer_params["rescale_grad"] = rescale_grad
            optimizer = opt.create(
                optimizer, sym=self.symbol, param_idx2name=idx2name,
                **optimizer_params
            )
        else:
            assert isinstance(optimizer, opt.Optimizer)

        self._optimizer = optimizer
        self._kvstore = kvstore
        self._update_on_kvstore = update_on_kvstore
        self._updater = None
        fused = self._fusable(kvstore)
        if not fused:
            # training runs the executors: they get their weights now,
            # and the kvstore's pull below lands on them as the
            # reference's does (rank 0's values over each worker's own)
            self._ensure_exec_params()
        if kvstore:
            _initialize_kvstore(
                kvstore=kvstore, param_arrays=self._exec_group.param_arrays,
                arg_params=self._arg_params,
                param_names=self._param_names,
                # the fused step never reads the executor group: nothing
                # is pulled into it (_ensure_exec_params fills it from
                # the fused state if an executor ever runs)
                update_on_kvstore=update_on_kvstore and not fused
            )
        if update_on_kvstore:
            kvstore.set_optimizer(self._optimizer)
        else:
            self._updater = opt.get_updater(optimizer)
        from ..parallel.train_step import amp_requested

        if fused:
            self._init_fused()
        elif self._mesh is not None:
            # the user explicitly asked for a mesh; quietly training
            # single-device instead would be a silent wrong answer
            raise MXNetError(
                "Module was given a mesh but training cannot take the "
                "fused path: requires kvstore 'device'/'dist_device_sync' "
                "(got %r), for_training, no inputs_need_grad, no "
                "fixed_param_names, no installed monitor (monitored "
                "training needs the per-op executor path), and "
                "batch_size %% dp == 0"
                % (getattr(kvstore, "type", kvstore),))
        elif amp_requested():
            # the per-key executor path never reads MXTPU_AMP: training
            # fp32 under a bf16 request would be a silent wrong answer
            raise MXNetError(
                "MXTPU_AMP=bf16 cannot engage: training takes the "
                "per-key executor path here (kvstore %r, %d device(s)); "
                "AMP lives on the fused flat-update path (kvstore "
                "'device', dp>1)"
                % (getattr(kvstore, "type", kvstore), len(self._context)))
        self.optimizer_initialized = True
        if self._preload_opt_states is not None:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None

    # -- fused mesh path ------------------------------------------------
    def _fusable(self, kvstore):
        """kvstore 'device'/'dist_device_sync' routes training through the
        fused ShardedTrainStep (SURVEY §5.8: device-side reduce ≡ in-XLA
        allreduce over the mesh). The executor-group path remains for
        inference, input grads, and the 'local' kvstore."""
        if self._mesh is not None:
            dp = self._mesh.shape.get("dp", 1)
        elif (kvstore is not None and "dist" in kvstore.type
                and kvstore.num_workers > 1):
            # multiworker fused mesh spans jax.devices(); this process
            # contributes its LOCAL batch rows across its LOCAL devices,
            # so that is the divisibility that must hold (mirrors the
            # shape contract of make_array_from_process_local_data)
            import jax

            dp = jax.local_device_count()
        else:
            dp = len(self._context)
        return (
            kvstore is not None
            and "device" in kvstore.type
            and self.for_training
            and not self.inputs_need_grad
            and not self._fixed_param_names
            # Monitor needs per-op executor callbacks; the fused
            # whole-graph program has none, so monitored training keeps
            # the reference's per-op executor path
            and self._monitor is None
            and self._exec_group.batch_size % dp == 0
        )

    def _init_fused(self):
        import jax
        from jax.sharding import Mesh

        from ..parallel.train_step import ShardedTrainStep

        multiworker = (self._kvstore is not None
                       and "dist" in self._kvstore.type
                       and self._kvstore.num_workers > 1)
        if self._mesh is not None:
            mesh = self._mesh
            if multiworker:
                procs = {d.process_index for d in mesh.devices.flat}
                if len(procs) < jax.process_count():
                    # a process-local mesh would psum only locally and
                    # the workers would silently train unsynchronized
                    raise MXNetError(
                        "dist kvstore %r with a mesh spanning %d of %d "
                        "processes: the fused step's gradient psum would "
                        "skip the other workers. Build the mesh from "
                        "jax.devices() (all processes), or drop the "
                        "explicit mesh." % (self._kvstore.type,
                                            len(procs),
                                            jax.process_count()))
        elif multiworker:
            # dist fused path MUST span every process's devices (found
            # by the fault-recovery test: with a local mesh a dead peer
            # did not even stall the survivor). Reference semantics:
            # dist_device_sync reduces across ALL workers every step.
            mesh = Mesh(np.asarray(jax.devices()), ("dp",))
        else:
            devices = [c.jax_device for c in self._context]
            mesh = Mesh(np.asarray(devices), ("dp",))
        self._fused_multiproc = not all(
            d.process_index == jax.process_index()
            for d in mesh.devices.flat)
        with _tm.span("module.fused_build"):
            self._fused_trainer = ShardedTrainStep(
                self._symbol, mesh, optimizer=self._optimizer,
                param_specs=self._param_specs,
                data_names=self._data_names, label_names=self._label_names,
            ).compile()
            self._fused_owner = self
            # before the fused copies are placed: both at once do not
            # fit (nothing to drop unless an executor has already run)
            self._release_exec_arrays()
        if multiworker:
            # ranks may have initialized params independently; adopt the
            # kvstore's root-broadcast values (kv.init stored rank 0's)
            # so the replicated device_put sees identical bytes on every
            # process — reference dist init semantics (all workers start
            # from rank 0's weights)
            for idx, name in enumerate(self._exec_group.param_names):
                self._kvstore.pull(idx, out=self._arg_params[name])
        self._fused_params, self._fused_aux = self._fused_trainer.place_params(
            self._arg_params, self._aux_params)
        self._params_dirty = True  # a draw made on the mesh is only there
        self._fused_opt = self._fused_trainer.make_state(self._fused_params)
        if self._fused_trainer.amp:
            # make_state captured the fp32 params as master slabs; the
            # compiled step now consumes bf16 working copies (invariant:
            # working params == bf16(masters) at every step boundary)
            self._fused_params = self._fused_trainer.amp_cast_params(
                self._fused_params)
        self._fused_t = 0

    def _make_fused_batch(self, data_batch):
        import jax

        sharding = self._fused_trainer.batch_sharding()
        multiproc = getattr(self, "_fused_multiproc", False) or getattr(
            self._fused_owner, "_fused_multiproc", False)

        def _put(arr):
            if multiproc:
                # this process contributes its LOCAL rows of the global
                # batch (reference: each dist worker reads its own data
                # shard; global batch = local batch x num_workers)
                return jax.make_array_from_process_local_data(
                    sharding, self._fused_trainer.count_h2d(arr.asnumpy()))
            data = getattr(arr, "_data", None)
            if data is not None and getattr(data, "sharding", None) == sharding:
                # DeviceFeedIter staged this batch on the mesh already —
                # hand the (immutable) buffer straight to the compiled
                # step: no asnumpy sync, no per-step host->device copy
                _M_FEED_HITS.inc()
                return data
            return jax.device_put(
                self._fused_trainer.count_h2d(arr.asnumpy()), sharding)

        batch = {}
        for name, arr in zip(self._data_names, data_batch.data):
            batch[name] = _put(arr)
        if self._label_names and data_batch.label:
            for name, arr in zip(self._label_names, data_batch.label):
                batch[name] = _put(arr)
        return batch

    def _release_exec_arrays(self):
        """The fused step keeps parameters, gradients and optimizer state
        on the mesh itself, so while it trains the executor group's own
        weight and gradient buffers are dead weight (the gradients are
        never written on this path, the weights are stale after one
        step): two parameter-sized buffers per device, at 1.5 G
        parameters the difference between fitting a 16 GB chip and not.
        From bind on they hold no buffer at all (``nd.deferred_full``);
        what an executor-path forward or backward has made since goes
        here, each array back to the deferred zero it was born as.
        ``_ensure_exec_params`` fills the weights again before any
        executor-path forward, and an executor backward writes its
        gradients anew (``grad_req`` ``write``; an accumulating
        gradient is left alone)."""
        for exe in self._exec_group.execs:
            args, grads = exe.arg_dict, exe.grad_dict
            for name in self._param_names:
                args[name]._drop_buffer()
                if exe._grad_req.get(name) == "write":
                    grads[name]._drop_buffer()
        self._exec_params_stale = True

    def _outdate_exec_params(self):
        """The truth moved (host parameters were set, a fused update
        ran): what the executor group holds is out of date, and under
        the fused step it goes."""
        if self._fused_trainer is None:
            self._exec_params_stale = True
        elif not self._exec_params_stale:
            self._release_exec_arrays()

    def _ensure_exec_params(self):
        """Give the executor group its weights before an executor runs:
        from the fused state after fused updates (the eval/predict path
        still runs per-device executors), else the host parameters that
        ``init_params`` / ``set_params`` left for this moment."""
        if self._exec_params_stale:
            if self._fused_trainer is not None:
                self._sync_params_from_devices()
            self._exec_group.set_params(self._arg_params, self._aux_params)
            self._exec_params_stale = False

    def borrow_optimizer(self, shared_module):
        """Parity module.py:529. When the shared module runs the fused
        mesh path, this module joins it: same optimizer, and the sharded
        param/aux/opt-state dicts live on the OWNER so every borrower
        (e.g. BucketingModule children, which share param names) sees
        each other's updates."""
        assert shared_module.optimizer_initialized
        self._optimizer = shared_module._optimizer
        self._kvstore = shared_module._kvstore
        self._update_on_kvstore = shared_module._update_on_kvstore
        self._updater = shared_module._updater
        if shared_module._fused_trainer is not None:
            from ..parallel.train_step import ShardedTrainStep

            owner = shared_module._fused_owner or shared_module
            self._fused_owner = owner
            if owner._fused_trainer.flat_mode is not None:
                # borrowers update a param-name SUBSET of the owner's
                # dict; the flat slabs span the owner's full param space
                # and cannot express that — demote the owner to the
                # legacy per-param update (state converted in place)
                if owner._fused_trainer.amp:
                    # the legacy path has no master slabs: reconstitute
                    # the fp32 truth as the working params before the
                    # masters are dropped with the flat state
                    owner._fused_params = (
                        owner._fused_trainer.master_params_placed(
                            owner._fused_opt))
                owner._fused_opt = owner._fused_trainer.disable_flat_update(
                    owner._fused_opt)
                owner._fused_trainer.compile()
            self._fused_trainer = ShardedTrainStep(
                self._symbol, shared_module._fused_trainer.mesh,
                optimizer=self._optimizer,
                param_specs=shared_module._fused_trainer.param_specs,
                data_names=self._data_names, label_names=self._label_names,
                flat_update=False,
            ).compile()
            # the shared weights are released or stale, the gradients unused
            self._release_exec_arrays()
            self._params_dirty = True  # the truth is the owner's fused state
        self.optimizer_initialized = True

    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        if (self._fused_trainer is not None
                and (is_train is None or is_train) and self.for_training):
            # defer: the fused step runs fwd+bwd+update at update()
            self._fused_batch = data_batch
            self._fused_outputs = None
            self._fused_outs_raw = None
            return
        # executor path (eval/predict): drop any stale fused outputs so
        # get_outputs/update_metric serve THIS forward's results
        self._fused_outputs = None
        self._fused_outs_raw = None
        self._fused_batch = None
        self._ensure_exec_params()
        self._exec_group.forward(data_batch, is_train)

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        if self._fused_trainer is not None and self._fused_batch is not None:
            assert out_grads is None, \
                "fused path computes gradients in update()"
            return
        self._exec_group.backward(out_grads=out_grads)

    def update(self):
        """Parity module.py:553."""
        assert self.binded and self.params_initialized and self.optimizer_initialized
        self._params_dirty = True
        if (self._kvstore is not None
                and getattr(self._kvstore, "_heartbeat", None) is not None):
            # fused-path steps bypass kvstore push/pull, so mark training
            # progress here too (parallel/heartbeat.py prog_<rank>)
            self._kvstore._heartbeat.progress()
        if self._fused_trainer is not None:
            assert self._fused_batch is not None, "forward() before update()"
            owner = self._fused_owner
            optm = self._optimizer
            owner._fused_t += 1
            optm.num_update = max(owner._fused_t, optm.num_update)
            # One scheduled lr per step for ALL params. (The reference's
            # per-param Updater staggers scheduler transitions by one
            # batch for the first param — an artifact of interleaving
            # _get_lr/_update_count across params, not a spec; the fused
            # step uses the post-increment count like params 1..N-1 do.)
            lr = (optm.lr_scheduler(optm.num_update)
                  if optm.lr_scheduler is not None else optm.lr)
            # borrowed trainers lazily adopt the owner's state for any
            # params this symbol shares; missing opt-state entries are
            # created on first use
            if self is not owner and self._fused_params is None:
                self._fused_params = owner._fused_params
                self._fused_aux = owner._fused_aux
                self._fused_opt = owner._fused_opt
            with _tm.span("module.update", path="fused"):
                # staging + enqueue together are the step's host-side
                # cost: the trainer call returns before the device runs
                t0 = time.perf_counter()
                with _tm.span("module.stage"):
                    batch = self._make_fused_batch(self._fused_batch)
                _H_STAGE_HOST.observe(time.perf_counter() - t0)
                p, a, s, outs = self._fused_trainer(
                    owner._fused_params, owner._fused_aux, owner._fused_opt,
                    batch, lr=lr, t=owner._fused_t,
                )
                _H_DISPATCH_HOST.observe(time.perf_counter() - t0)
            owner._fused_params, owner._fused_aux, owner._fused_opt = p, a, s
            outs = list(outs)
            if getattr(self._fused_trainer, "guard", False):
                # last output head is the guardrail diag (loss, gnorm²,
                # gate_ok): queue it for the fit-side monitor, keep it
                # out of get_outputs()/metrics
                owner._guard_pending = getattr(owner, "_guard_pending", [])
                owner._guard_pending.append((owner._fused_t, outs.pop()))
            # raw jax.Arrays; _local_rows conversion (a host transfer in
            # multi-process runs) happens lazily on first read so loops
            # that never touch outputs don't stall the async pipeline
            self._fused_outs_raw = outs
            self._fused_outputs = None
            self._fused_batch = None
            self._outdate_exec_params()
            return
        if self._update_on_kvstore:
            with _tm.span("module.update", path="kvstore"):
                _update_params_on_kvstore(
                    self._exec_group.param_arrays,
                    self._exec_group.grad_arrays, self._kvstore
                )
        else:
            with _tm.span("module.update", path="local"):
                _update_params(
                    self._exec_group.param_arrays,
                    self._exec_group.grad_arrays,
                    updater=self._updater, num_device=len(self._context),
                    kvstore=self._kvstore
                )

    def _install_step_outputs(self, outs_raw):
        """Publish one step's raw outputs as the current fused outputs
        (fit uses this per step, under its one-step lookahead, so
        update_metric/get_outputs serve that step's results — the ONLY
        sanctioned way for callers to set fused-output state)."""
        self._fused_outs_raw = outs_raw
        self._fused_outputs = None

    def _drain_guard_diag(self):
        """Return queued (step_t, diag) guardrail samples and clear the
        queue.  diag is a length-3 float32 vector (loss, grad-norm²,
        gate_ok); materialising it here is the only host sync the
        guardrail adds, one tiny transfer per step."""
        owner = self._fused_owner or self
        pending = getattr(owner, "_guard_pending", None)
        if not pending:
            return []
        out = [(int(t), np.asarray(_local_rows(d))) for t, d in pending]
        pending.clear()
        return out

    def _materialized_fused_outputs(self):
        if self._fused_outputs is None and self._fused_outs_raw is not None:
            self._fused_outputs = [
                nd.NDArray(_local_rows(o)) for o in self._fused_outs_raw]
        return self._fused_outputs

    def get_outputs(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        if self._fused_trainer is not None:
            outs = self._materialized_fused_outputs()
            if outs is not None:
                return outs
            if self._fused_batch is not None:
                # forward() was deferred and update() has not run yet:
                # serve outputs through the executor path
                self._ensure_exec_params()
                self._exec_group.forward(self._fused_batch, True)
                return self._exec_group.get_outputs(
                    merge_multi_context=merge_multi_context
                )
        return self._exec_group.get_outputs(merge_multi_context=merge_multi_context)

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.params_initialized and self.inputs_need_grad
        return self._exec_group.get_input_grads(merge_multi_context=merge_multi_context)

    def update_metric(self, eval_metric, labels):
        if self._fused_trainer is not None and (
                self._fused_outputs is not None
                or self._fused_outs_raw is not None):
            # The one place a fused step's outputs reach the metric
            # (under fit's lookahead with the next step already
            # enqueued behind it). Wrapping the device arrays is a host
            # transfer only in multi-process runs; the blocking fetch
            # happens inside eval_metric.update (NDArray.asnumpy). This
            # interval, fetch and the metric's arithmetic together, is
            # what module.output_sync_seconds holds.
            with _tm.span("module.update_metric"):
                t0 = time.perf_counter()
                eval_metric.update(labels,
                                   self._materialized_fused_outputs())
                _H_OUTPUT_SYNC.observe(time.perf_counter() - t0)
            return
        with _tm.span("module.update_metric"):
            self._exec_group.update_metric(eval_metric, labels)

    def _metric_snapshot(self):
        """fit()'s lookahead hook: the fused path's raw per-step outputs
        are freshly allocated jax arrays (never donated, never written
        again), so holding references keeps them valid while later steps
        dispatch. Returns None on the executor path: its output NDArrays
        are REUSED across steps, so a read after the next dispatch would
        see the later step's values, and fit stays synchronous there."""
        if (self._fused_trainer is not None
                and self._fused_outs_raw is not None):
            return list(self._fused_outs_raw)
        return None

    def _sync_params_from_devices(self):
        """Parity module.py:666."""
        with _tm.span("module.sync_params"):
            if self._fused_trainer is not None:
                owner = self._fused_owner
                trainer = owner._fused_trainer
                params_src = owner._fused_params
                if trainer.amp:
                    # the working copies are bf16 casts; the fp32 truth
                    # lives in the master slabs
                    params_src = dict(params_src)
                    params_src.update(
                        trainer.master_params_named(owner._fused_opt))
                for name, arr in params_src.items():
                    if name in self._arg_params:
                        self._arg_params[name][:] = np.asarray(arr)
                for name, arr in owner._fused_aux.items():
                    if name in self._aux_params:
                        self._aux_params[name][:] = np.asarray(arr)
                self._params_dirty = False
                return
            if not self._exec_params_stale:  # else the host's are newer
                self._exec_group.get_params(self._arg_params,
                                            self._aux_params)
            self._params_dirty = False

    def _topology(self):
        """The runtime topology this module trains at, recorded into
        checkpoint manifests (elastic resume): dp degree, mesh axis
        shape, and batch geometry. The state payload itself is
        layout-independent — this is the metadata that lets the
        restoring side rescale its data cursor and lets ckpt_inspect
        warn about a cross-world restore up front."""
        if not self.binded:
            return None
        global_batch = self._exec_group.batch_size
        mesh_shape = None
        if self._fused_trainer is not None:
            mesh = self._fused_owner._fused_trainer.mesh
            mesh_shape = {k: int(v) for k, v in mesh.shape.items()}
            dp = mesh_shape.get("dp", 1)
            if getattr(self, "_fused_multiproc", False):
                # each process feeds its local rows; the global batch is
                # the fleet's (reference dist semantics, _init_optimizer
                # rescale math)
                import jax

                global_batch *= max(1, jax.process_count())
        else:
            dp = len(self._context)
        dp = max(1, int(dp))
        return {
            "dp": dp,
            "mesh": mesh_shape,
            "global_batch": int(global_batch),
            "per_replica_batch": int(global_batch) // dp,
        }

    def _capture_train_state(self):
        """Consistent snapshot of params + optimizer state for the atomic
        checkpointer (resilience/checkpoint.py).

        Fused path: params/aux/opt are immutable jax.Arrays rebound each
        step, but the compiled step DONATES them (train_step.py
        donate_argnums), so a raw reference captured here is deleted the
        moment the next step dispatches. Snapshot device-side copies
        instead: an async device-to-device pass that owns fresh buffers,
        still without any host pull on the train thread — the checkpoint
        writer thread does the blocking host transfers. Executor path:
        arrays are mutated in place, so the snapshot copies to host here.
        """
        assert self.binded and self.params_initialized
        if self._fused_trainer is not None:
            import jax

            def _copy(tree):
                # a + 0 forces fresh output buffers (never aliased to the
                # donated inputs); dtype-preserving for float/int arrays
                return jax.tree_util.tree_map(lambda a: a + 0, tree)

            owner = self._fused_owner
            fused_state = dict(owner._fused_opt)
            trainer = owner._fused_trainer
            arg_src = dict(owner._fused_params)
            amp_blob = None
            if trainer.flat_mode is not None:
                if trainer.amp:
                    # snapshot the fp32 masters as "arg" — the on-disk
                    # params are always full precision, so an AMP
                    # checkpoint restores into an fp32 run unchanged
                    # (and vice versa); the loss-scaler state rides as
                    # a separate scalar blob
                    arg_src = trainer.master_params_named(fused_state)
                    amp_blob = {
                        "scale": fused_state[trainer.AMP_SCALE_KEY],
                        "good": fused_state[trainer.AMP_GOOD_KEY],
                    }
                # carve flat bucket slabs back to per-param trees so the
                # snapshot layout never depends on MXTPU_SHARD_UPDATE /
                # MXTPU_BUCKET_BYTES (device-side slices: fresh buffers,
                # still no host pull on the train thread)
                fused_state = trainer.flat_state_to_named(fused_state)
            out = {
                "arg": _copy(arg_src),
                "aux": _copy(dict(owner._fused_aux)),
                "opt": {"kind": "fused", "t": owner._fused_t,
                        "state": _copy(fused_state)},
            }
            if amp_blob is not None:
                out["opt"]["amp"] = _copy(amp_blob)
            return out
        arg, aux = self.get_params()
        state = {
            "arg": {k: np.array(v.asnumpy()) for k, v in arg.items()},
            "aux": {k: np.array(v.asnumpy()) for k, v in aux.items()},
            "opt": {"kind": "none"},
        }
        if not self.optimizer_initialized:
            return state
        if self._kvstore is not None:
            # in-flight async push/pull ops still mutate updater state;
            # quiesce the comm engine so the snapshot is a step boundary
            # (deferred bucketed reduces included)
            self._kvstore._flush_buckets()
            self._kvstore._comm.wait_for_all()
        updater = (self._kvstore._updater if self._update_on_kvstore
                   else self._updater)
        if updater is not None:
            state["opt"] = {"kind": "updater", "bytes": updater.get_states()}
        return state

    def _restore_train_state(self, blob):
        """Inverse of :meth:`_capture_train_state` over a host-side blob
        (numpy trees from checkpoint load): params back onto devices,
        optimizer state re-placed, fused executors marked stale."""
        assert self.binded and self.params_initialized
        arg = {k: nd.array(v) for k, v in (blob.get("arg") or {}).items()}
        aux = {k: nd.array(v) for k, v in (blob.get("aux") or {}).items()}
        self.set_params(arg, aux)
        if self._fused_trainer is not None:
            owner = self._fused_owner
            owner._fused_params, owner._fused_aux = (
                owner._fused_trainer.place_params(
                    self._arg_params, self._aux_params))
            if owner._fused_trainer.amp:
                # blob["arg"] is the fp32 truth (masters when saved
                # under AMP); working copies are its bf16 cast, masters
                # are rebuilt below in _place_fused_opt_state
                owner._fused_params = (
                    owner._fused_trainer.amp_cast_params(
                        owner._fused_params))
            if self is not owner:
                self._fused_params = owner._fused_params
                self._fused_aux = owner._fused_aux
        opt = blob.get("opt") or {"kind": "none"}
        kind = opt.get("kind", "none")
        if kind == "fused":
            if self._fused_trainer is None:
                raise MXNetError(
                    "checkpoint carries fused optimizer state but this "
                    "module trains on the executor path — rebind with a "
                    "device kvstore (or retrain) to resume it")
            self._place_fused_opt_state(opt["t"], opt["state"],
                                        amp_blob=opt.get("amp"),
                                        sync_masters=False)
        elif kind == "updater":
            if self._fused_trainer is not None:
                raise MXNetError(
                    "checkpoint carries executor-path optimizer state but "
                    "this module trains on the fused path — resume with "
                    "the same kvstore type it was saved under")
            if self._kvstore is not None:
                self._kvstore._flush_buckets()
                self._kvstore._comm.wait_for_all()
            updater = (self._kvstore._updater if self._update_on_kvstore
                       else self._updater)
            if updater is None:
                raise MXNetError(
                    "checkpoint carries optimizer state but no updater is "
                    "initialized — call init_optimizer before restoring")
            updater.set_states(opt["bytes"])
        elif self._fused_trainer is not None:
            owner = self._fused_owner
            trainer = owner._fused_trainer
            if trainer.amp:
                # params-only blob: under AMP the masters ARE the weight
                # truth, so leaving them stale would silently resume
                # from the pre-restore weights — rebuild them from the
                # just-restored fp32 params (scaler state reset)
                state = dict(owner._fused_opt)
                state.update(
                    trainer.build_amp_master_state(self._arg_params))
                owner._fused_opt = state
                if self is not owner:
                    self._fused_opt = owner._fused_opt

    def _fused_opt_host_state(self):
        """Fused optimizer state pulled to host: {"t": int, "state":
        {name: nested numpy tuples}} — the on-disk payload shape shared
        by save_optimizer_states and the checkpoint subsystem. Always
        per-param, never flat-bucket slabs: snapshots stay readable
        whatever MXTPU_SHARD_UPDATE/MXTPU_BUCKET_BYTES said at save
        time."""
        owner = self._fused_owner
        state = dict(owner._fused_opt)
        trainer = owner._fused_trainer
        amp_blob = None
        if trainer.flat_mode is not None:
            if trainer.amp:
                amp_blob = trainer.amp_state_blob(state)
            state = trainer.flat_state_to_named(state)

        def _host(s):
            if s is None:
                return None
            if isinstance(s, tuple):
                return tuple(_host(x) for x in s)
            return np.asarray(s)

        if amp_blob is not None:
            return {"t": owner._fused_t, "amp": amp_blob,
                    "state": {k: _host(v) for k, v in state.items()}}
        return {"t": owner._fused_t,
                "state": {k: _host(v) for k, v in state.items()}}

    def _place_fused_opt_state(self, t, state_tree, amp_blob=None,
                               sync_masters=True):
        """Place a host optimizer-state tree back onto the fused
        trainer's shardings (shared by load_optimizer_states and
        checkpoint resume).

        Under AMP the flat state also carries the fp32 master slabs and
        the loss-scaler scalars, which the per-param ``state_tree``
        deliberately does not (it must stay dtype-portable). Masters are
        rebuilt from ``self._arg_params``: checkpoint resume
        (``sync_masters=False``) restored those from the blob's fp32
        "arg" payload just before calling here; a standalone
        load_optimizer_states (``sync_masters=True``) first syncs them
        from the CURRENT device masters so the rebuilt slabs match the
        weights the run is actually at. ``amp_blob`` restores the saved
        loss scale / good-step counter; None starts the scaler fresh."""
        import jax

        owner = self._fused_owner
        trainer = owner._fused_trainer

        def _place(name, s):
            if s is None:
                return None
            if isinstance(s, tuple):
                return tuple(_place(name, x) for x in s)
            return jax.device_put(
                s, trainer._state_sharding_for(name, s)
            )

        owner._fused_t = int(t)
        if trainer.flat_mode is not None:
            if trainer.amp and sync_masters:
                # pulls the old masters into self._arg_params before the
                # flat state (and with it the old masters) is replaced
                self._sync_params_from_devices()
            # repack the per-param snapshot into this run's flat bucket
            # slabs (pads re-zeroed — they provably stay zero under every
            # elementwise optimizer, so resume is bitwise-exact)
            owner._fused_opt = trainer.named_state_to_flat(state_tree)
            if trainer.amp:
                blob = amp_blob or {}
                owner._fused_opt.update(trainer.build_amp_master_state(
                    self._arg_params,
                    scale=blob.get("scale"),
                    good=blob.get("good", 0.0)))
        else:
            owner._fused_opt = {
                k: _place(k, v) for k, v in state_tree.items()
            }
        if self is not owner:
            self._fused_t = owner._fused_t
            self._fused_opt = owner._fused_opt

    def save_optimizer_states(self, fname):
        """Parity module.py:674 — atomic write (temp + fsync + rename)."""
        from ..resilience.checkpoint import atomic_file

        assert self.optimizer_initialized
        if self._fused_trainer is not None:
            import pickle

            with atomic_file(fname) as fout:
                pickle.dump(self._fused_opt_host_state(), fout)
            return
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
        else:
            with atomic_file(fname) as fout:
                fout.write(self._updater.get_states())

    def load_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._fused_trainer is not None:
            import pickle

            with open(fname, "rb") as fin:
                blob = pickle.load(fin)
            self._place_fused_opt_state(blob["t"], blob["state"],
                                        amp_blob=blob.get("amp"))
            return
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
        else:
            self._updater.set_states(open(fname, "rb").read())

    def install_monitor(self, mon):
        assert self.binded
        if self._fused_trainer is not None:
            raise MXNetError(
                "This module already trains through the fused whole-graph "
                "XLA path, which has no per-op boundaries for Monitor "
                "callbacks. Rebind first — fit(..., monitor=mon, "
                "force_rebind=True) — so training routes through the "
                "per-op executor path.")
        self._monitor = mon
        self._exec_group.install_monitor(mon)
