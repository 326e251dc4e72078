"""DataParallelExecutorGroup: per-device executors for data parallelism.

Parity: reference ``python/mxnet/module/executor_group.py`` (+
``executor_manager.py`` ``_split_input_slice``). The reference binds one
GraphExecutor per GPU and scatters each batch by ``work_load_list``; here
each context gets its own jit-compiled executor (one XLA program per
device) and gradients combine through the KVStore — the same shape as the
reference's §3.1 call stack. (The fused single-program mesh path lives in
mxnet_tpu.parallel; this class keeps exact reference semantics.)
"""
from __future__ import annotations

import logging

import numpy as np

from .. import context as ctx_mod
from .. import ndarray as nd
from ..base import MXNetError
from ..executor import Executor
from ..io import DataDesc


def _split_input_slice(batch_size, work_load_list):
    """Parity executor_manager.py:14 — batch → per-device slices."""
    total_work_load = sum(work_load_list)
    batch_num_list = [
        round(work_load * batch_size / total_work_load)
        for work_load in work_load_list
    ]
    batch_num_sum = sum(batch_num_list)
    if batch_num_sum != batch_size:
        batch_num_list[-1] += batch_size - batch_num_sum
    slices = []
    end = 0
    for batch_num in batch_num_list:
        begin = int(min(end, batch_size))
        end = int(min(begin + batch_num, batch_size))
        if begin >= end:
            raise ValueError("Too many slices. Some splits are empty.")
        slices.append(slice(begin, end))
    return slices


def _load_general(data, targets):
    for d_src, d_targets in zip(data, targets):
        if isinstance(d_targets, nd.NDArray):
            d_src.copyto(d_targets)
            continue
        if len(d_targets) == 1:
            # single-device fast path: when the one target slice covers
            # the whole batch and src/dst agree on shape, dtype, and
            # placement, adopt the source's (immutable) buffer — this
            # replaces the per-step slice + host round-trip copy, and a
            # DeviceFeedIter-staged batch needs no transfer at all
            slice_idx, d_dst = d_targets[0]
            src = getattr(d_src, "_data", None)
            # the target as it stands, made or not: it is only replaced
            dst = getattr(d_dst, "_buf", None)
            if (src is not None and dst is not None
                    and getattr(d_src, "_engine_dep", None) is None
                    and getattr(d_dst, "_engine_dep", None) is None
                    and (slice_idx.stop - slice_idx.start) == d_src.shape[0]
                    and tuple(d_dst.shape) == tuple(d_src.shape)
                    and dst.dtype == src.dtype
                    and getattr(src, "sharding", None)
                    == getattr(dst, "sharding", None)):
                d_dst._data = src
                continue
        for slice_idx, d_dst in d_targets:
            d_src[slice_idx].copyto(d_dst)


def _load_data(batch, targets):
    _load_general(batch.data, targets)


def _load_label(batch, targets):
    _load_general(batch.label, targets)


def _merge_multi_context(outputs):
    """Concatenate per-device outputs along batch (parity
    executor_group.py:52 _merge_multi_context with axis 0). Shards are
    committed to their executor's device, so they must be gathered onto
    one device first — jax refuses cross-committed-device concatenation
    (the reference copies into one pinned-CPU output for the same
    reason)."""
    def _gather(tensors):
        if len(tensors) == 1:
            return tensors[0]
        home = tensors[0].context
        return nd.concatenate(
            [t.as_in_context(home) for t in tensors], axis=0)

    return [_gather(tensors) for tensors in outputs]


class _Weights:
    """Whether a group's weight arrays are out of date (``stale``): the
    truth is then the module's host parameters or its fused state, and
    ``Module._ensure_exec_params`` fills the arrays before an executor
    runs. One object for every group that shares the arrays by identity
    (``shared_group``: bucketing), since what one of them fills or
    outdates it does for all."""

    __slots__ = ("stale",)

    def __init__(self):
        self.stale = False


class DataParallelExecutorGroup(object):
    """Parity: executor_group.py:77 DataParallelExecutorGroup."""

    def __init__(self, symbol, contexts, workload, data_shapes, label_shapes,
                 param_names, for_training, inputs_need_grad,
                 shared_group=None, logger=logging, fixed_param_names=None,
                 grad_req="write"):
        self.param_names = param_names
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.symbol = symbol
        self.contexts = contexts
        self.workload = workload
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.logger = logger
        self.fixed_param_names = fixed_param_names or []
        if shared_group is None:
            self.shared_data_arrays = [{} for _ in contexts]
        else:
            self.shared_data_arrays = shared_group.shared_data_arrays
        self.shared_group = shared_group
        self.weights = _Weights() if shared_group is None \
            else shared_group.weights

        data_names = [x[0] for x in data_shapes]
        if isinstance(grad_req, str):
            self.grad_req = {}
            for k in self.arg_names:
                if k in self.param_names:
                    self.grad_req[k] = (
                        "null" if k in self.fixed_param_names else grad_req
                    )
                elif k in data_names:
                    self.grad_req[k] = grad_req if inputs_need_grad else "null"
                else:
                    self.grad_req[k] = "null"
        elif isinstance(grad_req, (list, tuple)):
            self.grad_req = dict(zip(self.arg_names, grad_req))
        elif isinstance(grad_req, dict):
            self.grad_req = {k: "null" for k in self.arg_names}
            self.grad_req.update(grad_req)
        else:
            raise ValueError("invalid grad_req")

        self.execs = []
        self.data_arrays = None
        self.label_arrays = None
        self.param_arrays = None
        self.grad_arrays = None
        self.aux_arrays = None
        self.batch_size = None
        self.slices = None
        self.data_shapes = None
        self.label_shapes = None
        self.bind_exec(data_shapes, label_shapes, shared_group)

    def decide_slices(self, data_shapes):
        """Parity executor_group.py:207."""
        assert len(data_shapes) > 0
        major_axis = [0] * len(data_shapes)  # batch-major (layout handling n/a)
        for (name, shape), axis in zip(data_shapes, major_axis):
            batch_size = shape[axis]
            if self.batch_size is not None:
                assert batch_size == self.batch_size, (
                    "all data must have the same batch size"
                )
            else:
                self.batch_size = batch_size
                self.slices = _split_input_slice(self.batch_size, self.workload)
        return major_axis

    def bind_exec(self, data_shapes, label_shapes, shared_group=None,
                  reshape=False):
        """Parity executor_group.py:270."""
        self.batch_size = None
        self.data_layouts = self.decide_slices(data_shapes)
        if label_shapes is not None:
            self.label_layouts = self.decide_slices(label_shapes)
        self.execs = []
        for i in range(len(self.contexts)):
            self.execs.append(
                self._bind_ith_exec(i, data_shapes, label_shapes, shared_group)
            )
        self.data_shapes = data_shapes
        self.label_shapes = label_shapes
        self._output_shapes_cache = None
        self._collect_arrays()

    def reshape(self, data_shapes, label_shapes):
        if data_shapes == self.data_shapes and label_shapes == self.label_shapes:
            return
        # fresh executors and arrays, shared with nobody
        self.weights = _Weights()
        self.bind_exec(data_shapes, label_shapes, reshape=True)

    def _collect_arrays(self):
        self.data_arrays = [
            [(self.slices[i], e.arg_dict[name]) for i, e in enumerate(self.execs)]
            for name, _ in self.data_shapes
        ]
        if self.label_shapes is not None:
            self.label_arrays = [
                [(self.slices[i], e.arg_dict[name]) for i, e in enumerate(self.execs)]
                for name, _ in self.label_shapes
            ]
        else:
            self.label_arrays = None
        self.param_arrays = [
            [exec_.arg_arrays[i] for exec_ in self.execs]
            for i, name in enumerate(self.arg_names)
            if name in self.param_names
        ]
        if self.for_training:
            self.grad_arrays = [
                [exec_.grad_arrays[i] for exec_ in self.execs]
                for i, name in enumerate(self.arg_names)
                if name in self.param_names
            ]
        else:
            self.grad_arrays = None
        data_names = [x[0] for x in self.data_shapes]
        if self.inputs_need_grad:
            self.input_grad_arrays = [
                [exec_.grad_arrays[self.arg_names.index(name)] for exec_ in self.execs]
                for name in data_names
            ]
        else:
            self.input_grad_arrays = None
        self.aux_arrays = [
            [exec_.aux_arrays[i] for exec_ in self.execs]
            for i in range(len(self.aux_names))
        ]

    def _sliced_shape(self, shapes, i):
        return [
            (name, tuple([self.slices[i].stop - self.slices[i].start] + list(shape[1:])))
            for name, shape in shapes
        ]

    def _bind_ith_exec(self, i, data_shapes, label_shapes, shared_group):
        """Parity executor_group.py:537 — per-device simple_bind with
        shared_data_arrays reuse."""
        data_shapes_i = self._sliced_shape(data_shapes, i)
        if label_shapes is not None:
            label_shapes_i = self._sliced_shape(label_shapes, i)
        else:
            label_shapes_i = []
        shared_exec = None if shared_group is None else shared_group.execs[i]
        input_shapes = dict(data_shapes_i)
        input_shapes.update(dict(label_shapes_i))
        return Executor.simple_bind(
            self.symbol, self.contexts[i], grad_req=self.grad_req,
            shared_exec=shared_exec, **input_shapes
        )

    # ------------------------------------------------------------------
    def set_params(self, arg_params, aux_params):
        for exec_ in self.execs:
            exec_.copy_params_from(arg_params, aux_params, allow_extra_params=True)

    def get_params(self, arg_params, aux_params):
        """Weighted merge back to CPU params (parity executor_group.py:317:
        the reference averages weight copies across devices)."""
        for name, block in zip(self.param_names, self.param_arrays):
            for w in block:
                # copyto reads its source as it stands: a kvstore pull
                # into this weight may still be in flight
                w.wait_to_read()
            weight = sum(w.copyto(ctx_mod.cpu()) for w in block) / len(block)
            weight.astype(arg_params[name].dtype).copyto(arg_params[name])
        for name, block in zip(self.aux_names, self.aux_arrays):
            weight = sum(w.copyto(ctx_mod.cpu()) for w in block) / len(block)
            weight.astype(aux_params[name].dtype).copyto(aux_params[name])

    def forward(self, data_batch, is_train=None):
        """Scatter batch slices, run per-device forward (parity
        executor_group.py:355)."""
        _load_data(data_batch, self.data_arrays)
        if is_train is None:
            is_train = self.for_training
        if self.label_arrays is not None and data_batch.label:
            _load_label(data_batch, self.label_arrays)
        for exec_ in self.execs:
            exec_.forward(is_train=is_train)

    def get_output_shapes(self):
        # static inference, cached per bind (shapes only change on
        # bind_exec/reshape which reset the cache)
        if getattr(self, "_output_shapes_cache", None) is None:
            exe0 = self.execs[0]
            input_shapes = {
                name: exe0.arg_dict[name].shape
                for name, _ in self.data_shapes + (self.label_shapes or [])
            }
            _, out_shapes, _ = self.symbol.infer_shape(**input_shapes)
            concat_shapes = []
            for key, the_shape in zip(self.symbol.list_outputs(), out_shapes):
                the_shape = list(the_shape)
                the_shape[0] = self.batch_size
                concat_shapes.append((key, tuple(the_shape)))
            self._output_shapes_cache = concat_shapes
        return self._output_shapes_cache

    def get_outputs(self, merge_multi_context=True):
        outputs = [
            [exec_.outputs[i] for exec_ in self.execs]
            for i in range(len(self.execs[0].outputs))
        ]
        if merge_multi_context:
            outputs = _merge_multi_context(outputs)
        return outputs

    def get_input_grads(self, merge_multi_context=True):
        assert self.inputs_need_grad
        if merge_multi_context:
            return _merge_multi_context(self.input_grad_arrays)
        return self.input_grad_arrays

    def backward(self, out_grads=None):
        """Parity executor_group.py:481."""
        assert self.for_training, "re-bind with for_training=True to run backward"
        if out_grads is None:
            out_grads = []
        for i, exec_ in enumerate(self.execs):
            out_grads_slice = []
            for grad in out_grads:
                og = grad[self.slices[i]].as_in_context(self.contexts[i])
                out_grads_slice.append(og)
            exec_.backward(out_grads=out_grads_slice if out_grads_slice else None)

    def update_metric(self, eval_metric, labels):
        """Parity executor_group.py:510."""
        for texec, islice in zip(self.execs, self.slices):
            labels_slice = [label[islice] for label in labels]
            eval_metric.update(labels_slice, texec.outputs)

    def install_monitor(self, mon):
        for exe in self.execs:
            mon.install(exe)
