"""Executor: symbol → compiled XLA forward/backward.

Parity: reference ``python/mxnet/executor.py`` + ``src/executor/``
(GraphExecutor). This is THE seam SURVEY.md §3.2 identifies: everything the
reference does in GraphExecutor::Init — gradient pass, placement,
shape/type inference, memory planning, cached engine ops, bulk segments —
is replaced by tracing the whole symbol into one JAX function and
jit-compiling it:

- InitFullGraph + nnvm Gradient pass  → jax.vjp over the traced forward
- PlanMemory / InplaceAddTo           → XLA buffer assignment (+ donation)
- InitCachedOps / bulk-exec segments  → a single fused XLA module per
  (forward, forward+backward) — strictly stronger than the reference's
  15-node bulk segments
- AttachOpResources (temp space/rng)  → functional PRNG keys folded per-node

The training step (forward+backward) compiles to ONE XLA executable, so
per-op dispatch overhead — the reason the reference needs its threaded
engine — is zero on the hot path.
"""
from __future__ import annotations

import functools
import itertools
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import ndarray as nd
from . import random as _random
from . import telemetry as _tm
from .base import MXNetError, np_dtype
from .context import Context
from .ndarray import NDArray
from .ops import registry as _registry
from .symbol import Symbol, _topo_order

__all__ = ["Executor"]

_M_COMPILE_COUNT = _tm.counter(
    "executor.jit_compile_count", "XLA trace+compile events, by segment key")
_M_COMPILE_SECONDS = _tm.counter(
    "executor.jit_compile_seconds",
    "wall seconds spent in first-call trace+compile, by segment key")
_M_CACHE_HITS = _tm.counter(
    "executor.fn_cache_hits", "compiled-callable cache hits, by segment key")
_M_CACHE_MISSES = _tm.counter(
    "executor.fn_cache_misses",
    "compiled-callable cache misses (compiles), by segment key")
_H_STEP_SECONDS = _tm.histogram(
    "executor.step_seconds", "executor forward / fused fwd+bwd dispatch time")
_M_PLAN_HITS = _tm.counter(
    "executor.dispatch_plan_hits",
    "Steady-state dispatches served from the cached canonicalization "
    "plan (per-step graph-wide shape resolution and arg-dict churn "
    "skipped)")
_M_PLAN_MISSES = _tm.counter(
    "executor.dispatch_plan_misses",
    "Dispatch-plan cache misses: a new (shape, dtype, sharding) input "
    "signature was canonicalized and cached")


def _instrument_jit(fn, key):
    """Wrap a jitted callable with compile/cache accounting: the first
    call is where jax traces + XLA compiles (recorded as a cache miss
    plus compile count/seconds under ``segment=key``); every later call
    counts as a cache hit. Zero-overhead passthrough while telemetry is
    disabled."""
    state = {"compiled": False}

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        if not _tm.enabled():
            state["compiled"] = True
            return fn(*args, **kwargs)
        if state["compiled"]:
            _M_CACHE_HITS.inc(segment=key)
            return fn(*args, **kwargs)
        state["compiled"] = True
        _M_CACHE_MISSES.inc(segment=key)
        with _tm.span("jit_compile", segment=key):
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            dt = time.perf_counter() - t0
        _M_COMPILE_COUNT.inc(segment=key)
        _M_COMPILE_SECONDS.inc(dt, segment=key)
        return out

    return wrapper


def _as_jax(x):
    return x._data if isinstance(x, NDArray) else x


def _ctx_group(node):
    """A node's placement group: accepts both the in-memory attr name and
    the reference's serialized __ctx_group__ spelling (symbol.py:1183)."""
    return node.attrs.get("ctx_group") or node.attrs.get("__ctx_group__")


def _mirror_enabled():
    """Whole-graph gradient-checkpoint switch: the env flag only
    (reference MXNET_BACKWARD_DO_MIRROR, graph_executor.cc:213-226) —
    process-wide, not per-graph. Per-node __force_mirroring__ attrs remat
    just their own node — see _compute_node — so one flagged activation
    doesn't silently escalate to whole-model recompute."""
    from .base import get_env

    return bool(get_env("MXNET_BACKWARD_DO_MIRROR", 0))


def _force_mirrored(node):
    return node.attrs.get("__force_mirroring__") in ("True", "true", "1")


def op_class(op_name):
    """conv | fc | bn | pool | act | loss | attn | ssm | linattn | gdn |
    sconv | moe | norm | embed | hc | other: the class a node's device ops
    are filed under (the first part of its named scope). An op states its
    class where it is registered (``OpDef(op_class=)``); the rest go by two
    rules of name."""
    cls = (_registry.get(op_name).op_class if _registry.exists(op_name)
           else None)
    if cls is not None:
        return cls
    if op_name.endswith("Output"):
        return "loss"
    if op_name.startswith(("elemwise_", "broadcast_", "_plus", "_Plus",
                           "_add", "_minus", "_Minus", "_sub", "_mul",
                           "_Mul", "_div", "_Div")):
        return "act"
    return "other"


def _compute_node(node, attrs, in_vals, is_train):
    """Run one node's fcompute; a node carrying __force_mirroring__
    recomputes (only) itself in backward via jax.checkpoint — the
    per-node escape hatch the reference's need_mirror honors first.

    The node's ops are traced under the scope ``<op class>/<node
    name>`` (metadata only): backward ops inherit it inside JAX's
    ``transpose(jvp(...))`` wrapper, so a device op in a profiler trace
    says which layer and which kind of layer it belongs to."""
    cls = op_class(node.op.name)
    # while jax traces (and telemetry is on) the host seconds a node's
    # fcompute takes are observed by class: jit.node_trace_seconds, the
    # host's table with the device table's rows
    t0 = _tm.setup.trace_clock() if _tm.setup.tracing() else None
    with jax.named_scope("%s/%s" % (cls, node.name)):
        if is_train and _force_mirrored(node):
            fn = jax.checkpoint(
                lambda *iv: node.op.fcompute(attrs, list(iv), is_train))
            results = fn(*in_vals)
        else:
            results = node.op.fcompute(attrs, in_vals, is_train)
    if t0 is not None:
        _tm.setup.note_node_trace(cls, t0)
    return results


_MIRROR_SAVE_DEFAULT = "dot_general,conv_general_dilated"


def _mirror_policy(prim, *_args, **_params):
    """Which residuals to SAVE under memory mirroring. The reference
    recomputes every op in backward except Convolution / FullyConnected /
    Concat / SoftmaxOutput (graph_executor.cc need_mirror) — i.e. keep
    the MXU-expensive results, rematerialize the bandwidth-cheap ones
    (activations, BN, pooling). The XLA translation: save dot/conv
    primitive outputs, recompute everything else. (Dropout recompute is
    safe here: masks come from deterministic per-node fold_in keys.)

    MXNET_MIRROR_SAVE tunes the saved set (comma-separated primitive
    names; tests/test_mirror.py pins the default's remat) to trade
    recompute time against activation memory, e.g. adding
    reduce_window_max,reduce_window_sum (pooling) or concatenate
    (the reference's Concat) cuts the recompute chains at extra pins.
    Read per call (trace-time only) so a sweep can change it between
    compiles without cache invalidation."""
    names = os.environ.get("MXNET_MIRROR_SAVE", _MIRROR_SAVE_DEFAULT)
    return prim.name in _mirror_save_set(names)


@functools.lru_cache(maxsize=8)
def _mirror_save_set(names):
    return frozenset(n.strip() for n in names.split(",") if n.strip())


_M_SHIFT_GRADS = _tm.counter(
    "conv.shift_grad_lowerings", "Traces of a Convolution node that carries "
    "the gradient of the per-channel shift inside its data (an input "
    "BatchNorm's beta, _shift_grad_plan) and takes it from the batch's "
    "summed cotangent and one forward convolution at batch C "
    "(ops/nn.py::_carry_shift_grad), one per node and lowering, nothing "
    "per step; labels: node")


def _shift_grad_plan(nodes, output_entries):
    """{id(node): (node, index) of a beta} for the two ends of every input
    BatchNorm whose beta takes its gradient through the convolution that
    reads it, from the graph alone: a ``Convolution`` whose data is,
    through ``Cast`` nodes only, the first output of a ``BatchNorm`` with
    ``fix_gamma`` that normalises a variable of the symbol by batch
    statistics, each value on the way having that one reader.

    Such a BatchNorm's ``dgamma`` and ``dx`` have no reader (the batch
    wants none unless a caller asks), so of the convolution's data
    gradient of the whole batch only ``dbeta = sum(dy)`` is read:
    ``_GraphProgram.__call__`` hands the convolution beta (``__shift__``)
    and BatchNorm a beta under ``stop_gradient``. A BatchNorm in
    mid-network needs the whole ``dy`` for its ``dx``, one that learns
    gamma for ``sum(dy * xhat)``, and a second reader's cotangent is not
    the convolution's to sum: all of them keep the plain form."""
    readers = {}
    for node in nodes:
        for c, i in node.inputs:
            readers[(id(c), i)] = readers.get((id(c), i), 0) + 1
    for n, i in output_entries:
        readers[(id(n), i)] = readers.get((id(n), i), 0) + 1
    plan = {}
    for node in nodes:
        if node.is_variable or node.op.name != "Convolution":
            continue
        src, out = node.inputs[0]
        while (not src.is_variable and src.op.name == "Cast"
               and readers[(id(src), out)] == 1):
            src, out = src.inputs[0]
        if (src.is_variable or src.op.name != "BatchNorm" or out != 0
                or readers[(id(src), 0)] != 1
                or not src.inputs[0][0].is_variable):
            continue
        attrs = src.canon_attrs()
        if (bool(attrs.get("fix_gamma", True))
                and not bool(attrs.get("use_global_stats", False))):
            plan[id(node)] = plan[id(src)] = src.inputs[2]
    return plan


def _node_attrs(program, node, rng):
    """Execution-time attrs for one node — the ONE place where per-node
    execution semantics (shape overrides, CustomOp scoping keys, rng
    folding) live; _GraphProgram.__call__ and _PlacedProgram segments
    both call it so the two paths cannot silently diverge."""
    attrs = node.canon_attrs()
    if id(node) in program.shape_overrides:
        attrs["shape"] = program.shape_overrides[id(node)]
    if node.op.name == "Custom":
        # stateful CustomOp instances live per (bind, node) like the
        # reference's one-CustomOp-per-bind (custom-inl.h); the host
        # uses these keys to scope instance caching
        attrs["__program_id__"] = program._program_uid
        attrs["__node_name__"] = node.name
    if node.op.needs_rng:
        if rng is None:
            raise MXNetError("executor: rng required for %s" % node.name)
        attrs["__rng__"] = jax.random.fold_in(
            rng, program._node_ids[id(node)])
    return attrs


class _GraphProgram:
    """A symbol lowered to a pure function of (args, aux, rng) — the unit
    that gets jitted. Built once per bind; shared by fwd and fwd+bwd."""

    _uid_counter = itertools.count()

    def __init__(self, symbol: Symbol, shape_overrides=None):
        # monotonic uid (not id(self): CPython recycles ids, which would let
        # a new program inherit a dead bind's stateful CustomOp instances)
        self._program_uid = next(_GraphProgram._uid_counter)
        self.symbol = symbol
        # id(node) -> resolved out shape, for creation ops whose attr shape
        # has unknown (0) dims (RNN begin_state zeros)
        self.shape_overrides = shape_overrides or {}
        self.nodes = _topo_order([n for n, _ in symbol._outputs])
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.output_entries = list(symbol._outputs)
        self._var_nodes = {
            n.name: n for n in self.nodes if n.is_variable
        }
        # stable per-node ids for rng folding
        self._node_ids = {id(n): i for i, n in enumerate(self.nodes)}
        self._shift_grads = _shift_grad_plan(self.nodes, self.output_entries)
        # (shape, dtype, sharding) input signature -> canonicalized
        # per-signature dispatch state (see dispatch_plan)
        self._dispatch_plans = {}

    def dispatch_plan(self, sig, build):
        """Steady-state dispatch fast path. ``sig`` is the caller's
        (shape, dtype, sharding) input signature; ``build()`` produces
        the canonicalized per-signature state — today the resolved
        creation-op shape overrides (the arg-ordering/donation plan
        proper lives inside jax.jit, keyed by the same signature).
        Repeat signatures skip the graph-wide shape re-resolution and
        the full params+batch dict build/sort that used to run before
        EVERY dispatch; a shape, dtype, or sharding change (partial
        final batch, Module.reshape, re-placed inputs) re-canonicalizes
        exactly once."""
        plan = self._dispatch_plans.get(sig)
        if plan is None:
            _M_PLAN_MISSES.inc()
            # a miss past warmup is a fresh trace/compile on the hot
            # path — the anatomy layer fingerprints it and diffs against
            # the previous signature (no-op unless telemetry is on)
            _tm.anatomy.note_plan_miss(self._program_uid, sig)
            plan = build()
            self._dispatch_plans[sig] = plan
        else:
            _M_PLAN_HITS.inc()
        self.shape_overrides = plan
        return plan

    def __call__(self, arg_values, aux_values, rng, is_train):
        """arg_values: dict name→jax array; aux_values: dict name→jax array.
        Returns (outputs list, new_aux dict)."""
        import jax

        env = {}
        for name, v in arg_values.items():
            node = self._var_nodes.get(name)
            if node is not None:
                env[(id(node), 0)] = v
        for name, v in aux_values.items():
            node = self._var_nodes.get(name)
            if node is not None:
                env[(id(node), 0)] = v
        new_aux = {}
        for node in self.nodes:
            if node.is_variable:
                if (id(node), 0) not in env:
                    raise MXNetError("executor: missing input %s" % node.name)
                continue
            attrs = _node_attrs(self, node, rng)
            in_vals = [env[(id(c), i)] for (c, i) in node.inputs]
            if (is_train and id(node) in self._shift_grads
                    and in_vals[0].shape[1] < in_vals[0].shape[0]):
                # an input BatchNorm and its convolution
                # (_shift_grad_plan): beta's gradient moves from the one
                # to the other, a forward convolution at batch C; with no
                # fewer channels than samples (both nodes see the same
                # two) that would cost what the data gradient does
                if node.op.name == "BatchNorm":
                    in_vals[2] = jax.lax.stop_gradient(in_vals[2])
                else:
                    beta, i = self._shift_grads[id(node)]
                    attrs["__shift__"] = env[(id(beta), i)]
                    _M_SHIFT_GRADS.inc(node=node.name)
            results = _compute_node(node, attrs, in_vals, is_train)
            n_outs = node.num_outputs()
            for i, v in enumerate(results[:n_outs]):
                env[(id(node), i)] = v
            # trailing results update this node's aux-state variables
            n_args = node._extra.get("n_args", len(node.inputs))
            aux_inputs = node.inputs[n_args:]
            for (c, _), v in zip(aux_inputs, results[n_outs:]):
                new_aux[c.name] = v
        outputs = [env[(id(n), i)] for (n, i) in self.output_entries]
        for name in self.aux_names:
            if name not in new_aux:
                new_aux[name] = aux_values[name]
        return outputs, new_aux


class _LazyOutputs:
    """Sequence view over an executor's outputs that materializes the
    deferred train-step forward on first access."""

    def __init__(self, exe):
        self._exe = exe

    def __len__(self):
        return len(self._exe.outputs)

    def __getitem__(self, i):
        return self._exe.outputs[i]

    def __iter__(self):
        return iter(self._exe.outputs)

    def __repr__(self):
        return repr(self._exe.outputs)


class _PlacedProgram:
    """Model-parallel execution of a _GraphProgram across devices.

    The TPU-native redesign of the reference's placement pipeline
    (nnvm::pass::PlaceDevice + _CrossDeviceCopy insertion + engine
    overlap, src/executor/graph_executor.cc:245-334,
    src/operator/cross_device_copy.cc): the topo order is split into
    maximal contiguous same-device segments; each segment jit-compiles
    ONCE on its device (computation follows its committed inputs);
    boundary values move with an explicit eager ``jax.device_put`` — the
    _CrossDeviceCopy analog — and jax's async dispatch pipelines
    segments on different devices exactly like the reference's engine
    pipelines model-parallel LSTM stages.

    Backward runs segment-by-segment in reverse: each segment has a
    cached JITTED backward that recomputes its forward from the saved
    boundary inputs and transposes it (rematerialization — one extra
    segment-forward per step buys a fully-compiled backward with no
    per-step python AD tracing). Cotangents move back across the same
    device boundaries, and are only computed for inputs that can reach
    a gradient variable — data/label cotangents are never materialized.
    This stitched design exists because SPMD alone cannot express
    distinct per-stage computations on distinct devices in one program.
    """

    def __init__(self, program, node_dev, grad_names=()):
        self.program = program
        segs = []
        for node in program.nodes:
            if node.is_variable:
                continue
            dev = node_dev[id(node)]
            if segs and segs[-1][0] == dev:
                segs[-1][1].append(node)
            else:
                segs.append((dev, [node]))
        self.segments = segs

        # which nodes' outputs can influence a gradient variable's ct:
        # a value needs a cotangent iff a grad var is among its ancestors
        grad_names = set(grad_names)
        needs_ct = {}
        for node in program.nodes:
            if node.is_variable:
                needs_ct[id(node)] = node.name in grad_names
            else:
                needs_ct[id(node)] = any(
                    needs_ct[id(c)] for (c, _) in node.inputs)
        self._needs_ct = needs_ct

        final_keys = {(id(n), i) for n, i in program.output_entries}
        raw = []
        for dev, nodes in segs:
            in_seg = {id(n) for n in nodes}
            needs, seen = [], set()
            prods = []
            aux_names = []
            for node in nodes:
                for (c, i) in node.inputs:
                    k = (id(c), i)
                    if id(c) in in_seg or k in seen:
                        continue
                    seen.add(k)
                    needs.append(k)
                prods.extend(
                    (id(node), i) for i in range(node.num_outputs()))
                n_args = node._extra.get("n_args", len(node.inputs))
                aux_names.extend(c.name for (c, _) in node.inputs[n_args:])
            raw.append((needs, prods, aux_names))
        # keep only produced keys someone later actually reads
        consumed = set(final_keys)
        for needs, _, _ in raw:
            consumed.update(needs)
        self._seg_io = [
            (needs, [k for k in prods if k in consumed], aux_names)
            for needs, prods, aux_names in raw
        ]
        self._fn_cache = {}

    def _seg_run(self, si, is_train):
        """Pure per-segment forward body (traced under fwd and bwd jits)."""
        _, nodes = self.segments[si]
        needs, out_keys, _ = self._seg_io[si]
        program = self.program

        def run(in_vals, rng):
            env = dict(zip(needs, in_vals))
            aux_out = []
            for node in nodes:
                attrs = _node_attrs(program, node, rng)
                ins = [env[(id(c), i)] for (c, i) in node.inputs]
                results = _compute_node(node, attrs, ins, is_train)
                n_outs = node.num_outputs()
                for i, v in enumerate(results[:n_outs]):
                    env[(id(node), i)] = v
                n_args = node._extra.get("n_args", len(node.inputs))
                for _c, v in zip(node.inputs[n_args:], results[n_outs:]):
                    aux_out.append(v)
            return tuple(env[k] for k in out_keys), tuple(aux_out)

        return run

    def _seg_fn(self, si, is_train):
        key = ("fwd", si, is_train)
        if key not in self._fn_cache:
            _M_CACHE_MISSES.inc(segment="seg%d_fwd" % si)
            self._fn_cache[key] = jax.jit(self._seg_run(si, is_train))
        else:
            _M_CACHE_HITS.inc(segment="seg%d_fwd" % si)
        return self._fn_cache[key]

    def _seg_bwd_fn(self, si):
        """Jitted backward for segment si: recompute forward from the
        saved boundary inputs, transpose, and return cotangents ONLY for
        inputs that can reach a gradient variable."""
        key = ("bwd", si)
        if key not in self._fn_cache:
            _M_CACHE_MISSES.inc(segment="seg%d_bwd" % si)
            needs, _, _ = self._seg_io[si]
            diff_idx = tuple(
                i for i, (nid, _o) in enumerate(needs)
                if self._needs_ct.get(nid, False))
            run = self._seg_run(si, True)

            def bwd(in_vals, rng, cts_out):
                diff_vals = tuple(in_vals[i] for i in diff_idx)

                # has_aux keeps aux-state updates (BN running stats)
                # outside the cotangent space, so custom_vjp symbolic-zero
                # fast paths (e.g. BN's one-pass backward) apply on the
                # placed path exactly as on the fused path.
                def f(dv):
                    iv = list(in_vals)
                    for i, v in zip(diff_idx, dv):
                        iv[i] = v
                    return run(tuple(iv), rng)

                _, vjp_fn, _aux = jax.vjp(f, diff_vals, has_aux=True)
                (cts_in,) = vjp_fn(cts_out)
                return cts_in

            self._fn_cache[key] = (jax.jit(bwd), diff_idx)
        else:
            _M_CACHE_HITS.inc(segment="seg%d_bwd" % si)
        return self._fn_cache[key]

    @staticmethod
    def _dev_of(v):
        devs = getattr(v, "devices", None)
        return next(iter(devs())) if callable(devs) else None

    def __call__(self, args_by_name, aux_by_name, rng, is_train,
                 with_vjp=False):
        env = {}
        for name, v in args_by_name.items():
            node = self.program._var_nodes.get(name)
            if node is not None:
                env[(id(node), 0)] = v
        for name, v in aux_by_name.items():
            node = self.program._var_nodes.get(name)
            if node is not None:
                env[(id(node), 0)] = v
        new_aux = {}
        saved = []
        for si, (dev, _nodes) in enumerate(self.segments):
            needs, out_keys, aux_names = self._seg_io[si]
            for k in needs:
                if k not in env:
                    raise MXNetError(
                        "executor: missing input for placed segment")
            in_vals = tuple(jax.device_put(env[k], dev) for k in needs)
            outs, aux_vals = self._seg_fn(si, is_train)(in_vals, rng)
            if with_vjp:
                saved.append((in_vals, aux_vals, rng))
            env.update(zip(out_keys, outs))
            new_aux.update(zip(aux_names, aux_vals))
        outputs = [env[(id(n), i)] for n, i in self.program.output_entries]
        for name in self.program.aux_names:
            if name not in new_aux:
                new_aux[name] = aux_by_name[name]
        return outputs, new_aux, (env, saved)

    def backward(self, env, saved, out_cts):
        """Reverse pass over the segments; returns cotangent env keyed
        like the forward env (var grads live at their var-node keys)."""
        ct_env = {}

        def _accum(k, ct):
            if k in ct_env:
                ct_env[k] = ct_env[k] + jax.device_put(
                    ct, self._dev_of(ct_env[k]))
            else:
                ct_env[k] = ct

        for (n, i), ct in zip(self.program.output_entries, out_cts):
            _accum((id(n), i), ct)
        for si in reversed(range(len(self.segments))):
            dev, _nodes = self.segments[si]
            needs, out_keys, _aux_names = self._seg_io[si]
            in_vals, _aux_vals, rng = saved[si]
            bwd, diff_idx = self._seg_bwd_fn(si)
            if not diff_idx:
                continue  # nothing upstream of this segment needs grads
            cts_out = tuple(
                jax.device_put(ct_env[k], dev) if k in ct_env
                else jnp.zeros_like(env[k])
                for k in out_keys
            )
            cts_in = bwd(in_vals, rng, cts_out)
            for i, ct in zip(diff_idx, cts_in):
                _accum(needs[i], ct)
        return ct_env


_G_SHARED_USES = _tm.gauge(
    "lm.shared_argument_uses", "The largest number of nodes that read ONE "
    "argument of the symbol bound last (a weight a looped stack visits T "
    "times reads T, a head tied to the embedding 2, an ordinary weight 1): "
    "set at bind, nothing per step")


def _argument_uses(program):
    """{argument name: how many node inputs of the program's graph read
    it} (an argument with several readers has ONE gradient, their sum)."""
    uses = {}
    for node in program.nodes:
        n_args = node._extra.get("n_args", len(node.inputs))
        for c, _ in node.inputs[:n_args]:
            if c.is_variable:
                uses[c.name] = uses.get(c.name, 0) + 1
    return uses


def resolve_creation_shapes(symbol, shapes_by_name):
    """For creation ops (_zeros/_ones) whose shape attr has unknown (0)
    dims — MXNet's bind-time-inferred convention, e.g. rnn_cell
    begin_state batch dims — resolve concrete shapes via graph-wide
    inference given the input shapes. Returns a _GraphProgram
    shape_overrides dict. Used by Executor at bind and ShardedTrainStep
    at first call (same program layer, two front doors)."""
    nodes = _topo_order([n for n, _ in symbol._outputs])
    from .ops.utils import as_tuple

    def _shape_attr(n):
        return as_tuple(n.canon_attrs().get("shape")) or ()

    pending = [
        n for n in nodes
        if (not n.is_variable) and not n.inputs and 0 in _shape_attr(n)
    ]
    if not pending:
        return {}
    env = symbol._infer_shape_env(**shapes_by_name)
    return {id(n): env[(id(n), 0)] for n in pending if (id(n), 0) in env}


class Executor:
    """Bound computation: holds arg/grad/aux NDArrays + compiled step fns.

    Parity: reference ``include/mxnet/executor.h`` —
    Forward/Backward/outputs/arg_dict/grad_dict/aux_dict/reshape/
    copy_params_from/set_monitor_callback.
    """

    def __init__(self, symbol, ctx, arg_arrays, grad_arrays, grad_req,
                 aux_arrays, group2ctx=None):
        self._symbol = symbol
        self._ctx = ctx
        overrides = self._resolve_creation_shapes(symbol, arg_arrays)
        self._program = _GraphProgram(symbol, overrides)
        self.arg_arrays = list(arg_arrays)
        self.grad_arrays = list(grad_arrays)
        self.aux_arrays = list(aux_arrays)
        self._arg_names = self._program.arg_names
        self._aux_names = self._program.aux_names
        self._output_names = symbol.list_outputs()
        self._group2ctx = group2ctx or {}
        self._monitor_callback = None
        if isinstance(grad_req, str):
            grad_req = {n: grad_req for n in self._arg_names}
        elif isinstance(grad_req, (list, tuple)):
            grad_req = dict(zip(self._arg_names, grad_req))
        self._grad_req = grad_req
        # names we differentiate wrt (grad buffer attached + req != null)
        self._grad_names = [
            n
            for n, g in zip(self._arg_names, self.grad_arrays)
            if g is not None and self._grad_req.get(n, "null") != "null"
        ]
        self._outputs_list = [None] * len(self._output_names)
        self._stash = None  # (arg_vals, aux_vals, rng) captured at forward()
        self._needs_rng = any(
            (not n.is_variable) and n.op.needs_rng for n in self._program.nodes
        )
        self._placed = self._build_placed()
        if self._placed is not None:
            self._fwd_jit = _instrument_jit(
                self._make_fwd_placed(), "fwd_placed")
            self._fwdbwd_jit = _instrument_jit(
                self._make_fwdbwd_placed(), "fwdbwd_placed")
        else:
            self._fwd_jit = _instrument_jit(self._make_fwd(), "fwd")
            self._fwdbwd_jit = _instrument_jit(self._make_fwdbwd(), "fwdbwd")
        self._pending_train_step = False
        if _tm.enabled():
            _G_SHARED_USES.set(max(_argument_uses(self._program).values(),
                                   default=0))

    def _build_placed(self):
        """ctx_group placement (reference AssignContext/PlaceDevice):
        returns a _PlacedProgram when any node's ctx_group maps through
        group2ctx to a device other than the bind ctx, else None (the
        whole-graph single-device jit stays the fast path)."""
        if not self._group2ctx:
            return None
        default_dev = self._ctx.jax_device
        node_dev = {}
        distinct = False
        for node in self._program.nodes:
            if node.is_variable:
                # variable-only groups count too: simple_bind committed
                # such params to their group's device, and the whole-
                # graph jit would crash on mixed committed inputs
                grp = _ctx_group(node)
                ctx = self._group2ctx.get(grp) if grp else None
                if ctx is not None and ctx.jax_device != default_dev:
                    distinct = True
                continue
            grp = _ctx_group(node)
            ctx = self._group2ctx.get(grp) if grp else None
            dev = ctx.jax_device if ctx is not None else default_dev
            node_dev[id(node)] = dev
            if dev != default_dev:
                distinct = True
        if not distinct:
            return None
        return _PlacedProgram(self._program, node_dev,
                              grad_names=self._grad_names)

    @staticmethod
    def _resolve_creation_shapes(symbol, arg_arrays):
        arg_names = symbol.list_arguments()
        shapes = {
            n: a.shape for n, a in zip(arg_names, arg_arrays) if a is not None
        }
        return resolve_creation_shapes(symbol, shapes)

    # ------------------------------------------------------------------
    # compiled callables
    # ------------------------------------------------------------------
    def _make_fwd(self):
        program = self._program
        arg_names = tuple(self._arg_names)
        aux_names = tuple(self._aux_names)

        @functools.partial(jax.jit, static_argnums=(3,))
        def fwd(arg_vals, aux_vals, rng, is_train):
            args = dict(zip(arg_names, arg_vals))
            aux = dict(zip(aux_names, aux_vals))
            outs, new_aux = program(args, aux, rng, is_train)
            return tuple(outs), tuple(new_aux[n] for n in aux_names)

        return fwd

    def _make_fwdbwd(self):
        program = self._program
        arg_names = tuple(self._arg_names)
        aux_names = tuple(self._aux_names)
        grad_names = tuple(self._grad_names)

        do_mirror = _mirror_enabled()

        @jax.jit
        def fwdbwd(arg_vals, aux_vals, rng, out_grads):
            args = dict(zip(arg_names, arg_vals))
            aux = dict(zip(aux_names, aux_vals))
            fixed = {k: v for k, v in args.items() if k not in grad_names}

            def f(diff_vals):
                a = dict(fixed)
                a.update(dict(zip(grad_names, diff_vals)))
                outs, new_aux = program(a, aux, rng, True)
                return tuple(outs), tuple(new_aux[n] for n in aux_names)

            if do_mirror:
                # memory mirror: trade recompute FLOPs for activation
                # memory exactly where the reference does
                f = jax.checkpoint(f, policy=_mirror_policy)

            diff_vals = tuple(args[n] for n in grad_names)
            # has_aux: aux-state updates ride OUTSIDE the cotangent space
            # (they never carry gradient), so ops whose bwd rule detects
            # symbolic-zero cotangents (BatchNorm's mean/var outputs)
            # skip those terms instead of streaming zero arrays through
            # the graph.
            outs, vjp_fn, new_aux = jax.vjp(f, diff_vals, has_aux=True)
            if out_grads is None:
                cts = tuple(jnp.ones_like(o) for o in outs)
            else:
                cts = tuple(out_grads)
            (grads,) = vjp_fn(cts)
            return outs, new_aux, grads

        return fwdbwd

    def _make_fwd_placed(self):
        placed = self._placed
        arg_names = tuple(self._arg_names)
        aux_names = tuple(self._aux_names)

        def fwd(arg_vals, aux_vals, rng, is_train):
            args = dict(zip(arg_names, arg_vals))
            aux = dict(zip(aux_names, aux_vals))
            outs, new_aux, _ = placed(args, aux, rng, is_train)
            return tuple(outs), tuple(new_aux[n] for n in aux_names)

        return fwd

    def _make_fwdbwd_placed(self):
        placed = self._placed
        arg_names = tuple(self._arg_names)
        aux_names = tuple(self._aux_names)
        grad_names = tuple(self._grad_names)
        var_nodes = self._program._var_nodes

        def fwdbwd(arg_vals, aux_vals, rng, out_grads):
            args = dict(zip(arg_names, arg_vals))
            aux = dict(zip(aux_names, aux_vals))
            outs, new_aux, (env, vjps) = placed(
                args, aux, rng, True, with_vjp=True)
            if out_grads is None:
                cts = tuple(jnp.ones_like(o) for o in outs)
            else:
                cts = tuple(out_grads)
            ct_env = placed.backward(env, vjps, cts)
            grads = []
            for name in grad_names:
                key = (id(var_nodes[name]), 0)
                ct = ct_env.get(key)
                if ct is None:
                    ct = jnp.zeros_like(args[name])
                else:
                    # grad lands where the param lives (its ctx_group
                    # device), like reference arg_grad ctx assignment
                    ct = jax.device_put(
                        ct, _PlacedProgram._dev_of(args[name]))
                grads.append(ct)
            return (tuple(outs), tuple(new_aux[n] for n in aux_names),
                    tuple(grads))

        return fwdbwd

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    def _drain_pending_pulls(self):
        """kvstore-managed weights may have an engine-scheduled pull
        still in flight (the executor-path overlap this framework
        preserves from the reference's prioritized comm engine); drain
        before snapshotting ._data. The inline attr check keeps the
        common no-kvstore case to one comparison per array."""
        for a in self.arg_arrays:
            if a._engine_dep is not None:
                a._drain_engine()

    def forward(self, is_train=False, **kwargs):
        """Parity: Executor::Forward. For a training step the launch is
        deferred so backward() can run forward+backward as ONE fused XLA
        executable (the whole-graph analog of the reference's bulk-exec
        segments); reading .outputs before backward() materializes a
        forward-only run from the same stashed inputs + rng, so results are
        bit-identical either way."""
        if kwargs:
            arg_dict = self.arg_dict
            for k, v in kwargs.items():
                if k not in arg_dict:
                    raise MXNetError("unknown input %s" % k)
                if arg_dict[k]._engine_dep is not None:
                    arg_dict[k]._drain_engine()  # don't race a pull
                if isinstance(v, NDArray):
                    arg_dict[k]._data = v._data
                else:
                    arg_dict[k]._data = nd.array(v)._data
        rng = _random.next_key() if self._needs_rng else None
        self._drain_pending_pulls()
        arg_vals = tuple(a._data for a in self.arg_arrays)
        aux_vals = tuple(a._data for a in self.aux_arrays)
        self._stash = (arg_vals, aux_vals, rng, bool(is_train))
        if is_train and self._grad_names:
            self._pending_train_step = True
            # lazy view: materializes via the outputs property on first
            # element access, so callers using forward()'s return value get
            # fresh data while the fit loop (which ignores it) keeps the
            # single fused fwd+bwd launch.
            return _LazyOutputs(self)
        self._pending_train_step = False
        with _tm.span("executor.forward", train=bool(is_train)):
            t0 = time.perf_counter()
            outs, new_aux = self._fwd_jit(
                arg_vals, aux_vals, rng, bool(is_train))
            _H_STEP_SECONDS.observe(time.perf_counter() - t0, phase="fwd")
        self._set_outputs(outs)
        if is_train:
            for a, v in zip(self.aux_arrays, new_aux):
                a._data = v
        self._run_monitor()
        return self.outputs

    @property
    def outputs(self):
        if self._pending_train_step:
            arg_vals, aux_vals, rng, _ = self._stash
            outs, new_aux = self._fwd_jit(arg_vals, aux_vals, rng, True)
            self._set_outputs(outs)
            # moving-stat aux updates happen on forward in the reference
            # (FMutateInputs); backward recomputes the same values from the
            # stashed aux so there is no double-apply.
            for a, v in zip(self.aux_arrays, new_aux):
                a._data = v
            self._pending_train_step = False
        return self._outputs_list

    def _set_outputs(self, outs):
        for i, v in enumerate(outs):
            if self._outputs_list[i] is None:
                self._outputs_list[i] = NDArray(v)
            else:
                self._outputs_list[i]._data = v
        return self._outputs_list

    def backward(self, out_grads=None):
        """Run the fused forward+backward XLA step and write gradients into
        grad_arrays honoring grad_req (write/add/null). Parity:
        Executor::Backward; grad_req semantics = kWriteTo/kAddTo/kNullOp."""
        if out_grads is not None:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            out_grads = tuple(_as_jax(g) for g in out_grads)
        if not self._grad_names:
            return
        if self._stash is not None:
            arg_vals, aux_vals, rng, _ = self._stash
        else:
            # backward without a prior forward must not snapshot stale
            # weights either
            self._drain_pending_pulls()
            arg_vals = tuple(a._data for a in self.arg_arrays)
            aux_vals = tuple(a._data for a in self.aux_arrays)
            rng = _random.next_key() if self._needs_rng else None
        with _tm.span("executor.fwdbwd"):
            t0 = time.perf_counter()
            outs, new_aux, grads = self._fwdbwd_jit(
                arg_vals, aux_vals, rng, out_grads)
            _H_STEP_SECONDS.observe(time.perf_counter() - t0, phase="fwdbwd")
        self._pending_train_step = False
        self._set_outputs(outs)
        for a, v in zip(self.aux_arrays, new_aux):
            a._data = v
        gmap = dict(zip(self._grad_names, grads))
        for name, garr in zip(self._arg_names, self.grad_arrays):
            if garr is None or name not in gmap:
                continue
            req = self._grad_req.get(name, "write")
            if req == "add":
                garr._data = garr._data + gmap[name]
            elif req == "write":
                garr._data = gmap[name]
        self._run_monitor()

    # ------------------------------------------------------------------
    # dict views (parity executor.py:248-298)
    # ------------------------------------------------------------------
    @property
    def arg_dict(self):
        return dict(zip(self._arg_names, self.arg_arrays))

    @property
    def grad_dict(self):
        return dict(zip(self._arg_names, self.grad_arrays))

    @property
    def aux_dict(self):
        return dict(zip(self._aux_names, self.aux_arrays))

    @property
    def output_dict(self):
        return dict(zip(self._output_names, self.outputs))

    def copy_params_from(self, arg_params, aux_params=None, allow_extra_params=False):
        arg_dict = self.arg_dict
        for name, array in arg_params.items():
            if name in arg_dict:
                array.copyto(arg_dict[name])
            elif not allow_extra_params:
                raise MXNetError("Found name \"%s\" not in executor arguments" % name)
        if aux_params is not None:
            aux_dict = self.aux_dict
            for name, array in aux_params.items():
                if name in aux_dict:
                    array.copyto(aux_dict[name])
                elif not allow_extra_params:
                    raise MXNetError("Found name \"%s\" not in executor aux states" % name)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Return a new executor with new input shapes, sharing parameter
        arrays (parity executor.py:360; the reference shares memory — XLA
        owns buffers here so we share the NDArray handles)."""
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**kwargs)
        new_args = []
        new_grads = []
        for name, arr, garr, shp in zip(
            self._arg_names, self.arg_arrays, self.grad_arrays, arg_shapes
        ):
            if name in kwargs or tuple(arr.shape) != tuple(shp):
                new_args.append(
                    nd.deferred_full(shp, 0, ctx=self._ctx, dtype=arr.dtype))
                new_grads.append(
                    None if garr is None else
                    nd.deferred_full(shp, 0, ctx=self._ctx, dtype=arr.dtype)
                )
            else:
                new_args.append(arr)
                new_grads.append(garr)
        new_aux = []
        for arr, shp in zip(self.aux_arrays, aux_shapes):
            if tuple(arr.shape) != tuple(shp):
                new_aux.append(
                    nd.deferred_full(shp, 0, ctx=self._ctx, dtype=arr.dtype))
            else:
                new_aux.append(arr)
        return Executor(
            self._symbol, self._ctx, new_args, new_grads, self._grad_req,
            new_aux, self._group2ctx
        )

    def set_monitor_callback(self, callback):
        self._monitor_callback = callback

    def _run_monitor(self):
        if self._monitor_callback is None:
            return
        for name, out in zip(self._output_names, self.outputs):
            if out is not None:
                self._monitor_callback(name, out)

    def debug_str(self):
        return self._symbol.debug_str()

    # ------------------------------------------------------------------
    # binding entry points
    # ------------------------------------------------------------------
    @staticmethod
    def bind(symbol, ctx, args, args_grad=None, grad_req="write",
             aux_states=None, group2ctx=None, shared_exec=None):
        if isinstance(ctx, (list, tuple)):
            ctx = ctx[0]
        if not isinstance(ctx, Context):
            ctx = Context(ctx)
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        arg_arrays = _check_arguments(args, arg_names, "args")
        if args_grad is None:
            grad_arrays = [None] * len(arg_names)
        elif isinstance(args_grad, dict):
            grad_arrays = [args_grad.get(n) for n in arg_names]
        else:
            grad_arrays = list(args_grad)
            grad_arrays += [None] * (len(arg_names) - len(grad_arrays))
        if aux_states is None:
            aux_arrays = []
            if aux_names:
                _, _, aux_shapes = symbol.infer_shape(
                    **{n: a.shape for n, a in zip(arg_names, arg_arrays)}
                )
                aux_arrays = [nd.zeros(s, ctx=ctx) for s in aux_shapes]
        elif isinstance(aux_states, dict):
            aux_arrays = [aux_states[n] for n in aux_names]
        else:
            aux_arrays = list(aux_states)
        return Executor(
            symbol, ctx, arg_arrays, grad_arrays, grad_req, aux_arrays, group2ctx
        )

    @staticmethod
    def _var_contexts(symbol, group2ctx):
        """name -> Context for inputs with a ctx_group placement: a
        variable's own ctx_group attr wins, else it inherits its first
        consumer's group (reference AssignContext propagation,
        graph_executor.cc:245-334)."""
        if not group2ctx:
            return {}
        out = {}
        nodes = _topo_order([n for n, _ in symbol._outputs])
        for n in nodes:
            if n.is_variable:
                grp = _ctx_group(n)
                if grp in group2ctx:
                    out[n.name] = group2ctx[grp]
        for n in nodes:
            if n.is_variable:
                continue
            grp = _ctx_group(n)
            if grp not in group2ctx:
                continue
            for (c, _i) in n.inputs:
                if c.is_variable and c.name not in out:
                    out[c.name] = group2ctx[grp]
        return out

    @staticmethod
    def simple_bind(symbol, ctx, grad_req="write", type_dict=None,
                    group2ctx=None, shared_exec=None, **kwargs):
        """Infer shapes/types, declare arg/grad/aux arrays, bind.
        Parity: symbol.py:1114. With group2ctx, params/grads belong to
        their group's device (reference simple_bind honors AssignContext
        when allocating, symbol.py:1114-1210). Every array is born
        deferred (``nd.deferred_full``): a zero that is made on its
        device when something first reads it, and never if it is
        written whole first (``set_params``, a batch, a ``write``
        gradient)."""
        if isinstance(ctx, (list, tuple)):
            ctx = ctx[0]
        if not isinstance(ctx, Context):
            ctx = Context(ctx)
        arg_shapes, _, aux_shapes = symbol.infer_shape(**kwargs)
        arg_types, _, aux_types = symbol.infer_type(**(type_dict or {}))
        arg_names = symbol.list_arguments()
        var_ctx = Executor._var_contexts(symbol, group2ctx)
        # share param arrays with shared_exec when shapes match (bucketing)
        shared = shared_exec.arg_dict if shared_exec is not None else {}
        arg_arrays = []
        for name, shape, dtype in zip(arg_names, arg_shapes, arg_types):
            if name in shared and tuple(shared[name].shape) == tuple(shape):
                arg_arrays.append(shared[name])
            else:
                arg_arrays.append(nd.deferred_full(
                    shape, 0, ctx=var_ctx.get(name, ctx), dtype=dtype))
        req_of = (
            (lambda n: grad_req)
            if isinstance(grad_req, str)
            else (lambda n: grad_req.get(n, "null"))
            if isinstance(grad_req, dict)
            else (lambda n: dict(zip(arg_names, grad_req)).get(n, "null"))
        )
        grad_arrays = [
            nd.deferred_full(shape, 0, ctx=var_ctx.get(name, ctx), dtype=dtype)
            if req_of(name) != "null" else None
            for name, shape, dtype in zip(arg_names, arg_shapes, arg_types)
        ]
        shared_aux = shared_exec.aux_dict if shared_exec is not None else {}
        aux_names = symbol.list_auxiliary_states()
        aux_arrays = []
        for name, shape, dtype in zip(aux_names, aux_shapes, aux_types):
            if name in shared_aux and tuple(shared_aux[name].shape) == tuple(shape):
                aux_arrays.append(shared_aux[name])
            else:
                # aux states (BN moving stats) live with their owning
                # node's group too — _var_contexts covers them because
                # aux vars appear among consumer-node inputs
                aux_arrays.append(nd.deferred_full(
                    shape, 0, ctx=var_ctx.get(name, ctx), dtype=dtype))
        return Executor(
            symbol, ctx, arg_arrays, grad_arrays, grad_req, aux_arrays, group2ctx
        )


def _check_arguments(args, names, kind):
    if isinstance(args, dict):
        out = []
        for n in names:
            if n not in args:
                raise MXNetError("missing %s: %s" % (kind, n))
            out.append(args[n])
        return out
    args = list(args)
    if len(args) != len(names):
        raise MXNetError(
            "%s length %d != expected %d (%s)" % (kind, len(args), len(names), names)
        )
    return args
