"""Sharded training step: the fused TPU path for Module training.

This is the TPU-native replacement for the reference's §3.1 hot loop
(per-device executors + KVStore push/pull, python/mxnet/module/module.py:432-553
+ model.py:88-117): the ENTIRE step — forward, backward, gradient
allreduce, optimizer update — compiles to one XLA program over a Mesh:

- batch sharded over ``dp`` (DataParallelExecutorGroup.decide_slices →
  jax.sharding with PartitionSpec('dp'))
- params replicated over dp, optionally sharded over ``tp``
  (PlaceDevice/ctx_group → PartitionSpec)
- gradient sync = psum over ICI, inserted by GSPMD from the shardings
  (KVStore device/dist_device_sync → in-XLA allreduce; the reference's
  priority-ordered push overlap becomes XLA latency-hiding scheduling)
- optimizer state optionally sharded over dp (ZeRO-1 / "Automatic
  Cross-Replica Sharding of Weight Update", PAPERS.md)

The optimizer update is NOT re-implemented here: the step function
traces straight through ``Optimizer.update`` of ANY registered optimizer
(reference python/mxnet/optimizer.py surface) by wrapping the traced
jax values in NDArrays — the imperative op layer nests fine under jit.
Step-dependent quantities (learning rate after scheduling, update count
``t`` for Adam-style bias correction) enter the compiled program as
traced scalars so one compilation serves every step.
"""
from __future__ import annotations

import contextlib
import logging
import os

import numpy as np

from .. import telemetry as _tm
from ..base import MXNetError
from ..base import bucket_bytes_env as _env_bucket_bytes

_M_STEPS = _tm.counter(
    "train_step.steps", "Optimizer steps dispatched through the fused "
    "ShardedTrainStep path")
_M_FLAT_LOWERINGS = _tm.counter(
    "train_step.flat_update_lowerings", "Traces of the flat AMP update "
    "(one per trace of a step, nothing per step); labels: form (slab: "
    "ops/optimizer_ops.slab_update, one XLA fusion a bucket; optimizer: "
    "traced through the optimizer's own update), calls (updates a step, "
    "one a bucket), tile_rows (rows of 128 lanes a shard is a multiple "
    "of)")
_H_BUCKET_BYTES = _tm.histogram(
    "kvstore.bucket_bytes", "Payload bytes per coalesced gradient bucket "
    "(kvstore GradBucketer flushes and fused flat-update plan buckets)")


# A shard of the AMP path's slabs is whole (16, 128) tiles, a bf16 array's
# on the chip and so every operand's of the update: the float32 masters and
# state, the bf16 gradient and the bf16 copy that is all-gathered. The plan
# pads to it once, so a step pads, slices and copies nothing round its
# update, and the all-gather of a shard needs no re-tiling (the parent's
# shards, a multiple of dp only, cost a ``reduce`` a gather: PERF.md 7).
# The float32 path keeps its shards as wide as their elements.
_LANES = 128
_AMP_SHARD_ALIGN = 16 * _LANES


def _slab_axes(shape):
    """The order in which a key's axes are laid into its slab, or None
    for the order they are in. Trailing axes that together are narrower
    than one row of lanes go first (a conv filter ``[O, I, 3, 3]`` lies in
    its slab as ``[3, 3, O, I]``): the chip pads a minor dimension to 128
    lanes, so a row-major ``reshape(-1)`` of such a filter goes through a
    copy 40 times its size, in and out, every step, while the chip itself
    holds the filter with the large axes minor and the reordered reshape
    is a re-tiling. A slab is elementwise to everything that reads it, so
    the order inside a key is the plan's to choose."""
    for k in range(1, len(shape)):
        narrow = int(np.prod(shape[k:]))
        if narrow < _LANES:
            if narrow == 1:
                return None
            return tuple(range(k, len(shape))) + tuple(range(k))
    return None


def _to_slab(x):
    """A key's array (jax or numpy) as the 1-D run it is in its slab."""
    axes = _slab_axes(x.shape)
    return (x if axes is None else x.transpose(axes)).reshape(-1)


def _from_slab(run, shape):
    """Inverse of ``_to_slab``: a key's 1-D run back in ``shape``."""
    axes = _slab_axes(shape)
    if axes is None:
        return run.reshape(shape)
    back = tuple(int(a) for a in np.argsort(axes))
    return run.reshape(tuple(shape[a] for a in axes)).transpose(back)


class _FlatBucket:
    """One size-capped flat slab of the parameter space: contiguous
    per-key views carved out of a single (padded) 1-D buffer, all
    sharing one (dtype, lr_mult, wd_mult) signature so a single set of
    fused-optimizer scalar kwargs is valid for the whole slab. A view's
    elements lie in the order ``_to_slab`` gives them."""

    __slots__ = ("rep_index", "dtype", "views", "size", "padded")

    def __init__(self, rep_index, dtype, views, dp, shard_align):
        self.rep_index = rep_index  # index whose _fused_kwargs apply
        self.dtype = dtype
        self.views = views  # [(index, name, offset, size, shape)]
        self.size = sum(v[3] for v in views)
        # pad so the slab splits evenly into dp contiguous shards, each
        # a multiple of shard_align (the pad is zeros and stays zeros)
        whole = dp * shard_align
        self.padded = -(-self.size // whole) * whole


class _FlatUpdatePlan:
    """Bucketing layout for the flat fused update (tentpole part 2/3).

    Groups params by (dtype, lr_mult, wd_mult), walks each group in
    REVERSE key order (backward produces late keys' gradients first, so
    their buckets' collectives can fly while earlier layers are still
    differentiating), and packs size-capped buckets."""

    def __init__(self, param_names, shapes, dtypes, optimizer, dp,
                 bucket_bytes, comm_itemsize=None, shard_align=1):
        self._shard_align = shard_align
        groups = {}
        order = []
        for i, name in enumerate(param_names):
            key = (dtypes[name],
                   optimizer._mult_for(i, optimizer.lr_mult),
                   optimizer._mult_for(i, optimizer.wd_mult))
            if key not in groups:
                groups[key] = []
                order.append(key)
            groups[key].append((i, name))
        self.buckets = []
        for key in order:
            dtype = key[0]
            # the size cap counts bytes as they move on the WIRE: under
            # AMP the slab dtype is the fp32 master but gradients and the
            # gathered weight copy travel bf16, so the caller passes
            # comm_itemsize=2 and MXTPU_BUCKET_BYTES keeps meaning actual
            # collective payload bytes
            itemsize = comm_itemsize or np.dtype(dtype).itemsize
            cap = max(1, bucket_bytes // itemsize)
            pending = []
            pending_elems = 0
            for i, name in reversed(groups[key]):
                size = int(np.prod(shapes[name])) if shapes[name] else 1
                if pending and pending_elems + size > cap:
                    self._close(pending, dtype, dp)
                    pending, pending_elems = [], 0
                pending.append((i, name, size, shapes[name]))
                pending_elems += size
            if pending:
                self._close(pending, dtype, dp)
        self.by_name = {}
        for bi, b in enumerate(self.buckets):
            for (i, name, off, size, shape) in b.views:
                self.by_name[name] = (bi, off, size, shape)
        for b in self.buckets:
            _H_BUCKET_BYTES.observe(
                b.size * np.dtype(b.dtype).itemsize, path="flat_update")

    def _close(self, pending, dtype, dp):
        views = []
        off = 0
        for (i, name, size, shape) in pending:
            views.append((i, name, off, size, shape))
            off += size
        self.buckets.append(_FlatBucket(pending[0][0], dtype, views, dp,
                                        self._shard_align))


class _EveryKeyCount(dict):
    """Stand-in for Optimizer._index_update_count during tracing: every
    parameter reads the SAME traced step counter ``t`` (the fused step
    updates all params exactly once per step, so the per-index counts
    the reference tracks are all equal to t here)."""

    def __init__(self, t):
        super().__init__()
        self._t = t

    def __getitem__(self, key):
        return self._t

    def __setitem__(self, key, value):
        pass

    def __contains__(self, key):
        return True


def amp_requested():
    """Whether ``MXTPU_AMP`` asks for bf16 mixed precision. A value that
    is neither bf16 nor a spelling of "off" raises: running fp32 under
    a request nobody understood would be a silent wrong answer."""
    req = os.environ.get("MXTPU_AMP", "").lower()
    if req in ("bf16", "bfloat16"):
        return True
    if req not in ("", "0", "off", "none", "fp32", "f32", "float32"):
        raise MXNetError("MXTPU_AMP=%s not understood (only bf16)" % req)
    return False


def _wrap_state(state, NDArray):
    if state is None:
        return None
    if isinstance(state, tuple):
        return tuple(_wrap_state(s, NDArray) for s in state)
    return NDArray(state)


def _unwrap_state(state):
    if state is None:
        return None
    if isinstance(state, tuple):
        return tuple(_unwrap_state(s) for s in state)
    return state._data


def _abstract(args):
    """Shape, dtype and sharding of every array in ``args``: what a
    lowering needs, and all that is left of donated arguments once they
    are dispatched. An uncommitted array keeps no sharding, as in the
    dispatch itself."""
    import jax

    def one(x):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype,
            sharding=x.sharding if getattr(x, "committed", True) else None)

    return jax.tree_util.tree_map(one, args)


def _keep_dtype(new, old):
    """Cast an updated weight / state tree back to the dtype it is
    stored in. lr and t enter the step as traced f32 scalars, so the
    update of a reduced-precision weight (a bf16 symbol's parameters)
    computes in f32; without the cast back the step would hand f32
    weights to its own next call — a retrace, then a dtype clash in the
    first bf16 op. A no-op for f32 training."""
    if new is None:
        return None
    if isinstance(new, tuple):
        return tuple(_keep_dtype(n, o) for n, o in zip(new, old))
    return new.astype(old.dtype)


class ShardedTrainStep:
    """Compile a Symbol's full train step over a Mesh.

    Wraps the same _GraphProgram the Executor uses, but jits it with
    sharding constraints instead of per-device loops. Loss convention:
    sum of outputs drives the vjp (the *Output loss heads carry their own
    backward, like Executor.backward); the optimizer's rescale_grad
    normalizes by global batch exactly as the reference's updater does.
    """

    def __init__(self, symbol, mesh, optimizer=None, param_specs=None,
                 data_names=("data",), label_names=("softmax_label",),
                 dtype=None, zero1=False, flat_update=None):
        from jax.sharding import PartitionSpec as P

        from ..executor import _GraphProgram

        self.symbol = symbol
        self.mesh = mesh
        self.optimizer = optimizer
        self.program = _GraphProgram(symbol)
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.data_names = list(data_names)
        self.label_names = list(label_names)
        self.param_names = [
            n for n in self.arg_names
            if n not in self.data_names + self.label_names
        ]
        # ZeRO-1: shard otherwise-replicated optimizer state over dp when
        # the leading dim divides evenly (opt-in: changes layout only, not
        # numerics — each dp rank updates its state shard then the
        # all-gather is implicit in the next step's reads).
        self.zero1 = zero1
        # parameter shardings: default replicated; caller may pass
        # name -> PartitionSpec (tp-sharded layers)
        self.param_specs = dict(param_specs or {})
        self._batch_spec = P("dp")
        self._step = None
        self._needs_rng = any(
            (not n.is_variable) and n.op.needs_rng
            for n in self.program.nodes
        )
        # -- flat bucketed/sharded update (arXiv:2004.13336) ------------
        # flat_mode: None = legacy per-param update;
        # "shard" = each dp replica updates its contiguous 1/N shard of
        #   the flat param+state space inside shard_map, state is
        #   materialized sharded (1/N per device), updated weights are
        #   all-gathered in-step;
        # "replicated" = identical flat layout and identical shard-width
        #   update body, but run on every replica via a scan over the dp
        #   chunks with full-size state — the bitwise-matched baseline
        #   the sharded mode is tested against (same chunk width ⇒ same
        #   XLA elementwise codegen; full-width codegen may contract
        #   mul+add into FMA differently, which is why the baseline is
        #   chunk-matched rather than the monolithic legacy update).
        self.flat_bucket_bytes = _env_bucket_bytes()
        dp = mesh.shape.get("dp", 1)
        non_dp = 1
        for ax, n in mesh.shape.items():
            if ax != "dp":
                non_dp *= n
        eligible = (
            optimizer is not None
            and getattr(optimizer, "elementwise_update", False)
            and dp > 1
            and non_dp == 1
            and not self.param_specs
            and not zero1  # explicit ZeRO-1 request → legacy layout
            and self.flat_bucket_bytes > 0
        )
        if flat_update is False or not eligible:
            self.flat_mode = None
        else:
            self.flat_mode = (
                "shard"
                if os.environ.get("MXTPU_SHARD_UPDATE", "1") != "0"
                else "replicated")
            logging.getLogger(__name__).info(
                "fused update path: flat bucketed (%s, dp=%d, "
                "MXTPU_BUCKET_BYTES=%d)", self.flat_mode, dp,
                self.flat_bucket_bytes)
        self._flat_plan = None  # built lazily from placed param shapes
        # -- bf16 AMP (ISSUE 8 tentpole) --------------------------------
        # forward/backward in bf16, fp32 master weights living as flat
        # slabs in opt_state, bf16 gradient + weight collectives, dynamic
        # loss scaling. Rides the flat update exclusively: the masters
        # ARE the flat slabs, so AMP without the flat path has nowhere to
        # keep fp32 truth.
        self.amp = amp_requested()
        if self.amp and self.flat_mode is None:
            # training fp32 under a bf16 request would be a silent
            # wrong answer (and a silent 2x on every step)
            raise MXNetError(
                "MXTPU_AMP=bf16 cannot engage: it requires the flat "
                "fused-update path (elementwise optimizer, dp>1, "
                "MXTPU_BUCKET_BYTES>0, no tp/zero1, no borrowing "
                "module); got dp=%d" % dp)
        if self.amp:
            logging.getLogger(__name__).info(
                "AMP: bf16 compute + fp32 master slabs (%s mode)",
                self.flat_mode)
        self.amp_cast_data = os.environ.get(
            "MXTPU_AMP_CAST_DATA", "1") != "0"
        self.amp_scale_init = float(
            os.environ.get("MXTPU_LOSS_SCALE", str(2.0 ** 15)))
        self.amp_scale_window = int(
            os.environ.get("MXTPU_LOSS_SCALE_WINDOW", "2000"))
        self.amp_scale_max = 2.0 ** 24
        # -- training guardrails (resilience/guardrail.py) --------------
        # guard=True makes the step (a) emit a (loss, grad_norm²,
        # gate_ok) diag output head and (b) apply the AMP-style
        # branchless select — generalized to fp32 — so a non-finite or
        # out-of-threshold gradient updates NOTHING, bitwise.
        # guard_threshold is the host-side grad-norm² bound the
        # GuardrailMonitor refreshes at step boundaries; it rides into
        # the compiled program as a traced scalar (no recompiles), inf
        # means "gate on non-finite only" (detector warmup). fit() arms
        # this AFTER construction (guardrails="auto"), re-jitting the
        # already-lazy step wrapper.
        self.guard = False
        self.guard_threshold = float("inf")

    # ------------------------------------------------------------------
    def _spec_for(self, name):
        from jax.sharding import PartitionSpec as P

        return self.param_specs.get(name, P())

    def _sharding_for(self, name):
        from jax.sharding import NamedSharding

        return NamedSharding(self.mesh, self._spec_for(name))

    def _state_sharding_for(self, name, arr):
        """Opt-state sharding: param's spec, or dp-sharded under ZeRO-1."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = self._spec_for(name)
        if (self.zero1 and spec == P() and arr.ndim >= 1
                and arr.shape[0] % self.mesh.shape["dp"] == 0):
            spec = P("dp")
        return NamedSharding(self.mesh, spec)

    # -- flat bucketed/sharded update layer -----------------------------
    @staticmethod
    def _flat_key(bucket_index):
        """Opt-state dict key of one flat bucket's state slab (the dict
        otherwise maps param name -> state; flat slabs span params)."""
        return "__flat__%d" % bucket_index

    # AMP additions to the opt_state dict: fp32 master weight slab per
    # bucket (same layout/sharding as the state slabs) plus two
    # replicated device scalars — the live loss scale and the count of
    # consecutive finite steps. Living in opt_state means they ride the
    # K-step scan carry, buffer donation, and checkpointing for free.
    AMP_SCALE_KEY = "__amp_scale__"
    AMP_GOOD_KEY = "__amp_good__"

    @staticmethod
    def _master_key(bucket_index):
        return "__master__%d" % bucket_index

    def amp_cast_params(self, params):
        """bf16 working copies of fp32 params (the arrays the forward/
        backward consumes under AMP); non-f32 entries pass through."""
        import jax
        import jax.numpy as jnp

        if not self.amp:
            return params
        out = {}
        for n, p in params.items():
            if p.dtype == jnp.float32:
                out[n] = jax.device_put(
                    jnp.asarray(p, jnp.bfloat16), self._sharding_for(n))
            else:
                out[n] = p
        return out

    def build_amp_master_state(self, params_by_name, scale=None,
                               good=0.0):
        """Pack full-shape fp32 params into master slabs + the scale
        scalars. `params_by_name` must be fp32 truth (host or device);
        `scale`/`good` seed the loss scaler (fresh init by default)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        plan = self._flat_plan
        assert plan is not None, "flat plan not built yet"
        sharding = self._flat_state_sharding()
        names = [[name for (_i, name, _o, _s, _sh) in b.views]
                 for b in plan.buckets]
        pads = [b.padded - b.size for b in plan.buckets]
        state = {}
        mesh_devices = set(self.mesh.devices.flat)
        if all(isinstance(params_by_name[n], jax.Array)
               and params_by_name[n].sharding.device_set <= mesh_devices
               for bucket in names for n in bucket):
            # already on the mesh (make_state's placed params): packed
            # there by one program, nothing fetched and sent back
            def pack(buckets):
                slabs = []
                for parts, pad in zip(buckets, pads):
                    flats = [_to_slab(p.astype(jnp.float32))
                             for p in parts]
                    if pad:
                        flats.append(jnp.zeros((pad,), jnp.float32))
                    slabs.append(jnp.concatenate(flats))
                return slabs

            slabs = jax.jit(pack, out_shardings=[sharding] * len(names))(
                [[params_by_name[n] for n in bucket] for bucket in names])
            if _tm.enabled():
                _tm.note_const(4 * sum(pads))
            for bi, slab in enumerate(slabs):
                state[self._master_key(bi)] = slab
        else:
            for bi, bucket in enumerate(names):
                parts = [_to_slab(np.asarray(params_by_name[name],
                                             np.float32))
                         for name in bucket]
                if pads[bi]:
                    parts.append(np.zeros((pads[bi],), np.float32))
                state[self._master_key(bi)] = jax.device_put(
                    self.count_h2d(np.concatenate(parts)), sharding)
        rep = NamedSharding(self.mesh, P())
        state[self.AMP_SCALE_KEY] = jax.device_put(self.count_h2d(
            np.asarray(self.amp_scale_init if scale is None else scale,
                       np.float32)), rep)
        state[self.AMP_GOOD_KEY] = jax.device_put(
            self.count_h2d(np.asarray(good, np.float32)), rep)
        return state

    def master_params_named(self, opt_state):
        """fp32 master weights carved back to per-param shapes and
        axis order (lazy device slices — the fp32 truth for
        metrics/checkpoints)."""
        plan = self._flat_plan
        assert plan is not None, "flat plan not built yet"
        out = {}
        for bi, b in enumerate(plan.buckets):
            m = opt_state[self._master_key(bi)]
            for (_i, name, off, size, shape) in b.views:
                out[name] = _from_slab(m[off:off + size], shape)
        return out

    def master_params_placed(self, opt_state):
        """Masters as full fp32 params at their param shardings — what a
        demoted (non-flat, non-AMP) run continues from."""
        import jax

        named = self.master_params_named(opt_state)
        return {n: jax.device_put(
                    self.count_h2d(np.asarray(v, np.float32)),
                    self._sharding_for(n))
                for n, v in named.items()}

    def amp_state_blob(self, opt_state):
        """Host snapshot of the scaler scalars for checkpoints."""
        return {
            "scale": float(np.asarray(opt_state[self.AMP_SCALE_KEY])),
            "good": float(np.asarray(opt_state[self.AMP_GOOD_KEY])),
        }

    def _ensure_flat_plan(self, params):
        if self._flat_plan is None:
            shapes = {n: tuple(params[n].shape) for n in self.param_names}
            dtypes = {n: str(params[n].dtype) for n in self.param_names}
            comm_itemsize, shard_align = None, 1
            if self.amp:
                # the plan describes the fp32 MASTER slabs regardless of
                # whether it is built from fp32 params (make_state) or
                # their bf16 working copies (step trace) — same layout
                # either way; the cap counts bf16 wire bytes
                dtypes = {n: ("float32" if d == "bfloat16" else d)
                          for n, d in dtypes.items()}
                comm_itemsize = 2
                shard_align = _AMP_SHARD_ALIGN
            self._flat_plan = _FlatUpdatePlan(
                self.param_names, shapes, dtypes, self.optimizer,
                self.mesh.shape["dp"], self.flat_bucket_bytes,
                comm_itemsize=comm_itemsize, shard_align=shard_align)
        return self._flat_plan

    def _flat_state_sharding(self):
        """State-slab sharding: each dp replica materializes only its
        contiguous 1/N shard in "shard" mode; the "replicated" baseline
        keeps full slabs everywhere (that redundancy is what the sharded
        mode removes)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = P("dp") if self.flat_mode == "shard" else P()
        return NamedSharding(self.mesh, spec)

    def flat_state_to_named(self, opt_state):
        """Carve the flat state slabs back into the per-param nested
        trees the legacy layout uses (lazy device-side slices; callers
        numpy-ify off-thread). Checkpoints and save_optimizer_states
        always store THIS layout, so snapshots are layout-independent:
        a run with sharding on resumes with it off and vice versa."""
        plan = self._flat_plan
        assert plan is not None, "flat plan not built yet"

        def _slice(st, off, size, shape):
            if st is None:
                return None
            if isinstance(st, tuple):
                return tuple(_slice(s, off, size, shape) for s in st)
            return _from_slab(st[off:off + size], shape)

        named = {}
        for bi, b in enumerate(plan.buckets):
            st = opt_state.get(self._flat_key(bi))
            for (_i, name, off, size, shape) in b.views:
                named[name] = _slice(st, off, size, shape)
        return named

    def named_state_to_flat(self, named):
        """Inverse of flat_state_to_named: pack per-param (host) state
        trees into device-placed flat slabs, each key in its slab's
        order and each slab zero-padded to the plan's width (pad lanes
        stay exactly zero under every elementwise_update optimizer, so
        they never leak into views)."""
        import jax

        plan = self._flat_plan
        assert plan is not None, "flat plan not built yet"
        sharding = self._flat_state_sharding()

        def _pack(parts, pad, dtype):
            if all(p is None for p in parts):
                return None
            if isinstance(parts[0], tuple):
                return tuple(
                    _pack([p[j] for p in parts], pad, dtype)
                    for j in range(len(parts[0])))
            flats = [_to_slab(np.asarray(p)) for p in parts]
            leaf_dtype = flats[0].dtype
            if pad:
                flats.append(np.zeros((pad,), leaf_dtype))
            return jax.device_put(
                self.count_h2d(np.concatenate(flats)), sharding)

        state = {}
        for bi, b in enumerate(plan.buckets):
            try:
                parts = [named[name] for (_i, name, _o, _s, _sh) in b.views]
            except KeyError as exc:
                raise KeyError(
                    "optimizer state for param %s missing from the named "
                    "snapshot — the checkpoint does not match this "
                    "symbol's parameter set" % (exc,))
            state[self._flat_key(bi)] = _pack(
                parts, b.padded - b.size, b.dtype)
        return state

    def opt_state_shard_info(self, opt_state):
        """(total_elements, resident_elements) across the optimizer
        state tree, where *resident* counts what THIS process's first
        addressable device actually materializes. The 1/N-memory claim
        of the sharded update is exactly ``resident ≈ total / dp`` —
        tests at each elastic world size assert on this surface instead
        of groping at device allocator stats."""
        total = 0
        resident = 0

        def _walk(leaf):
            nonlocal total, resident
            if leaf is None:
                return
            if isinstance(leaf, tuple):
                for part in leaf:
                    _walk(part)
                return
            total += int(leaf.size)
            shards = getattr(leaf, "addressable_shards", None)
            if shards:
                resident += int(shards[0].data.size)
            else:
                resident += int(leaf.size)

        for leaf in (opt_state or {}).values():
            _walk(leaf)
        return total, resident

    def disable_flat_update(self, opt_state):
        """Demote to the legacy per-param update (borrow_optimizer /
        BucketingModule: borrowers share a param-name SUBSET, which the
        flat slabs cannot express). Converts the flat state back to
        per-name placement and invalidates compiled steps; returns the
        converted opt_state dict."""
        if self.flat_mode is None:
            return opt_state
        import jax

        named = self.flat_state_to_named(opt_state)

        def _place(name, s):
            if s is None:
                return None
            if isinstance(s, tuple):
                return tuple(_place(name, x) for x in s)
            host = self.count_h2d(np.asarray(s))
            return jax.device_put(host,
                                  self._state_sharding_for(name, host))

        placed = {n: _place(n, s) for n, s in named.items()}
        self.flat_mode = None
        # AMP cannot outlive the flat path (the masters ARE the slabs);
        # callers reconstitute fp32 params via master_params_placed()
        # BEFORE this conversion drops the master/scale keys
        self.amp = False
        self._step = None
        return placed

    def batch_sharding(self):
        from jax.sharding import NamedSharding

        return NamedSharding(self.mesh, self._batch_spec)

    # ------------------------------------------------------------------
    def count_h2d(self, host):
        """``host``, a numpy array on its way to the mesh, counted into
        ``device.h2d_bytes`` while telemetry is on."""
        if _tm.enabled():
            _tm.note_h2d(host.nbytes, self.mesh.devices.flat[0])
        return host

    def place_params(self, arg_arrays_by_name, aux_arrays_by_name):
        """Parameters and auxiliary states on the mesh by spec: what
        nobody has read yet is made there, the rest is put there
        (``ndarray.place``; an NDArray that held a draw holds nothing
        after). Accepts numpy arrays or NDArrays; returns dicts of
        jax.Arrays."""
        from .. import ndarray as ndmod

        names = list(self.param_names) + list(self.aux_names)
        with _tm.span("train_step.place_params"):
            placed = dict(zip(names, ndmod.place(
                [arg_arrays_by_name[n] for n in self.param_names]
                + [aux_arrays_by_name[n] for n in self.aux_names],
                [self._sharding_for(n) for n in names])))
        return ({n: placed[n] for n in self.param_names},
                {n: placed[n] for n in self.aux_names})

    def make_state(self, params):
        """Build optimizer state via the optimizer's OWN create_state on
        a float32 stand-in of each weight (a deferred zero: shape, type
        and device, no buffer), made where it lives (ZeRO-1 aware):
        what create_state declares as a constant is made on the mesh,
        the whole tree by one program and without a host array; what it
        computed is already on a device and moves from there."""
        with _tm.span("train_step.make_state"):
            return self._make_state(params)

    def _make_state(self, params):
        import jax

        from .. import ndarray as ndmod

        if self.optimizer is None:
            return {}
        if self.flat_mode is not None:
            plan = self._ensure_flat_plan(params)
            sharding = self._flat_state_sharding()
            state = self._place_fresh_state(
                {self._flat_key(bi): self.optimizer.create_state_flat(
                    b.rep_index, b.padded, dtype=b.dtype)
                 for bi, b in enumerate(plan.buckets)},
                lambda key, s: sharding)
            state = {k: v for k, v in state.items() if v is not None}
            if self.amp:
                # params here must be fp32 truth (callers pass the placed
                # fp32 params BEFORE amp_cast_params) — they become the
                # master slabs
                state.update(self.build_amp_master_state(params))
            return state
        # the stand-ins belong to one of this process's mesh devices, so
        # whatever a create_state computes from one is made there
        home = ndmod._ctx_of_jax_device(next(
            d for d in self.mesh.devices.flat
            if d.process_index == jax.process_index()))
        return self._place_fresh_state(
            {name: self.optimizer.create_state(
                i, ndmod.deferred_full(params[name].shape, 0, ctx=home))
             for i, name in enumerate(self.param_names)},
            self._state_sharding_for)

    def _place_fresh_state(self, tree, sharding_for):
        """``tree`` (key -> None, NDArray or nested tuples, as
        create_state returns them) on the mesh, each leaf where
        ``sharding_for(key, leaf)`` says. Leaves nobody has read are
        constants: all of them come out of ONE program on the mesh
        (``ndarray.make_deferred``), none crosses from the host."""
        import jax

        from .. import ndarray as ndmod

        consts = []

        def _place(key, s):
            if s is None:
                return None
            if isinstance(s, tuple):
                return tuple(_place(key, x) for x in s)
            sharding = sharding_for(key, s)
            buf = s._buf
            if isinstance(buf, ndmod._Deferred):
                consts.append(ndmod._Deferred(
                    buf.shape, buf.dtype, buf.value, sharding))
                return len(consts) - 1  # filled in below
            if _tm.enabled():
                ndmod._note_crossing(buf, self.mesh.devices.flat[0])
            return jax.device_put(buf, sharding)

        placed = {key: _place(key, s) for key, s in tree.items()}
        made = ndmod.make_deferred(consts)

        def _fill(s):
            if isinstance(s, tuple):
                return tuple(_fill(x) for x in s)
            return made[s] if isinstance(s, int) else s

        return {key: _fill(s) for key, s in placed.items()}

    def init(self, arg_shapes_by_name, initializer, seed=0):
        """Allocate + initialize sharded params/aux/opt-state on the mesh."""
        host_params = {}
        for name in self.param_names:
            shape = arg_shapes_by_name[name]
            host = np.zeros(shape, np.float32)

            class _Arr:
                def __init__(self, a):
                    self._a = a
                    self.shape = a.shape
                    self.size = a.size
                    self.dtype = a.dtype

                def __setitem__(self, k, v):
                    self._a[k] = v

                def asnumpy(self):
                    return self._a

            wrapper = _Arr(host)
            initializer(name, wrapper)
            host_params[name] = host
        _, _, aux_shapes = self.symbol.infer_shape(**arg_shapes_by_name)
        host_aux = {}
        for name, shape in zip(self.aux_names, aux_shapes):
            host_aux[name] = (
                np.ones(shape, np.float32)
                if name.endswith("var")
                else np.zeros(shape, np.float32)
            )
        params, aux = self.place_params(host_params, host_aux)
        opt_state = self.make_state(params)
        if self.amp:
            params = self.amp_cast_params(params)
        return params, aux, opt_state

    # ------------------------------------------------------------------
    @contextlib.contextmanager
    def _patched_optimizer(self, lr, t):
        """Patch the optimizer's step-dependent attributes with traced
        stand-ins for the duration of a trace (only runs at trace time),
        so the SAME compiled program is valid for every step: lr comes
        from the host scheduler each call, t drives Adam-style bias
        correction in-graph."""
        opt = self.optimizer
        saved_lr = opt.lr
        saved_sched = opt.lr_scheduler
        saved_counts = opt._index_update_count
        saved_num_update = opt.num_update
        opt.lr = lr
        opt.lr_scheduler = None  # host computes the scheduled lr
        opt._index_update_count = _EveryKeyCount(t)
        opt._update_count = lambda index: None  # instance shadow
        try:
            yield opt
        finally:
            del opt.__dict__["_update_count"]
            opt.lr = saved_lr
            opt.lr_scheduler = saved_sched
            opt._index_update_count = saved_counts
            opt.num_update = saved_num_update

    def _apply_optimizer(self, params, grads, opt_state, lr, t):
        """Trace through Optimizer.update for every param (legacy
        per-key layout; see _apply_optimizer_flat for the bucketed
        path)."""
        from ..ndarray import NDArray

        opt = self.optimizer
        new_params, new_state = {}, {}
        if opt is None:
            for name in self.param_names:
                new_params[name] = params[name] - lr * grads[name]
            return new_params, new_state

        with self._patched_optimizer(lr, t):
            for i, name in enumerate(self.param_names):
                w = NDArray(params[name])
                g = NDArray(grads[name])
                st = _wrap_state(opt_state.get(name), NDArray)
                opt.update(i, w, g, st)
                new_params[name] = _keep_dtype(w._data, params[name])
                if st is not None:
                    new_state[name] = _keep_dtype(
                        _unwrap_state(st), opt_state[name])
            # params/state owned by a sharing module (BucketingModule:
            # the owner dict may cover a superset of this symbol's args)
            # pass through untouched
            for name in params:
                if name not in new_params:
                    new_params[name] = params[name]
            for name in opt_state:
                if name not in new_state:
                    new_state[name] = opt_state[name]
        return new_params, new_state

    def _flat_body(self, bucket, w_c, g_c, st_c, lr, t):
        """One optimizer step on a width-S chunk of a flat bucket.

        Shared verbatim by BOTH flat modes: in "shard" mode it is the
        shard_map per-device body (S = padded/dp); in "replicated" mode
        the lax.scan body walks the same dp chunks of width S. Chunk
        widths matching is what makes the two modes bitwise-equal — XLA
        contracts mul+add into FMA per fusion width, so a full-width
        replicated update would round differently than the sharded one.
        The optimizer attrs are re-pointed at THIS scope's tracers so
        shard_map never closes over outer-scope values."""
        from ..ndarray import NDArray

        opt = self.optimizer
        opt.lr = lr
        opt._index_update_count = _EveryKeyCount(t)
        w = NDArray(w_c)
        g = NDArray(g_c)
        st = _wrap_state(st_c, NDArray)
        opt.update(bucket.rep_index, w, g, st)
        return (_keep_dtype(w._data, w_c),
                _keep_dtype(_unwrap_state(st), st_c))

    def _slab_rule(self):
        """The ``ops/optimizer_ops.slab_update`` rule the optimizer names
        for its AMP update, or None for one traced through its own
        ``update``."""
        kind = getattr(self.optimizer, "slab_rule", None)
        if kind == "sgd" and getattr(self.optimizer, "momentum", 0.0):
            kind = "sgd_mom"
        return kind

    def _flat_body_amp(self, bucket, m_c, g_c, st_c, lr, t, inv_scale,
                       finite):
        """One AMP optimizer step on a width-S chunk: bf16 grad in, fp32
        master + state updated, bf16 weight copy out; non-finite steps
        pass old values through bitwise (branchless select).

        Optimizers that declare a `slab_rule` go through
        ops/optimizer_ops.slab_update, the whole unscale / update / select
        / cast chain written once and one XLA fusion over the shard. Other
        elementwise optimizers trace through their own Optimizer.update on
        the unscaled fp32 gradient exactly like `_flat_body`."""
        import jax
        import jax.numpy as jnp

        from ..ndarray import NDArray
        from ..ops.optimizer_ops import slab_update

        opt = self.optimizer
        opt.lr = lr
        opt._index_update_count = _EveryKeyCount(t)
        kind = self._slab_rule()
        if kind is not None:
            kwargs = opt._fused_kwargs(bucket.rep_index)
            lr_eff = kwargs["lr"]
            if kind == "adam":
                tt = opt._index_update_count[bucket.rep_index]
                lr_eff = lr_eff * (
                    (1.0 - opt.beta2 ** tt) ** 0.5
                    / (1.0 - opt.beta1 ** tt))
            states = ()
            if st_c is not None:
                states = st_c if isinstance(st_c, tuple) else (st_c,)
            nm, nst, w16 = slab_update(
                kind, m_c, g_c, states, lr_eff, inv_scale, finite,
                wd=kwargs["wd"], rescale_grad=kwargs["rescale_grad"],
                clip_gradient=kwargs["clip_gradient"],
                momentum=getattr(opt, "momentum", 0.0),
                beta1=getattr(opt, "beta1", 0.9),
                beta2=getattr(opt, "beta2", 0.999),
                epsilon=getattr(opt, "epsilon", 1e-8))
            if st_c is None:
                new_st = None
            elif isinstance(st_c, tuple):
                new_st = tuple(nst)
            else:
                new_st = nst[0]
            return nm, new_st, w16
        # generic elementwise optimizer: unscale to fp32, trace through
        # its own update, select, cast
        g32 = g_c.astype(jnp.float32) * inv_scale
        w = NDArray(m_c)
        g = NDArray(g32)
        st = _wrap_state(st_c, NDArray)
        opt.update(bucket.rep_index, w, g, st)
        keep = finite > jnp.float32(0.5)
        nm = jnp.where(keep, w._data, m_c)
        nst_raw = _unwrap_state(st) if st is not None else None
        new_st = jax.tree_util.tree_map(
            lambda new, old: jnp.where(keep, new, old), nst_raw, st_c)
        return nm, new_st, nm.astype(jnp.bfloat16)

    def _apply_optimizer_flat_amp(self, params, grads, opt_state, lr, t):
        """The AMP twin of _apply_optimizer_flat. Differences:

        - no weight concat: the fp32 masters already live as flat slabs
          in opt_state, so only gradients get flattened per bucket
        - one global finite flag over every flat grad slab gates ALL
          buckets identically (a half-applied step could never be
          resumed consistently)
        - in "shard" mode the all-gather moves the bf16 weight copy —
          half the weight-collective bytes of the fp32 path
        - the loss scaler (scale, good-step count) updates in-graph:
          ×2 after `amp_scale_window` consecutive finite steps, ×0.5
          (floor 1.0) on any non-finite step, which also skips the
          update bitwise-cleanly via the finite select."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        plan = self._ensure_flat_plan(params)
        dp = self.mesh.shape["dp"]
        _M_FLAT_LOWERINGS.inc(
            form="slab" if self._slab_rule() else "optimizer",
            calls=len(plan.buckets), tile_rows=_AMP_SHARD_ALIGN // _LANES)
        scale = opt_state[self.AMP_SCALE_KEY]
        good = opt_state[self.AMP_GOOD_KEY]
        new_params, new_state = {}, {}
        # pass 1: flatten grads (bf16) per bucket + global finite flag
        flat_gs = []
        finite = jnp.asarray(True)
        for b in plan.buckets:
            pad = b.padded - b.size
            g_parts = [_to_slab(grads[name])
                       for (_i, name, _o, _s, _sh) in b.views]
            if pad:
                g_parts.append(jnp.zeros((pad,), g_parts[0].dtype))
            flat_g = jnp.concatenate(g_parts)
            # same hard fusion boundary as the fp32 path: the update
            # consumes materialized slabs, not fused gradient chains
            flat_g = jax.lax.optimization_barrier(flat_g)
            # pin the grad slab replicated: without this the partitioner
            # rebuilds each bucket's concat from partial per-tensor sums
            # with a SECOND full-slab all-reduce (observed on the CPU
            # partitioner at multi-bucket sizes). The fp32 path cannot
            # pin its grad concat (bitwise shard<->replicated parity
            # constraints, see _apply_optimizer_flat); the AMP path has
            # no such cross-mode bitwise contract.
            flat_g = jax.lax.with_sharding_constraint(
                flat_g, NamedSharding(self.mesh, P()))
            finite = jnp.logical_and(
                finite, jnp.all(jnp.isfinite(flat_g)))
            flat_gs.append(flat_g)
        finite_f = finite.astype(jnp.float32)
        inv_scale = jnp.float32(1.0) / scale
        with self._patched_optimizer(lr, t):
            for bi, b in enumerate(plan.buckets):
                flat_g = flat_gs[bi]
                master = opt_state[self._master_key(bi)]
                st = opt_state.get(self._flat_key(bi))

                if self.flat_mode == "shard":

                    def body(m_c, g_c, st_c, lr_c, t_c, inv_c, fin_c,
                             _b=b):
                        nm, nst, w16 = self._flat_body_amp(
                            _b, m_c, g_c, st_c, lr_c, t_c, inv_c, fin_c)
                        # the bf16 copy rejoins the replicated dispatch
                        # plan; master + state stay on their shard
                        w16_full = jax.lax.all_gather(
                            w16, "dp", tiled=True)
                        return w16_full, nm, nst

                    w16_full, nmaster, nst = jax.shard_map(
                        body, mesh=self.mesh,
                        in_specs=(P("dp"), P("dp"), P("dp"), P(), P(),
                                  P(), P()),
                        out_specs=(P(), P("dp"), P("dp")),
                        check_vma=False,
                    )(master, flat_g, st, lr, t, inv_scale, finite_f)
                else:
                    S = b.padded // dp

                    def scan_body(carry, xs, _b=b):
                        m_c, g_c, st_c = xs
                        return carry, self._flat_body_amp(
                            _b, m_c, g_c, st_c, lr, t, inv_scale,
                            finite_f)

                    m2 = master.reshape(dp, S)
                    g2 = flat_g.reshape(dp, S)
                    st2 = jax.tree_util.tree_map(
                        lambda a: a.reshape(dp, S), st)
                    _, (nm2, nst2, w16_2) = jax.lax.scan(
                        scan_body, 0, (m2, g2, st2))
                    nmaster = nm2.reshape(b.padded)
                    nst = jax.tree_util.tree_map(
                        lambda a: a.reshape(b.padded), nst2)
                    w16_full = w16_2.reshape(b.padded)

                for (_i, name, off, size, shape) in b.views:
                    new_params[name] = _from_slab(
                        w16_full[off:off + size], shape)
                new_state[self._master_key(bi)] = nmaster
                if nst is not None:
                    new_state[self._flat_key(bi)] = nst
        # dynamic loss scaler (grow/backoff), branchless
        window = jnp.float32(self.amp_scale_window)
        grown = (good + 1.0) >= window
        new_state[self.AMP_SCALE_KEY] = jnp.where(
            finite,
            jnp.where(grown,
                      jnp.minimum(scale * 2.0,
                                  jnp.float32(self.amp_scale_max)),
                      scale),
            jnp.maximum(scale * 0.5, jnp.float32(1.0)))
        new_state[self.AMP_GOOD_KEY] = jnp.where(
            finite,
            jnp.where(grown, jnp.float32(0.0), good + 1.0),
            jnp.float32(0.0))
        for name in params:
            if name not in new_params:
                new_params[name] = params[name]
        for k in opt_state:
            if k not in new_state:
                new_state[k] = opt_state[k]
        return new_params, new_state

    def _apply_optimizer_flat(self, params, grads, opt_state, lr, t):
        """Bucketed flat update: concat params/grads per bucket, run the
        optimizer on dp-wide chunks, carve per-key views back out.

        "shard" mode (MXTPU_SHARD_UPDATE=1, the default): the update
        runs inside shard_map — each replica updates only its contiguous
        1/N shard of the flat space against its reduce-scattered slice
        of the (GSPMD-allreduced) gradient, state stays sharded P("dp"),
        and updated weights are all-gathered back to replicated. The
        arXiv:2004.13336 recipe: O(params/N) update flops + state bytes.

        "replicated" mode: identical math via lax.scan over the same dp
        chunks on every replica — the bitwise parity baseline."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        opt = self.optimizer
        if opt is None or self.flat_mode is None:
            return self._apply_optimizer(params, grads, opt_state, lr, t)

        plan = self._ensure_flat_plan(params)
        dp = self.mesh.shape["dp"]
        new_params, new_state = {}, {}
        with self._patched_optimizer(lr, t):
            for bi, b in enumerate(plan.buckets):
                pad = b.padded - b.size
                w_parts = [_to_slab(params[name])
                           for (_i, name, _o, _s, _sh) in b.views]
                g_parts = [_to_slab(grads[name])
                           for (_i, name, _o, _s, _sh) in b.views]
                if pad:
                    zpad = jnp.zeros((pad,), w_parts[0].dtype)
                    w_parts.append(zpad)
                    g_parts.append(zpad)
                flat_w = jnp.concatenate(w_parts)
                flat_g = jnp.concatenate(g_parts)
                # hard fusion boundary: materialize the flat buffers in
                # BOTH modes so XLA cannot FMA-contract the gradient
                # chain into the update kernel differently per mode —
                # bitwise parity depends on both modes consuming the
                # same materialized values at the same chunk width
                flat_w, flat_g = jax.lax.optimization_barrier(
                    (flat_w, flat_g))
                # keep the weight concat replicated too: otherwise GSPMD
                # builds the flat buffer sharded and re-assembles it
                # with an extra full-size all-reduce (CPU partitioner).
                # The GRADIENT concat is left alone — constraining it
                # perturbs sharding propagation through the backward
                # graph enough to change reduction orders, which breaks
                # the bitwise shard↔replicated parity.
                rep = NamedSharding(self.mesh, P())
                flat_w = jax.lax.with_sharding_constraint(flat_w, rep)
                st = opt_state.get(self._flat_key(bi))

                if self.flat_mode == "shard":

                    def body(w_c, g_c, st_c, lr_c, t_c, _b=b):
                        nw, nst = self._flat_body(_b, w_c, g_c, st_c,
                                                  lr_c, t_c)
                        # weights rejoin the replicated dispatch plan;
                        # state stays resident on its owning shard
                        nw_full = jax.lax.all_gather(
                            nw, "dp", tiled=True)
                        return nw_full, nst

                    flat_nw, nst = jax.shard_map(
                        body, mesh=self.mesh,
                        in_specs=(P("dp"), P("dp"), P("dp"), P(), P()),
                        out_specs=(P(), P("dp")),
                        check_vma=False,
                    )(flat_w, flat_g, st, lr, t)
                else:
                    S = b.padded // dp

                    def scan_body(carry, xs, _b=b):
                        w_c, g_c, st_c = xs
                        return carry, self._flat_body(_b, w_c, g_c,
                                                      st_c, lr, t)

                    w2 = flat_w.reshape(dp, S)
                    g2 = flat_g.reshape(dp, S)
                    st2 = jax.tree_util.tree_map(
                        lambda a: a.reshape(dp, S), st)
                    _, (nw2, nst2) = jax.lax.scan(
                        scan_body, 0, (w2, g2, st2))
                    flat_nw = nw2.reshape(b.padded)
                    nst = jax.tree_util.tree_map(
                        lambda a: a.reshape(b.padded), nst2)

                for (_i, name, off, size, shape) in b.views:
                    new_params[name] = _from_slab(
                        flat_nw[off:off + size], shape)
                if nst is not None:
                    new_state[self._flat_key(bi)] = nst
        for name in params:
            if name not in new_params:
                new_params[name] = params[name]
        for k in opt_state:
            if k not in new_state:
                new_state[k] = opt_state[k]
        return new_params, new_state

    def _make_step_fn(self):
        """The single-step fwd+bwd+psum+optimizer body (pure; shared by
        the per-step jit and the K-step lax.scan program)."""
        import jax
        import jax.numpy as jnp

        from ..executor import _mirror_enabled, _mirror_policy
        from ..ops import kernels

        program = self.program
        trace_part = _tm.setup.trace_part
        do_mirror = _mirror_enabled()
        amp = self.amp
        guard = self.guard
        amp_cast = set(self.data_names) if (amp and self.amp_cast_data) \
            else set()

        # jax.named_scope below is metadata only (trace time, no op): a
        # device op in a profiler trace carries the phase it belongs to
        # (bench/reduce_scopes.py reads fwd_bwd, update, guard, amp_cast).
        # trace_part says where the trace's own host seconds went
        # (jit.trace_seconds: forward, backward, update); the body runs
        # only while jax traces it
        def step(params, aux, opt_state, batch, rng, lr, t, gthr):
            if amp_cast:
                # bf16 activations from the first op: cast floating DATA
                # feeds (never labels — loss heads compare against them
                # exactly). MXTPU_AMP_CAST_DATA=0 keeps feeds untouched.
                with jax.named_scope("amp_cast"):
                    batch = {
                        n: (v.astype(jnp.bfloat16)
                            if (n in amp_cast
                                and jnp.issubdtype(v.dtype, jnp.floating))
                            else v)
                        for n, v in batch.items()}

            def loss_fn(ps):
                with trace_part("forward"):
                    args = dict(ps)
                    args.update(batch)
                    outs, new_aux = program(args, aux, rng, True)
                    # *Output heads: drive vjp with ones
                    # (Executor.backward convention — the loss op bakes
                    # its own gradient)
                    loss = sum(jnp.sum(o.astype(jnp.float32) if amp else o)
                               for o in outs)
                    return loss, (outs, new_aux)

            if do_mirror:
                # MXNET_BACKWARD_DO_MIRROR: rematerialize cheap ops in
                # backward, keep dot/conv residuals (executor._mirror_policy)
                loss_fn = jax.checkpoint(loss_fn, policy=_mirror_policy)

            with jax.named_scope("fwd_bwd"), trace_part("backward"), \
                    kernels.common.partitioned_trace(self.mesh.size):
                if guard:
                    # value_and_grad instead of grad: the diag head
                    # needs the loss VALUE; the gradient computation is
                    # identical.
                    (loss_val, (outs, new_aux)), grads = \
                        jax.value_and_grad(loss_fn, has_aux=True)(params)
                else:
                    grads, (outs, new_aux) = jax.grad(
                        loss_fn, has_aux=True)(params)
            if amp:
                # Loss scaling rides the GRADIENT stream, not the loss
                # value: every loss head here ignores its incoming
                # cotangent by design (softmax_output-inl.h Backward —
                # ops/nn.py), so scaling the summed loss would never
                # reach the gradients. Multiplying the post-chain grads
                # by the scale is equivalent (bf16 carries fp32's full
                # exponent range, so the chain itself cannot overflow at
                # any representable scale) and exact for the
                # power-of-two scales the scaler produces.
                scale = opt_state[self.AMP_SCALE_KEY]
                grads = {k: g * scale.astype(g.dtype)
                         for k, g in grads.items()}
                outs = [o.astype(jnp.float32) for o in outs]
            # gradient allreduce over dp happens implicitly: params are
            # replicated, batch is dp-sharded → GSPMD inserts psum here.
            # (In flat "shard" mode the P("dp") in_specs then slice that
            # allreduced gradient per replica — allreduce+slice is XLA's
            # canonical reduce-scatter decomposition, which its collective
            # combiner re-forms into reduce-scatter on TPU.)
            if self.flat_mode is not None:
                # pin grads replicated at the source, IDENTICALLY in both
                # flat modes: without this GSPMD shards the downstream
                # flat concat and re-assembles it with an extra full-size
                # all-reduce per flat buffer (CPU partitioner), and any
                # mode-asymmetric resharding of the backward graph would
                # break the bitwise shard↔replicated parity
                from jax.sharding import NamedSharding, PartitionSpec as P

                rep = NamedSharding(self.mesh, P())
                grads = {k: jax.lax.with_sharding_constraint(g, rep)
                         for k, g in grads.items()}
            if amp:
                apply = self._apply_optimizer_flat_amp
            elif self.flat_mode is not None:
                apply = self._apply_optimizer_flat
            else:
                apply = self._apply_optimizer
            with jax.named_scope("update"), trace_part("update"):
                new_params, new_opt = apply(params, grads, opt_state, lr, t)
            new_aux = {**aux, **new_aux}  # carry shared-owner extras through
            if amp:
                # aux state (BN moving stats) keeps its fp32 dtype across
                # steps even when bf16 activations produced the batch
                # statistics this step folded in
                new_aux = {
                    k: (v.astype(aux[k].dtype)
                        if (k in aux and hasattr(v, "dtype")
                            and v.dtype != aux[k].dtype) else v)
                    for k, v in new_aux.items()}
            if guard:
                with jax.named_scope("guard"):
                    # Global grad-norm² from the SAME gradient stream the
                    # optimizer just consumed — replicated already, so this
                    # adds local reductions but no new collective. AMP grads
                    # arrive pre-multiplied by the loss scale; unscale the
                    # squared norm so the gate threshold and the host
                    # detector both see true magnitudes.
                    gn2 = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                              for g in grads.values())
                    if amp:
                        inv = 1.0 / opt_state[self.AMP_SCALE_KEY].astype(
                            jnp.float32)
                        gn2 = gn2 * inv * inv
                    ok = jnp.logical_and(jnp.isfinite(gn2), gn2 <= gthr)

                    def _sel(new, old):
                        # branchless select over a (possibly nested) state
                        # entry: select(True, new, old) is bitwise `new`, so
                        # a clean step is untouched by the gate
                        return jax.tree_util.tree_map(
                            lambda n_, o_: jnp.where(ok, n_, o_), new, old)

                    # AMP's scaler bookkeeping stays LIVE through a skip:
                    # reverting the scale would undo the backoff that makes
                    # the next attempt finite (same contract as the inner
                    # AMP gate, which also exempts these two keys).
                    passthru = ({self.AMP_SCALE_KEY, self.AMP_GOOD_KEY}
                                if amp else ())
                    new_params = {k: (_sel(v, params[k]) if k in params else v)
                                  for k, v in new_params.items()}
                    new_opt = {k: (v if (k in passthru or k not in opt_state)
                                   else _sel(v, opt_state[k]))
                               for k, v in new_opt.items()}
                    new_aux = {k: (_sel(v, aux[k]) if k in aux else v)
                               for k, v in new_aux.items()}
                    diag = jnp.stack([
                        jnp.asarray(loss_val, jnp.float32), gn2,
                        ok.astype(jnp.float32)])
                    outs = list(outs) + [diag]
            return new_params, new_aux, new_opt, outs

        return step

    def compile(self, data_shapes_by_name=None):
        """Build + jit the fused step fn. Returns self.

        Shardings are NOT pinned here: inputs arrive committed (placed by
        place_params/make_state/batch device_put) and GSPMD propagates —
        the idiomatic "computation follows sharding" path; donation keeps
        params/opt-state in place across steps."""
        import jax

        self._step = jax.jit(self._make_step_fn(), donate_argnums=(0, 1, 2))
        # signatures this jit has dispatched: the first call of each is
        # the one that traces, lowers and compiles or loads
        self._dispatched = set()
        try:
            _tm.anatomy.register_program(
                self.program._program_uid,
                mesh=str(dict(self.mesh.shape)),
                donation="params,aux,opt_state")
        except Exception:  # noqa: BLE001 — observer only
            pass
        return self

    def arm_guard(self):
        """Turn the guardrail gate + diag head on (fit(guardrails=...)).

        Re-wraps the step jit; jax.jit traces lazily, so arming before
        the first dispatch costs nothing extra, and arming later in a
        trainer's life retraces once at the next call. Idempotent."""
        if not self.guard:
            self.guard = True
            self.compile()
        return self

    def _capture_cost(self, cost_key, fn, specs, batch_shapes):
        """Price the program just dispatched for the step anatomy, once
        per signature and AFTER its dispatch: ``fn`` is lowered again
        from abstract arguments (the donated ones are gone) and never
        compiled, so telemetry builds no executable the untraced run
        does not build, and the jit's own lowering came first."""
        devices = self.mesh.size

        def analytic():
            # the TPU's client analyses no lowering: count by hand
            from ..telemetry import costmodel

            ops = costmodel.analytic_op_costs(
                self.symbol, dtype_bytes=2 if self.amp else 4,
                **batch_shapes)
            # a training step is ~3x the forward (forward + 2x backward)
            return {"flops": 3.0 * sum(o["flops"] for o in ops) / devices,
                    "bytes_accessed":
                        3.0 * sum(o["bytes"] for o in ops) / devices}

        _tm.anatomy.capture_cost(
            self.program._program_uid, cost_key,
            lambda: fn.lower(*specs), devices=devices, analytic=analytic,
            dtype="bf16" if self.amp else "f32")

    def __call__(self, params, aux, opt_state, batch, rng=None, lr=None, t=1):
        assert self._step is not None, "call compile() first"
        import jax.numpy as jnp

        # resolve 0-dims in creation-op shape attrs (rnn begin_state zeros
        # etc.) against the CURRENT input shapes, before jit traces. The
        # dispatch plan is keyed on the batch entries' (shape, dtype,
        # sharding) alone — param shapes are fixed per trainer — so the
        # steady state iterates 1-4 batch items instead of rebuilding and
        # sorting the full params+batch shape dict every step; a
        # batch-size change (Module.reshape, partial final batch) or a
        # re-placed input re-resolves once. Already-traced signatures
        # stay cached in jit.
        sig = tuple(
            (n, tuple(v.shape), str(v.dtype), getattr(v, "sharding", None))
            for n, v in batch.items())

        def _build():
            from ..executor import resolve_creation_shapes

            shapes = {n: tuple(v.shape) for n, v in params.items()}
            shapes.update({n: tuple(v.shape) for n, v in batch.items()})
            return resolve_creation_shapes(self.symbol, shapes)

        self.program.dispatch_plan(sig, _build)

        if lr is None:
            opt = self.optimizer
            if opt is not None and opt.lr_scheduler is not None:
                lr = float(opt.lr_scheduler(opt.num_update))
            else:
                lr = float(getattr(opt, "lr", 0.01))
        if rng is None:
            if self._needs_rng:
                from .. import random as _random

                rng = _random.next_key()
            else:
                rng = jnp.zeros((2,), jnp.uint32)  # unused placeholder
        lr_arr = jnp.asarray(lr, jnp.float32)
        t_arr = jnp.asarray(t, jnp.float32)
        gthr_arr = jnp.asarray(self.guard_threshold, jnp.float32)
        args = (params, aux, opt_state, batch, rng, lr_arr, t_arr, gthr_arr)
        cost_key = ("single",) + sig
        specs = (_abstract(args) if _tm.anatomy.cost_pending(
            self.program._program_uid, cost_key) else None)
        _M_STEPS.inc(path="single")
        first = sig not in self._dispatched
        if first:
            self._dispatched.add(sig)
        with (_tm.span("train_step.first_dispatch") if first
              else _tm.NULL_SPAN), _tm.span("train_step.dispatch", t=t):
            out = self._step(*args)
        if specs is not None:
            # the tracing's own set-up cost, timed as such
            with _tm.span(_tm.tracer.COST_CAPTURE):
                self._capture_cost(cost_key, self._step, specs, {
                    n: tuple(v.shape) for n, v in batch.items()})
        return out
