"""BaseModule: the abstract training-loop interface.

Capability parity with reference ``python/mxnet/module/base_module.py``
— the ``fit`` loop (base_module.py:368-516), ``score``/``predict``/
``iter_predict``, parameter accessors, and the forward_backward
contract. Re-authored around three shared helpers: a callback firer, an
inference-batch generator (forward + pad handling in one place), and a
param-file codec, instead of the reference's per-method inline loops.
"""
from __future__ import annotations

import collections
import copy
import logging
import os
import pickle
import signal
import time

import numpy as np

from .. import metric as metric_mod
from .. import ndarray as nd
from .. import random as _rnd
from .. import telemetry as _tm
from ..initializer import Uniform
from ..model import BatchEndParam
from ..io import DataDesc  # noqa: F401  (re-exported for subclasses)

_H_STEP_SECONDS = _tm.histogram(
    "fit.step_seconds", "Wall time of one fit-loop optimizer step "
    "(forward_backward + update), labelled by epoch")
_H_EPOCH_SECONDS = _tm.histogram(
    "fit.epoch_seconds", "Wall time of one training epoch")
_G_DISPATCH_DEPTH = _tm.gauge(
    "fit.dispatch_depth",
    "Enqueued steps whose metric fetch and batch-end callbacks have not "
    "run yet (1 in the steady state of a fused fit, 0 on the executor "
    "path and after every drain)")
_C_LOOKAHEAD = _tm.counter(
    "fit.lookahead_steps",
    "Steps whose metric fetch began with a later step already enqueued")
_C_RESUME_LOADED = _tm.counter(
    "resume.loaded", "fit() calls that restored state from a checkpoint")
_C_RESUME_NONE = _tm.counter(
    "resume.none_found",
    "fit() resume requests that found no valid checkpoint")
_C_PREEMPTED = _tm.counter(
    "fit.preempted",
    "fit() loops that exited through the SIGTERM/SIGINT grace path "
    "after writing a final checkpoint")


def _as_list(obj):
    return obj if isinstance(obj, list) else [obj]


def _fire(callbacks, epoch, nbatch, eval_metric, local_vars):
    """Invoke batch/epoch callbacks with the reference's BatchEndParam."""
    if callbacks is None:
        return
    params = BatchEndParam(epoch=epoch, nbatch=nbatch,
                           eval_metric=eval_metric, locals=local_vars)
    for cb in _as_list(callbacks):
        cb(params)


def _poison_batch(batch, mode):
    """Fault-injection support (``nan_grad_at_step`` /
    ``loss_spike_at_step``): a shallow copy of ``batch`` whose data is
    poisoned — NaN (non-finite gradient) or a 1e4 scale (finite loss /
    grad-norm spike) — with labels and metadata intact, so the
    guardrail sees exactly what a corrupt upstream feed would produce."""
    factor = float("nan") if mode == "nan" else 1.0e4
    out = copy.copy(batch)
    out.data = [
        nd.array(np.asarray(d.asnumpy(), dtype=np.float32) * factor)
        for d in batch.data]
    return out


def _check_input_names(symbol, names, typename, throw):
    args = symbol.list_arguments()
    missing = [n for n in names if n not in args]
    for name in missing:
        candidates = [a for a in args if not a.endswith(
            ("_weight", "_bias", "_gamma", "_beta"))]
        msg = (
            "\033[91mYou created Module with Module(..., %s_names=%s) but "
            "input with name '%s' is not found in symbol.list_arguments(). "
            "Did you mean one of:\n\t%s\033[0m"
            % (typename, str(names), name, "\n\t".join(candidates))
        )
        if throw:
            raise ValueError(msg)
        logging.warning(msg)


class BaseModule(object):
    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None
        self._total_exec_bytes = 0

    # ------------------------------------------------------------------
    # shared inference plumbing
    # ------------------------------------------------------------------
    def _infer_batches(self, eval_data, num_batch, reset,
                       want_outputs=True):
        """Yield (nbatch, batch, unpadded outputs) over an eval iter.
        Metric-only consumers pass want_outputs=False so the (possibly
        multi-device) output gather is skipped entirely."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        for nbatch, batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                return
            self.forward(batch, is_train=False)
            if want_outputs:
                pad = batch.pad or 0
                outs = [out[0:out.shape[0] - pad]
                        for out in self.get_outputs()]
            else:
                outs = None
            yield nbatch, batch, outs

    # ------------------------------------------------------------------
    # high-level
    # ------------------------------------------------------------------
    def forward_backward(self, data_batch):
        self.forward(data_batch, is_train=True)
        self.backward()

    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None, reset=True,
              epoch=0):
        """Run inference over eval_data, accumulating eval_metric."""
        eval_metric = metric_mod.create(eval_metric)
        eval_metric.reset()
        n_seen = 0
        for nbatch, batch, _outs in self._infer_batches(
                eval_data, num_batch, reset, want_outputs=False):
            self.update_metric(eval_metric, batch.label)
            _fire(batch_end_callback, epoch, nbatch, eval_metric, locals())
            n_seen = nbatch + 1
        _fire(score_end_callback, epoch, n_seen, eval_metric, locals())
        return eval_metric.get_name_value()

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        """Generator over (outputs, nbatch, batch) for streaming predict."""
        for nbatch, batch, outs in self._infer_batches(
                eval_data, num_batch, reset):
            yield outs, nbatch, batch

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False):
        """Predict over an iterator; merged across batches by default."""
        collected = [
            [o.copy() for o in outs]
            for _n, _b, outs in self._infer_batches(eval_data, num_batch,
                                                    reset)
        ]
        if not collected or not merge_batches:
            return collected
        arity = len(collected[0])
        if any(len(outs) != arity for outs in collected):
            raise AssertionError(
                "Cannot merge batches, as num of outputs is not the same "
                "in mini-batches. Maybe bucketing is used?")
        merged = [nd.concatenate([outs[i] for outs in collected])
                  for i in range(arity)]
        if arity == 1 and not always_output_list:
            return merged[0]
        return merged

    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            optimizer="sgd", optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=Uniform(0.01), arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None, checkpoint_dir=None, resume=None,
            guardrails=None):
        """THE training loop — parity base_module.py:368-516 (§3.1).

        Preemption-safe extension (docs/robustness.md): ``checkpoint_dir``
        (a path or a ``resilience.CheckpointManager``) turns on atomic
        full-state checkpointing — at every epoch end, every
        ``MXTPU_CKPT_INTERVAL`` optimizer steps, and on SIGTERM/SIGINT
        (drain in-flight dispatch, write a final checkpoint, exit with
        ``resilience.EXIT_PREEMPTED``). ``resume="auto"`` (or an explicit
        step number) restores params, optimizer state, RNG streams,
        metric accumulation, and the data-iterator position from the
        newest checkpoint whose manifest verifies — continuation is
        bitwise-identical to a run that was never interrupted.

        ``guardrails="auto"`` (requires ``checkpoint_dir``) arms the
        numeric guardrails (resilience/guardrail.py): the fused step
        gains a branchless skip gate on non-finite / out-of-threshold
        gradients, a robust z-score monitor watches loss and grad-norm,
        checkpoints carry a ``health`` stamp, and repeated anomalies
        rewind to the newest known-good snapshot — bounded by
        ``MXTPU_GUARD_MAX_REWINDS``, after which the run exits
        ``EXIT_GUARDRAIL`` with a structured verdict."""
        assert num_epoch is not None, "please specify number of epochs"
        self.bind(
            data_shapes=train_data.provide_data,
            label_shapes=train_data.provide_label,
            for_training=True, force_rebind=force_rebind
        )
        if monitor is not None:
            self.install_monitor(monitor)
        self.init_params(
            initializer=initializer, arg_params=arg_params,
            aux_params=aux_params, allow_missing=allow_missing,
            force_init=force_init
        )
        self.init_optimizer(
            kvstore=kvstore, optimizer=optimizer,
            optimizer_params=optimizer_params
        )
        eval_metric = metric_mod.create(eval_metric)
        if validation_metric is None:
            validation_metric = eval_metric

        # -- async dispatch pipeline (docs/performance.md) -------------
        # On the fused mesh path MXTPU_DEVICE_FEED (on) wraps train_data
        # in a DeviceFeedIter so the next batch's host->device transfer
        # is in flight during compute, and the loop keeps one step in
        # flight: a step's metric fetch and batch-end callbacks run
        # after the NEXT step has been enqueued (_post_step below).
        fit_data = train_data
        _trainer = getattr(self, "_fused_trainer", None)
        if (_trainer is not None
                and not getattr(self, "_fused_multiproc", False)
                and os.environ.get("MXTPU_DEVICE_FEED", "1") != "0"):
            from ..io import DeviceFeedIter

            fit_data = DeviceFeedIter(train_data, _trainer.batch_sharding())
        # post-step work (labels, outputs, callback arguments) of the
        # newest enqueued step; never more than one entry
        in_flight = collections.deque()

        def _run_post(labels, outs, cb_args):
            if outs is not None:
                # the step may no longer be the newest: serve ITS outputs
                # to the metric and to get_outputs() in its callbacks
                self._install_step_outputs(outs)
            self.update_metric(eval_metric, labels)
            with _tm.span("fit.callbacks"):
                _fire(batch_end_callback, *cb_args)

        def _post_step(epoch, nbatch, data_batch, cb_locals):
            """The step of ``data_batch`` has just been enqueued. Where
            its outputs stay valid while later steps dispatch (the fused
            path: ``_metric_snapshot``), its metric fetch and callbacks
            wait until the next step is enqueued and the PREVIOUS step's
            run now, so the device always has a step queued behind the
            one the host blocks on. Elsewhere (executor path, monitor)
            the outputs are reused across steps: synchronous order."""
            outs = self._metric_snapshot() if monitor is None else None
            post = (data_batch.label, outs,
                    (epoch, nbatch, eval_metric, cb_locals))
            if outs is None:
                _run_post(*post)
                return
            if in_flight:
                _C_LOOKAHEAD.inc()
                _run_post(*in_flight.popleft())
                # outside a callback get_outputs() serves the newest step
                self._install_step_outputs(outs)
            in_flight.append(post)
            _G_DISPATCH_DEPTH.set(1)

        def _drain_post():
            """Catch the loop up: called wherever state must be that of
            one step (epoch end, checkpoint capture, preemption)."""
            _G_DISPATCH_DEPTH.set(0)  # nothing is enqueued behind it
            while in_flight:
                _run_post(*in_flight.popleft())

        # -- preemption-safe checkpointing (resilience/) ---------------
        from ..resilience import checkpoint as _ckpt
        from ..resilience import fault as _fault

        ckpt_mgr = None
        if checkpoint_dir is not None:
            ckpt_mgr = (checkpoint_dir
                        if isinstance(checkpoint_dir, _ckpt.CheckpointManager)
                        else _ckpt.CheckpointManager(checkpoint_dir))
        elif resume is not None:
            raise ValueError("fit(resume=...) requires checkpoint_dir")
        try:
            ckpt_interval = max(0, int(os.environ.get(
                _ckpt.ENV_INTERVAL, "0")))
        except ValueError:
            ckpt_interval = 0

        # -- training guardrails (resilience/guardrail.py) -------------
        from ..resilience import guardrail as _guard

        guard_mon = None
        if guardrails is not None:
            if guardrails != "auto":
                raise ValueError(
                    'guardrails must be "auto" or None, got %r'
                    % (guardrails,))
            if ckpt_mgr is None:
                raise ValueError(
                    "fit(guardrails=...) requires checkpoint_dir — "
                    "rewind-to-last-good needs somewhere to rewind to")
            if _trainer is None:
                # the in-graph gate and diag stream live in the fused
                # step; without it there is nothing to observe
                self.logger.warning(
                    "guardrails: no fused trainer on this module — "
                    "anomaly detection disabled")
            else:
                _trainer.arm_guard()
                guard_mon = _guard.GuardrailMonitor(logger=self.logger)

        def _restore_from_state(state):
            """Reinstate module/optimizer/RNG (+ the elastic cursor
            translation) from a checkpoint state dict. Shared by the
            resume path and the guardrail rewind path. Returns
            ``(epoch, skip, gs, metric_blob)``."""
            self._restore_train_state(state["module"])
            rng = state.get("rng") or {}
            if rng.get("numpy") is not None:
                np.random.set_state(rng["numpy"])
            if rng.get("mx") is not None:
                _rnd.set_state(rng["mx"])
            epoch = int(state.get("epoch", 0))
            skip = int(state.get("nbatch", 0))
            gs = int(state.get("global_step", 0))
            metric_blob = state.get("metric")
            # -- elastic resume (docs/robustness.md) -------------------
            # The snapshot is layout-independent (named trees;
            # _restore_train_state just re-sharded the optimizer
            # slabs at THIS world's dp), but the iterator cursor
            # counts batches at the WRITER's global batch. When the
            # restoring world feeds a different global batch,
            # translate through the invariant that actually matters:
            # the global SAMPLE position.
            topo = state.get("topology")
            cur = self._topology()
            if topo and cur:
                wgb = int(topo.get("global_batch") or 0)
                cgb = int(cur.get("global_batch") or 0)
                if wgb and cgb and wgb != cgb:
                    samples = skip * wgb
                    skip, rem = divmod(samples, cgb)
                    if rem:
                        # round DOWN: re-feeding (<1 batch of) seen
                        # samples beats silently skipping unseen ones
                        self.logger.warning(
                            "elastic resume: sample position %d is "
                            "not a multiple of the new global batch "
                            "%d — %d samples will be re-fed",
                            samples, cgb, rem)
                    # the saved metric accumulated at the old batch
                    # geometry; with the cursor translated it still
                    # covers exactly the samples trained so far
                if topo.get("dp") != cur.get("dp"):
                    self.logger.info(
                        "elastic resume: checkpoint written at dp=%s "
                        "(global batch %s), restoring at dp=%s "
                        "(global batch %s) — optimizer state "
                        "re-sharded across %s replicas",
                        topo.get("dp"), wgb or "?", cur.get("dp"),
                        cgb or "?", cur.get("dp"))
            ckpt_mgr.last_step = gs
            return epoch, skip, gs, metric_blob

        resume_skip = 0
        resume_metric = None
        gs0 = 0
        if ckpt_mgr is not None and resume is not None:
            if resume == "auto":
                # under guardrails, prefer the newest HEALTHY snapshot:
                # a checkpoint stamped mid-anomaly would resume the very
                # divergence the rewind was escaping (the SIGKILL-
                # during-rewind chain relaunches through here)
                state = (ckpt_mgr.load_last_good()
                         if guard_mon is not None else ckpt_mgr.load())
            elif isinstance(resume, int) and not isinstance(resume, bool):
                state = ckpt_mgr.load(step=resume)
            else:
                raise ValueError(
                    'resume must be "auto" or a checkpoint step, got %r'
                    % (resume,))
            if state is None:
                _C_RESUME_NONE.inc()
                self.logger.info(
                    "resume: no valid checkpoint under %s — starting fresh",
                    ckpt_mgr.directory)
            else:
                begin_epoch, resume_skip, gs0, resume_metric = \
                    _restore_from_state(state)
                if guard_mon is not None:
                    guard_mon.restore(state.get("health"))
                    _trainer.guard_threshold = guard_mon.gate_threshold()
                _C_RESUME_LOADED.inc()
                self.logger.info(
                    "resume: restored step %d (epoch %d, batch %d)",
                    gs0, begin_epoch, resume_skip)

        loop = {"gs": gs0, "done": resume_skip, "epoch": begin_epoch,
                "last_saved": gs0}
        preempt = {"flag": False}

        # -- elastic shrink driver (docs/robustness.md) ----------------
        # MXTPU_ELASTIC=1 promotes heartbeat liveness from a reporter to
        # a driver: when a peer replica is declared lost mid-fit
        # (lost_ tombstone, or a heartbeat that went silent past
        # MXTPU_ELASTIC_TIMEOUT), drain at the next step boundary,
        # write a final synchronous checkpoint, and exit EXIT_RESHAPE —
        # the supervisor (tools/watchdog.py --elastic) relaunches at the
        # surviving world size, where resume="auto" re-binds the same
        # named-tree state at the new dp.
        elastic = None
        if ckpt_mgr is not None and os.environ.get("MXTPU_ELASTIC") == "1":
            from ..parallel import heartbeat as _hb

            _run_dir = _hb.run_dir()

            def _env_num(name, default, cast):
                try:
                    return cast(os.environ.get(name, default))
                except ValueError:
                    return cast(default)

            _world = _env_num(
                "MXTPU_WORLD_SIZE",
                os.environ.get("DMLC_NUM_WORKER", "0"), int)
            if _run_dir and _world > 1:
                elastic = {
                    "hb": _hb, "dir": _run_dir, "world": _world,
                    "rank": _env_num("DMLC_RANK", "0", int),
                    "poll": _env_num("MXTPU_ELASTIC_POLL", "5", float),
                    "timeout": _env_num(
                        "MXTPU_ELASTIC_TIMEOUT", "60", float),
                    "next": 0.0,
                }

        # -- fleet liveness (telemetry/fleet.py, docs/observability.md) -
        # Under a run dir every fitting process maintains hb_/prog_
        # signal files, even with a local kvstore (dist kvstores start
        # their own writer at creation — don't double up): the fleet
        # aggregator, fleet_top, and the watchdog read per-rank liveness
        # from these files.
        fleet_hb = None
        _fit_run_dir = os.environ.get("MXTPU_RUN_DIR")
        if _fit_run_dir and getattr(
                getattr(self, "_kvstore", None), "_heartbeat", None) is None:
            try:
                from ..parallel import heartbeat as _fleet_hb_mod

                _rank = 0
                for _var in ("DMLC_RANK", "JAX_PROCESS_ID"):
                    if os.environ.get(_var):
                        try:
                            _rank = int(os.environ[_var])
                            break
                        except ValueError:
                            pass
                fleet_hb = _fleet_hb_mod.HeartbeatWriter(
                    _fit_run_dir, _rank).start()
            except OSError:
                fleet_hb = None

        def _capture(epoch_next, nbatch_done):
            try:
                metric_blob = pickle.dumps(eval_metric, protocol=2)
            except Exception:  # unpicklable custom metric (e.g. lambda
                metric_blob = None  # feval): resume restarts its epoch
            topo = self._topology()
            # the streaming input pipeline's O(1) cursor: the global
            # SAMPLE position is the topology-independent invariant
            # (nbatch is only meaningful at the writer's global batch),
            # recorded explicitly so MANIFEST readers — and a restoring
            # world at any dp — can reposition without replaying batches
            sample_pos = None
            if topo and topo.get("global_batch"):
                sample_pos = int(nbatch_done) * int(topo["global_batch"])
            blob = {
                "module": self._capture_train_state(),
                "epoch": int(epoch_next),
                "nbatch": int(nbatch_done),
                "sample_position": sample_pos,
                "global_step": int(loop["gs"]),
                "metric": metric_blob,
                "rng": {"numpy": np.random.get_state(),
                        "mx": _rnd.get_state()},
                "topology": topo,
            }
            if guard_mon is not None:
                # health stamp: known-clean flag + detector state, so
                # retention can protect the rewind target and a rewind
                # restarts the statistics where this snapshot left them
                blob["health"] = guard_mon.health_blob(loop["gs"])
            return blob

        def _after_step(epoch, done):
            with _tm.span("fit.after_steps"):
                _bookkeep(epoch, done)

        def _bookkeep(epoch, done):
            """Bookkeeping at a step boundary: one more optimizer step
            has been dispatched (``done`` = batches of this epoch now
            trained). Fires the fault harness, honors a pending
            preemption, and takes interval snapshots. Every capture
            first drains the lookahead's pending post-step work
            (``_drain_post``), so the captured params, metric and
            iterator position are those of the same step."""
            if _fault.configured():
                _fault.fire("step", step=loop["gs"] + 1)
            loop["gs"] += 1
            loop["done"] = done
            loop["epoch"] = epoch
            _tm.anatomy.on_steps(1)
            if fleet_hb is not None:
                fleet_hb.progress()
            if guard_mon is not None:
                # fold the step's diag sample into the detector (one
                # tiny host transfer per step, never ahead of the
                # dispatch frontier)
                rewind = False
                for t, diag in self._drain_guard_diag():
                    verdict = guard_mon.observe(
                        t, float(diag[0]), float(diag[1]), float(diag[2]))
                    rewind = rewind or verdict == "rewind"
                # feed the warmed statistics back into the in-graph
                # gate: a traced scalar operand, so no recompile
                _trainer.guard_threshold = guard_mon.gate_threshold()
                if rewind:
                    raise _guard.GuardrailRewind(
                        step=loop["gs"], epoch=epoch, nbatch=done,
                        reason=guard_mon.last_reason)
            if ckpt_mgr is None:
                return
            if preempt["flag"]:
                # grace path: the step has been dispatched, its pending
                # post-step work runs, and the final checkpoint is
                # written synchronously
                _drain_post()
                ckpt_mgr.save(_capture(epoch, done), loop["gs"])
                _C_PREEMPTED.inc()
                self.logger.info(
                    "preempted: checkpoint at step %d written, exiting %d",
                    loop["gs"], _ckpt.EXIT_PREEMPTED)
                raise SystemExit(_ckpt.EXIT_PREEMPTED)
            if elastic is not None:
                now = time.monotonic()
                if now >= elastic["next"]:
                    elastic["next"] = now + elastic["poll"]
                    lost = [r for r in elastic["hb"].lost_nodes(
                                elastic["dir"], elastic["world"],
                                timeout=elastic["timeout"])
                            if r != elastic["rank"]]
                    if lost:
                        # drain at the step boundary, exactly like the
                        # preemption path, so the snapshot and the
                        # iterator position agree
                        _drain_post()
                        ckpt_mgr.save(_capture(epoch, done), loop["gs"])
                        self.logger.info(
                            "elastic: replica(s) %s declared lost — "
                            "checkpoint at step %d written, exiting %d "
                            "for shrink-and-continue",
                            lost, loop["gs"], _ckpt.EXIT_RESHAPE)
                        raise SystemExit(_ckpt.EXIT_RESHAPE)
            if (ckpt_interval
                    and loop["gs"] - loop["last_saved"] >= ckpt_interval):
                loop["last_saved"] = loop["gs"]
                _drain_post()
                ckpt_mgr.save_async(_capture(epoch, done), loop["gs"])

        old_handlers = {}
        if ckpt_mgr is not None:
            def _on_preempt(signum, frame):
                # flag only — the loop checkpoints at the next step
                # boundary, where captured state and iterator position
                # agree (a handler can run between a step's dispatch and
                # its bookkeeping)
                preempt["flag"] = True

            for _sig in (signal.SIGTERM, signal.SIGINT):
                try:
                    old_handlers[_sig] = signal.signal(_sig, _on_preempt)
                except ValueError:
                    pass  # not the main thread: periodic ckpts still work

        try:
            while True:
                try:
                    self._fit_epochs(
                        fit_data, train_data, eval_data, eval_metric,
                        validation_metric, begin_epoch, num_epoch, monitor,
                        batch_end_callback, epoch_end_callback,
                        eval_end_callback, eval_batch_end_callback,
                        _post_step, _drain_post, _after_step,
                        ckpt_mgr, loop, _capture, resume_skip,
                        resume_metric)
                    break
                except _guard.GuardrailRewind as rw:
                    # -- rewind-to-last-good (docs/robustness.md) ------
                    # The monitor votes at a step boundary; the pending
                    # post-step work is for a step about to be discarded.
                    in_flight.clear()
                    _G_DISPATCH_DEPTH.set(0)
                    self._drain_guard_diag()
                    ckpt_mgr.wait()  # in-flight async save must land
                    state = (ckpt_mgr.load_last_good()
                             if guard_mon.rewinds < guard_mon.max_rewinds
                             else None)
                    if state is None:
                        # budget exhausted (or nothing good on disk):
                        # publish the structured verdict where the
                        # watchdog looks and stop — replaying the same
                        # data diverges the same way
                        paths = _guard.write_verdict({
                            "action": "abort",
                            "reason": rw.reason,
                            "step": rw.step,
                            "epoch": rw.epoch,
                            "nbatch": rw.nbatch,
                            "rewinds": guard_mon.rewinds,
                            "budget": guard_mon.max_rewinds,
                            "last_clean_step": guard_mon.last_clean_step,
                        }, extra_dir=ckpt_mgr.directory)
                        self.logger.error(
                            "guardrail: unrecoverable anomaly at step %d "
                            "(%s) — rewind budget %d/%d spent, verdict "
                            "at %s, exiting %d",
                            rw.step, rw.reason, guard_mon.rewinds,
                            guard_mon.max_rewinds, paths or "<nowhere>",
                            _guard.EXIT_GUARDRAIL)
                        raise SystemExit(_guard.EXIT_GUARDRAIL)
                    _guard.count_rewind(guard_mon)
                    if _fault.configured():
                        # SIGKILL-during-rewind chain test hook: the
                        # last-good target is chosen but nothing is
                        # restored yet — a kill here must leave a
                        # relaunch able to recover
                        _fault.fire("rewind", step=rw.step)
                    begin_epoch, resume_skip, gs0, resume_metric = \
                        _restore_from_state(state)
                    guard_mon.restore(state.get("health"))
                    _trainer.guard_threshold = guard_mon.gate_threshold()
                    if begin_epoch == rw.epoch:
                        # steer past the poison window: everything up to
                        # and including the batch that tripped the
                        # detector is skipped via the O(1) sample
                        # cursor, not retrained
                        resume_skip = max(resume_skip, rw.nbatch)
                    self.logger.warning(
                        "guardrail: rewound to last-good step %d "
                        "(epoch %d) after anomaly at step %d — "
                        "re-entering at batch %d (%d/%d rewinds spent)",
                        gs0, begin_epoch, rw.step, resume_skip,
                        guard_mon.rewinds, guard_mon.max_rewinds)

                    def _seek(inner):
                        # reposition the source to the REWIND epoch:
                        # seek_epoch keeps the epoch counter (and with
                        # it the shuffle order) aligned; reset() is the
                        # fallback for order-free iterators
                        if hasattr(inner, "seek_epoch"):
                            inner.seek_epoch(begin_epoch)
                        else:
                            inner.reset()

                    if hasattr(fit_data, "rewind"):
                        fit_data.rewind(_seek)
                    else:
                        _seek(fit_data)
                    loop["gs"] = gs0
                    loop["done"] = resume_skip
                    loop["epoch"] = begin_epoch
                    loop["last_saved"] = gs0
        finally:
            if fleet_hb is not None:
                fleet_hb.stop()
            for _sig, handler in old_handlers.items():
                try:
                    signal.signal(_sig, handler)
                except ValueError:
                    pass
            if ckpt_mgr is not None:
                ckpt_mgr.wait()

    def _drain_guard_diag(self):
        """Guardrail diag samples queued since the last drain (none for
        the base/executor path — Module overrides on the fused path)."""
        return []

    def _note_op_costs(self, train_data):
        """Emit the bound symbol's per-op analytic cost table into the
        telemetry JSONL once per fit (``type=op_costs``) — perf_doctor
        joins it with the roofline peak tables to rank memory-bound ops
        as concrete kernel candidates. Advisory: any failure (symbol-
        less module, shapeless iterator) is silently skipped."""
        if not _tm.anatomy.enabled():
            return
        with _tm.span(_tm.tracer.COST_CAPTURE):
            self._emit_op_costs(train_data)

    def _emit_op_costs(self, train_data):
        try:
            from ..telemetry import costmodel as _cm

            sym = getattr(self, "symbol", None)
            if sym is None:
                return
            shapes = {}
            for desc in (list(getattr(train_data, "provide_data", None)
                              or []) +
                         list(getattr(train_data, "provide_label", None)
                              or [])):
                shapes[desc[0]] = tuple(desc[1])
            if not shapes:
                return
            _tm.anatomy.note_op_costs(
                _cm.analytic_op_costs(sym, **shapes))
        except Exception:  # noqa: BLE001 — advisory only
            pass

    def _fit_epochs(self, fit_data, train_data, eval_data, eval_metric,
                    validation_metric, begin_epoch, num_epoch, monitor,
                    batch_end_callback, epoch_end_callback,
                    eval_end_callback, eval_batch_end_callback,
                    _post_step, _drain_post, _after_step, ckpt_mgr,
                    loop, _capture, resume_skip, resume_metric):
        """Epoch loop body of :meth:`fit` (split out so the signal-window
        try/finally in fit stays readable)."""
        from ..resilience import fault as _fault

        _tm.anatomy.begin_loop()
        self._note_op_costs(train_data)
        for epoch in range(begin_epoch, num_epoch):
            tic = time.time()
            eval_metric.reset()
            skip = resume_skip if epoch == begin_epoch else 0
            if skip and resume_metric is not None:
                # resumed mid-epoch: reinstate the interrupted epoch's
                # accumulation AFTER reset(), via __dict__.update so the
                # validation_metric alias keeps pointing at the live
                # object — final epoch stats then match the
                # uninterrupted run exactly
                eval_metric.__dict__.update(
                    pickle.loads(resume_metric).__dict__)
            if skip:
                # already-trained batches are skipped, never re-fed:
                # they consumed no RNG and must consume none on resume
                fit_data.skip(skip)
            batches = iter(fit_data)

            def _next_batch():
                """The next batch, or None at the end of the epoch; the
                wait for it is ``fit.input``."""
                with _tm.span("fit.input"):
                    batch = next(batches, None)
                if batch is not None and _fault.configured():
                    # poison-batch injection (nan_grad_at_step /
                    # loss_spike_at_step): this batch will feed
                    # optimizer step gs + 1
                    _mode = _fault.batch_poison(loop["gs"] + 1)
                    if _mode:
                        batch = _poison_batch(batch, _mode)
                return batch

            nbatch = skip - 1
            while True:
                nbatch += 1
                # one span holds the whole step period, from the wait
                # for the batch to the bookkeeping after it: what the
                # spans inside it do not cover is the loop's own time
                with _tm.span("fit.step", epoch=epoch, nbatch=nbatch,
                              step=loop["gs"] + 1) as step_span:
                    data_batch = _next_batch()
                    if data_batch is None:
                        step_span.discard()
                        break
                    if monitor is not None:
                        monitor.tic()
                    t0 = time.perf_counter()
                    self.forward_backward(data_batch)
                    self.update()
                    _H_STEP_SECONDS.observe(
                        time.perf_counter() - t0, epoch=str(epoch))
                    if _tm.enabled():
                        _tm.sample_device_memory()
                    if monitor is not None:
                        monitor.toc_print()
                    # a copy: locals() hands out one dict a frame, and
                    # these may be read one step later
                    _post_step(epoch, nbatch, data_batch, dict(locals()))
                    _after_step(epoch, nbatch + 1)
            _drain_post()  # the last step's metric and callbacks
            # land before the epoch's statistics
            # close the partial anatomy interval on the epoch boundary so
            # its phase deltas land in the same JSONL flush below
            _tm.anatomy.emit_interval(force=True)

            for name, val in eval_metric.get_name_value():
                self.logger.info("Epoch[%d] Train-%s=%f", epoch, name, val)
            self.logger.info("Epoch[%d] Time cost=%.3f",
                             epoch, time.time() - tic)
            if _tm.enabled():
                _H_EPOCH_SECONDS.observe(time.time() - tic)
                _tm.flush()  # metrics snapshot per epoch (JSONL + prom)

            # sync params (and multi-device aux) back to the host copies
            arg_now, aux_now = self.get_params()
            self.set_params(arg_now, aux_now)
            if epoch_end_callback is not None:
                for cb in _as_list(epoch_end_callback):
                    cb(epoch, self.symbol, arg_now, aux_now)

            if eval_data:
                res = self.score(
                    eval_data, validation_metric,
                    score_end_callback=eval_end_callback,
                    batch_end_callback=eval_batch_end_callback, epoch=epoch
                )
                for name, val in res:
                    self.logger.info("Epoch[%d] Validation-%s=%f",
                                     epoch, name, val)

            if ckpt_mgr is not None and loop["gs"] > loop["last_saved"]:
                # epoch-boundary snapshot (always, interval or not):
                # records epoch+1/batch 0 so a resume starts the next
                # epoch cleanly. Async — the save overlaps eval/reset.
                loop["last_saved"] = loop["gs"]
                ckpt_mgr.save_async(_capture(epoch + 1, 0), loop["gs"])

            fit_data.reset()  # resets train_data through the feed wrapper

    # ------------------------------------------------------------------
    # symbol / params
    # ------------------------------------------------------------------
    @property
    def symbol(self):
        return self._symbol

    @property
    def data_names(self):
        raise NotImplementedError()

    @property
    def output_names(self):
        raise NotImplementedError()

    @property
    def data_shapes(self):
        raise NotImplementedError()

    @property
    def label_shapes(self):
        raise NotImplementedError()

    @property
    def output_shapes(self):
        raise NotImplementedError()

    def get_params(self):
        raise NotImplementedError()

    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False):
        raise NotImplementedError()

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True):
        self.init_params(
            initializer=None, arg_params=arg_params, aux_params=aux_params,
            allow_missing=allow_missing, force_init=force_init
        )

    def save_params(self, fname):
        from ..resilience.checkpoint import atomic_file

        arg_params, aux_params = self.get_params()
        blob = {"arg:" + k: v for k, v in arg_params.items()}
        blob.update({"aux:" + k: v for k, v in aux_params.items()})
        # atomic: a crash mid-write must not leave a truncated .params
        # where a previous good one (or nothing) used to be
        with atomic_file(fname) as f:
            nd._save_fileobj(f, blob)

    def load_params(self, fname):
        split = {"arg": {}, "aux": {}}
        for key, value in nd.load(fname).items():
            kind, _, name = key.partition(":")
            if kind not in split or not name:
                raise ValueError("Invalid param file " + fname)
            split[kind][name] = value
        self.set_params(split["arg"], split["aux"])

    # ------------------------------------------------------------------
    # computation interface
    # ------------------------------------------------------------------
    def forward(self, data_batch, is_train=None):
        raise NotImplementedError()

    def backward(self, out_grads=None):
        raise NotImplementedError()

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError()

    def get_input_grads(self, merge_multi_context=True):
        raise NotImplementedError()

    def update(self):
        raise NotImplementedError()

    def update_metric(self, eval_metric, labels):
        raise NotImplementedError()

    def _capture_train_state(self):
        """Checkpoint hook: snapshot everything this module needs for an
        exact resume. The generic default covers params only; Module
        overrides it to add optimizer state and the fused device dicts."""
        arg, aux = self.get_params()
        return {
            "arg": {k: v.asnumpy().copy() for k, v in arg.items()},
            "aux": {k: v.asnumpy().copy() for k, v in aux.items()},
            "opt": {"kind": "none"},
        }

    def _restore_train_state(self, blob):
        """Checkpoint hook: inverse of :meth:`_capture_train_state`."""
        self.set_params(
            {k: nd.array(v) for k, v in (blob.get("arg") or {}).items()},
            {k: nd.array(v) for k, v in (blob.get("aux") or {}).items()})

    def _topology(self):
        """Checkpoint hook: the runtime topology (dp, mesh, batch
        geometry) recorded into manifests for elastic resume, or None
        when this module type has no meaningful topology. Module
        overrides it."""
        return None

    def _metric_snapshot(self):
        """fit()'s lookahead hook: the outputs of the step just enqueued
        in a form that stays valid while later steps dispatch (Module's
        fused path returns its raw jax outputs), or None where outputs
        are reused across steps, which keeps fit's post-step work
        synchronous."""
        return None

    def _install_step_outputs(self, outs_raw):
        """Publish what _metric_snapshot returned for one step as the
        current outputs (update_metric and get_outputs serve them)."""
        raise NotImplementedError()

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        raise NotImplementedError()

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        raise NotImplementedError()

    def install_monitor(self, mon):
        raise NotImplementedError()
