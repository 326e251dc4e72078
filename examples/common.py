"""Shared example plumbing (parity: reference
example/image-classification/common/fit.py — add_fit_args + fit()).

Examples run unmodified on TPU (default) or CPU via ``--ctx cpu``.
"""
from __future__ import annotations

import argparse
import logging
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# --ctx cpu must take effect BEFORE jax initializes a backend: on a host
# with a chip the first backend touch claims it, and the virtual CPU
# device count is read once at backend start-up.
def _wants_cpu(argv):
    return "--ctx" in argv and \
        argv[argv.index("--ctx") + 1:][:1] == ["cpu"]


if _wants_cpu(sys.argv):
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    import jax

    jax.config.update("jax_platforms", "cpu")

import mxnet_tpu as mx


def add_fit_args(parser):
    """Parity common/fit.py:45."""
    parser.add_argument("--network", type=str, default=None)
    parser.add_argument("--num-layers", type=int, default=50)
    parser.add_argument("--num-group", type=int, default=32,
                        help="resnext cardinality")
    parser.add_argument("--ctx", type=str, default="tpu",
                        choices=["tpu", "cpu", "gpu"])
    parser.add_argument("--num-devices", type=int, default=1)
    # "auto": single device -> no kvstore; multi-device -> 'device' (the
    # fused in-XLA allreduce path); multi-process -> dist_device_sync.
    # The reference auto-upgrades the same way (model.py _create_kvstore);
    # defaulting to 'local' silently kept multi-device runs off the
    # flagship fused path (round-2 finding).
    parser.add_argument("--kv-store", type=str, default="auto")
    parser.add_argument("--num-epochs", type=int, default=10)
    parser.add_argument("--lr", type=float, default=0.1)
    parser.add_argument("--lr-factor", type=float, default=0.1)
    parser.add_argument("--lr-step-epochs", type=str, default="")
    parser.add_argument("--optimizer", type=str, default="sgd")
    parser.add_argument("--mom", type=float, default=0.9)
    parser.add_argument("--wd", type=float, default=1e-4)
    parser.add_argument("--batch-size", type=int, default=128)
    parser.add_argument("--disp-batches", type=int, default=20)
    parser.add_argument("--model-prefix", type=str, default=None)
    parser.add_argument("--load-epoch", type=int, default=None)
    parser.add_argument("--dtype", type=str, default="float32")
    return parser


def get_context(args):
    if args.ctx == "cpu":
        _force_cpu_backend()
    mk = {"tpu": mx.tpu, "cpu": mx.cpu, "gpu": mx.gpu}[args.ctx]
    if args.num_devices > 1:
        return [mk(i) for i in range(args.num_devices)]
    return mk()


def _force_cpu_backend():
    """Route jax to the host CPU (effective any time before the first
    backend-initializing call, e.g. for scripts whose --ctx DEFAULT is
    cpu and so bypass the argv check above)."""
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass  # backend already initialized; contexts still pick cpu devices


def fit(args, network, train, val=None, **kwargs):
    """Parity common/fit.py:89 — the canonical Module.fit driver."""
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)-15s %(message)s")
    kv_name = args.kv_store
    if kv_name == "auto":
        import os as _os

        if int(_os.environ.get("DMLC_NUM_WORKER",
                               _os.environ.get("JAX_NUM_PROCESSES", 1))) > 1:
            kv_name = "dist_device_sync"
        elif args.num_devices > 1:
            kv_name = "device"
        else:
            kv_name = "local"
    kv = mx.kv.create(kv_name)
    ctx = get_context(args)
    model = mx.mod.Module(network, context=ctx)

    optimizer_params = {
        "learning_rate": args.lr,
        "wd": args.wd,
    }
    if args.optimizer == "sgd":
        optimizer_params["momentum"] = args.mom
    if args.lr_step_epochs:
        epoch_size = kwargs.get("epoch_size") or 1
        steps = [int(e) * epoch_size
                 for e in args.lr_step_epochs.split(",") if e]
        optimizer_params["lr_scheduler"] = (
            mx.lr_scheduler.MultiFactorScheduler(steps,
                                                 factor=args.lr_factor))

    arg_params = aux_params = None
    begin_epoch = 0
    if args.model_prefix and args.load_epoch is not None:
        _, arg_params, aux_params = mx.model.load_checkpoint(
            args.model_prefix, args.load_epoch)
        begin_epoch = args.load_epoch

    checkpoint = (mx.callback.do_checkpoint(args.model_prefix)
                  if args.model_prefix else None)

    model.fit(
        train,
        eval_data=val,
        eval_metric=kwargs.get("eval_metric", "acc"),
        optimizer=args.optimizer,
        optimizer_params=optimizer_params,
        initializer=mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                                   magnitude=2),
        arg_params=arg_params,
        aux_params=aux_params,
        begin_epoch=begin_epoch,
        num_epoch=args.num_epochs,
        batch_end_callback=mx.callback.Speedometer(args.batch_size,
                                                   args.disp_batches),
        epoch_end_callback=checkpoint,
        kvstore=kv,
    )
    return model
