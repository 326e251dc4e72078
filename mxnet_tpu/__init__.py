"""mxnet_tpu: a TPU-native framework with the capability surface of
pre-Gluon Apache MXNet 0.9.5 (reference: Johnqczhang/mxnet), built on
JAX/XLA/Pallas/pjit.

Usage mirrors the reference's ``import mxnet as mx``:

    import mxnet_tpu as mx
    a = mx.nd.ones((2, 3), ctx=mx.tpu())
    data = mx.sym.Variable('data')
    net = mx.sym.FullyConnected(data, num_hidden=10)
    mod = mx.mod.Module(net, context=mx.tpu())
"""
import time as _time

_IMPORT_T0 = _time.perf_counter()  # telemetry.enable() publishes both

import jax as _jax

# float64 NDArrays are part of the reference API surface (mshadow DType
# switch); jax disables x64 by default — enable it before backend init.
# Weak typing keeps python-scalar arithmetic from promoting float32 arrays.
_jax.config.update("jax_enable_x64", True)

from .base import MXNetError, __version__
from .context import Context, cpu, gpu, tpu, current_context

from . import base
from . import ndarray
from . import ndarray as nd
from . import symbol
from . import symbol as sym
from . import symbol as symbol_doc  # reference keeps this alias
from . import ops
from . import executor
from . import operator
from . import autograd
from . import random
from . import random as rnd
from .attribute import AttrScope
from .name import NameManager, Prefix
from .executor import Executor

from . import initializer
from . import initializer as init
from . import optimizer
from . import lr_scheduler
from . import metric
from . import io
from . import io_pipeline
from . import recordio
from . import kvstore as kvs
from .kvstore import create as _kv_create
from . import kvstore
from . import callback
from . import monitor
from . import module
from . import module as mod
from . import rnn
from . import image
from . import profiler
from . import telemetry
from . import resilience
from . import visualization
from . import visualization as viz
from . import model
from .model import FeedForward
from . import test_utils
from . import engine
from . import parallel
from . import contrib
from . import executor_manager
from . import kvstore_server
from . import rtc
from . import libinfo
from . import log
from . import predict
from . import serving
from . import torch
from . import torch as th

kv = kvstore

# Parity __init__.py:37: non-worker DMLC roles get their documented no-op
# path at import (the PS tier is subsumed by in-step XLA collectives).
kvstore_server._init_kvstore_server_module()

_IMPORT_SECONDS = _time.perf_counter() - _IMPORT_T0
if telemetry.enabled():  # MXTPU_TELEMETRY=1: on before the second stamp
    telemetry.setup.publish_import()
