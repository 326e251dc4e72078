"""Runtime kernel compilation — ``mx.rtc`` parity, Pallas edition.

Parity: reference ``python/mxnet/rtc.py`` + ``src/common/mxrtc.cc``
(N15): users hand the framework a CUDA C kernel *source string* at
runtime; it is NVRTC-compiled once, cached, and launched with
``push(ins, outs, grid, block)``.

TPU-native redesign: the kernel source is the BODY of a Pallas TPU
kernel instead of CUDA C. Parameter refs are in scope as ``<name>_ref``
(inputs first, then outputs) plus ``pl`` (jax.experimental.pallas),
``pltpu``, ``jnp`` and ``np``. Compilation is Mosaic instead of NVRTC,
the compile cache is keyed on (source, shapes, dtypes) exactly like the
reference's kernel-name cache, and off-TPU the same kernel runs under
the Pallas interpreter so RTC code is portable to tests.

``grid_dims`` maps to the Pallas ``grid``; ``block_dims`` has no
meaning on a TPU (Mosaic owns the on-chip tiling) and is accepted and
ignored for signature parity.

Example::

    x = mx.nd.ones((8, 128))
    y = mx.nd.zeros((8, 128))
    k = mx.rtc.Rtc('axpy', [('x', x)], [('y', y)],
                   "y_ref[...] = x_ref[...] * 2.0")
    k.push([x], [y], (1, 1, 1), (1, 1, 1))
"""
from __future__ import annotations

import textwrap

import jax
import numpy as np

from .base import MXNetError
from .ndarray import NDArray


class Rtc(object):
    def __init__(self, name, inputs, outputs, kernel):
        self.name = name
        self.in_names = [n for n, _ in inputs]
        self.out_names = [n for n, _ in outputs]
        self.kernel_source = kernel
        self._cache = {}

        ref_args = [n + "_ref" for n in self.in_names + self.out_names]
        src = "def _rtc_kernel(%s):\n%s" % (
            ", ".join(ref_args),
            textwrap.indent(textwrap.dedent(kernel), "    ") or "    pass",
        )
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        namespace = {"pl": pl, "pltpu": pltpu, "jnp": jnp, "np": np,
                     "jax": jax}
        try:
            exec(compile(src, "<rtc:%s>" % name, "exec"), namespace)
        except SyntaxError as e:
            raise MXNetError("Rtc %s: invalid kernel source: %s" % (name, e))
        self._kernel = namespace["_rtc_kernel"]
        self._pl = pl

    def _compiled(self, in_shapes, in_dtypes, out_shapes, out_dtypes, grid,
                  platform):
        key = (in_shapes, in_dtypes, out_shapes, out_dtypes, grid,
               platform)
        fn = self._cache.get(key)
        if fn is None:
            # Mosaic on the chip, the Pallas interpreter elsewhere —
            # decided by where the inputs live, not the process default
            interpret = platform != "tpu"
            kwargs = {} if grid is None else {"grid": grid}
            call = self._pl.pallas_call(
                self._kernel,
                out_shape=[
                    jax.ShapeDtypeStruct(s, d)
                    for s, d in zip(out_shapes, out_dtypes)
                ],
                interpret=interpret,
                **kwargs,
            )
            fn = jax.jit(call)
            self._cache[key] = fn
        return fn

    def push(self, ins, outs, grid_dims=(1, 1, 1), block_dims=None):
        """Run the kernel. ``ins``/``outs`` are NDArray lists matching the
        constructor templates; results are written into ``outs``."""
        del block_dims  # no thread-block concept on TPU (Mosaic tiles)
        if len(ins) != len(self.in_names) or len(outs) != len(self.out_names):
            raise MXNetError("Rtc %s: wrong number of arrays" % self.name)
        # strip only TRAILING unit dims: interior 1s must survive or
        # pl.program_id axis numbering shifts under the kernel
        grid = tuple(int(g) for g in grid_dims)
        while grid and grid[-1] == 1:
            grid = grid[:-1]
        grid = grid or None
        in_vals = [a._data if isinstance(a, NDArray) else a for a in ins]
        fn = self._compiled(
            tuple(tuple(v.shape) for v in in_vals),
            tuple(str(v.dtype) for v in in_vals),
            tuple(tuple(o.shape) for o in outs),
            tuple(str(np.dtype(o.dtype)) for o in outs),
            grid,
            next(iter((in_vals[0] if in_vals else outs[0]._data)
                      .devices())).platform,
        )
        results = fn(*in_vals)
        for o, r in zip(outs, results):
            o[:] = np.asarray(r)
        return outs


def rtc(name, inputs, outputs, kernel):
    """Functional alias mirroring ``mx.rtc.Rtc``."""
    return Rtc(name, inputs, outputs, kernel)
