"""A kernel alone on the chip: what the scripts of this directory share.

Each script beside this file times one Pallas kernel (or kernel pair) of
`mxnet_tpu/ops/kernels/` alone, at the shapes its cells of `BENCHMARK.json`
call it with, against the form it replaced (the `jax.numpy` form, XLA's own
op). That comes before the kernel goes into a step: a kernel that is not
faster alone by shape will not be faster in a cell, and a chip call of one
script costs a minute where a cell costs ten. The benchmark of the system
is `bench/`; nothing here is an end-to-end number.

A script holds what is the kernel's own: its shapes (the cells' and a toy
one for the rehearsal), its forms as jitted callables, the operations or
bytes a call requires (counted from the shapes), and the rows it prints.
Everything else is a `Run`:

    run = alone.Run(__file__)          # the platform rule, the peaks
    t = 256 if run.rehearse else 8192  # toy shapes for the rehearsal
    ms = run.host_ms(f, *args)         # host clock, closed by a fetch
    ops = run.device_ops(f, *args)     # device 0's ops from a trace
    kernels = alone.by_kernel(ops, "flash_")
    run.row(form="kernel", fwd_ms=ms, kernels_device_ms=kernels,
            share=alone.ratio(run.bound(flops=n), kernels.get(...), 100))
    run.save(shape=dict(t=t))          # chiprun_out/<script>.json

**The platform rule** is the benchmark's (`bench/run.py`, `chip_smoke.py`).
On a TPU a script runs its real shapes, prints one JSON line a row and
writes `chiprun_out/<script>.json`. `--rehearse-cpu` runs the same flow
here at the toy size, through the kernels' own branch for other platforms
(`ops/kernels/common.on_tpu`; a bare `*_call` takes
`interpret=run.rehearse`): every row says `platform` `cpu`, there is no
peak, no trace and so no bound and no share, times mean nothing and no
file is written. It proves the script, not a number. Without a TPU and
without the flag, or with the flag on a TPU, a script exits 2.

**To add a script** (a kernel PR): name it after the op, build a `Run`
first, choose toy shapes that rehearse in seconds, give the forms stable
kernel names so `by_kernel` finds them, count required operations or
bytes from the shapes and leave the peaks to `Run.bound`, measure the
forms in the order `Run.alternate` gives, and add the script's name to
`SCRIPTS` in `tests/test_kernels_alone.py`. `PERF.md` holds the tables,
each with the PR whose chip run it is.
"""
from __future__ import annotations

import collections
import glob
import json
import os
import sys
import tempfile
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "bench")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

import reduce_scopes  # noqa: E402  (bench/: what reads a cell's trace)
import reduce_trace  # noqa: E402
from mxnet_tpu.telemetry import costmodel  # noqa: E402

REST = "everything else"


def op_name(text):
    """An op's name out of its HLO text (`%name.3 = ...`)."""
    return text.split(" = ")[0].lstrip("%")


def busy_ms(ops):
    """The device's busy ms a call over ``Run.device_ops``'s list; None
    where it is empty (a rehearsal)."""
    return sum(own for _, own, _ in ops) if ops else None


def by_kernel(ops, *prefixes):
    """``Run.device_ops``'s ms a call by kernel name (an op named
    ``prefixes``..., up to its first dot) and, under ``REST``, of every
    other op of the program."""
    ms = collections.Counter()
    for text, own, _ in ops:
        name = op_name(text)
        ms[name.split(".")[0] if name.startswith(prefixes) else REST] += own
    return dict(ms)


def named(ms, prefix):
    """The sum of ``by_kernel``'s entries named ``prefix``..."""
    return sum(v for k, v in ms.items() if k.startswith(prefix))


def ratio(a, b, scale=1.0):
    """``scale * a / b``; None where either is missing (a rehearsal has
    no peak and no device time; a form may have no such kernel)."""
    return scale * a / b if a is not None and b else None


class Run:
    """One run of one script: the platform rule, the peaks of the device,
    the clocks, the rows and the file they go to."""

    def __init__(self, script, argv=None):
        argv = sys.argv[1:] if argv is None else argv
        self.name = os.path.splitext(os.path.basename(script))[0]
        self.rehearse = "--rehearse-cpu" in argv
        dev = jax.devices()[0]
        self.platform, self.kind = dev.platform, str(dev.device_kind)
        self.peak_flops = self.peak_bytes = None
        if not self.rehearse:
            self.peak_flops = costmodel.peak_flops_for_kind(self.kind)
            self.peak_bytes = costmodel.peak_bytes_for_kind(self.kind)
        on_tpu = self.platform == "tpu"
        if on_tpu if self.rehearse else not (
                on_tpu and self.peak_flops and self.peak_bytes):
            print("%s: JAX found platform=%s kind=%r: without --rehearse-cpu "
                  "this needs a TPU of a kind in telemetry/costmodel.py's "
                  "tables, with it a host that has none. There is no CPU "
                  "branch." % (self.name, self.platform, self.kind),
                  file=sys.stderr)
            sys.exit(2)
        self.rows = []

    # -- what a call must take -------------------------------------------

    def bound(self, flops=0, nbytes=0, per=1e-3):
        """The least time the device could take over ``flops`` operations
        (bf16) and ``nbytes`` of HBM traffic, the larger of the two, in
        units of ``per`` seconds (ms); None in a rehearsal."""
        if self.rehearse:
            return None
        return max(flops / self.peak_flops, nbytes / self.peak_bytes) / per

    # -- the clocks ------------------------------------------------------

    def host_ms(self, f, *args, reps=20):
        """Host-clock ms a call of ``f(*args)``: two warm calls, then
        ``reps`` calls closed by a fetch (a rehearsal: one and one). The
        warm calls are closed the same way, so that the fetch's own two
        small programs are compiled before the clock starts."""
        def close(r):
            jax.block_until_ready(r)
            np.asarray(jax.tree_util.tree_leaves(r)[0].ravel()[:1])

        warm, reps = (1, 1) if self.rehearse else (2, reps)
        for _ in range(warm):
            close(f(*args))
        t0 = time.perf_counter()
        for _ in range(reps):
            r = f(*args)
        close(r)
        return (time.perf_counter() - t0) / reps * 1e3

    def device_ops(self, f, *args, reps=5, scopes=False):
        """Device 0's ops over a profiled run of ``reps`` calls after a
        warm one, as ``bench/reduce_trace.py`` loads them:
        [(HLO text, own ms a call, scope path)]. An op's own time is its
        length less what the ops inside it cover
        (``reduce_scopes.self_times``), so the list sums to the busy
        time; the scope path (``reduce_scopes.scope_names``) is read
        under ``scopes`` and "" otherwise. A rehearsal makes the warm
        call and returns no op."""
        jax.block_until_ready(f(*args))
        if self.rehearse:
            return []
        with tempfile.TemporaryDirectory() as where:
            with jax.profiler.trace(where):
                for _ in range(reps):
                    r = f(*args)
                jax.block_until_ready(r)
            path, = glob.glob(os.path.join(
                where, "plugins", "profile", "*", "*.xplane.pb"))
            ops = reduce_trace.load(path)["devices"][0]["ops"]
            names = (reduce_scopes.scope_names(path).get(0, {})
                     if scopes else {})
        return [(text, own / 1e6 / reps, names.get(text) or "")
                for text, own in reduce_scopes.self_times(
                    [(n, s, s + d) for n, s, d in ops])]

    def alternate(self, forms, rounds=3):
        """The (name, form) pairs of ``forms`` in turn, ``rounds`` times
        over (a rehearsal: once): forms measured so share a drift of the
        machine, and a script reports each form's readings sorted."""
        for _ in range(1 if self.rehearse else rounds):
            yield from forms.items()

    # -- the rows --------------------------------------------------------

    def row(self, **kw):
        """Print one row as a JSON line and keep it for ``save``."""
        if self.rehearse:
            kw["platform"] = self.platform
        print(json.dumps(kw), flush=True)
        self.rows.append(kw)

    def save(self, table=None, **head):
        """On the chip, write the rows under ``head`` to
        ``chiprun_out/<script>.json`` (``<script>_<table>.json`` for a
        script's other table); a rehearsal writes nothing."""
        if self.rehearse:
            return
        os.makedirs("chiprun_out", exist_ok=True)
        name = self.name + ("_" + table if table else "")
        with open(os.path.join("chiprun_out", name + ".json"), "w") as f:
            json.dump(dict(device=self.kind, platform=self.platform, **head,
                           rows=self.rows), f, indent=1)
