"""Busy milliseconds of device 0 per step in the ``sscan`` scope of the
``Mamba1`` nodes (``ssm/<node>/.../sscan``): the selective scan, a decay a
channel and a state index, as the kernel pair of ``ops/kernels/sscan.py``
(or the ``jax.numpy`` carried scan) runs it, with the transposes of ``B``,
``C`` and ``A`` and the sums of the backward's partials, forward and
backward together."""
import sscan_scopes


def compute(trace, counters, run):
    return sscan_scopes.ms(trace, run, "sscan")
