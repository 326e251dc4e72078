"""Busy milliseconds of device 0 a WHOLE step in ops whose scope's class
is ``conv`` and whose pass is ``wgrad``: the filter-gradient convolutions
(scope ``wgrad`` inside the node's, ``ops/nn.py::_conv_named_grads``) of
every ``Convolution`` node, with the optimizer's update that XLA fuses
into their epilogue. Whole steps as ``conv_fwd_device_ms``; None where the
program names no gradient, never 0.

Fails the run where backward time of class ``conv`` that carries neither
gradient's name is over 1% of the two that do: some path left a gradient
convolution unnamed. The detail says the whole steps counted and the
periods the slice held (what the ``trace_steps`` readers should divide
by)."""
import conv_scopes


def compute(trace, counters, run):
    value = conv_scopes.pass_ms(trace, run, "wgrad")
    if value is None:
        return None
    ok, why = conv_scopes.unnamed_check(conv_scopes.of(run))
    return value, ok, why
