"""Busy milliseconds of device 0 per step in the ``scan`` scope of the
state-space nodes (``ssm/<node>/.../scan``): softplus step sizes, log
decays and their running sums, the masked ``(C B^T) * decay`` product
against ``dt x`` inside a chunk, a chunk's end state, the recurrence
over the chunks, the carried state read through ``C`` and the skip,
forward and backward together."""
import ssm_scopes


def compute(trace, counters, run):
    return ssm_scopes.ms(trace, run, "scan")
