"""Host seconds of the fused step's trace before the window opened
that lie in the optimizer's ``apply`` block under the ``update`` scope
(``jit.trace_seconds{part=update}``). None where the program counts no
such seconds."""
import first_dispatch


def compute(trace, counters, run):
    return first_dispatch.part_s(run, "update")
