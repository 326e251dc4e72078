"""Host seconds of the fused step's trace before the window opened
that lie inside ``loss_fn`` (``jit.trace_seconds{part=forward}``): the
symbol program's walk and every node's ``fcompute``, under whatever
autodiff tracers jax runs it. None where the program counts no such
seconds."""
import first_dispatch


def compute(trace, counters, run):
    return first_dispatch.part_s(run, "forward")
