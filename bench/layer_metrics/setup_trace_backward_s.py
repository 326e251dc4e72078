"""Host seconds of the fused step's trace before the window opened
that lie in the ``jax.grad`` call less the forward
(``jit.trace_seconds{part=backward}``): linearisation's end,
transposition, and the ``custom_vjp`` backward rules, where a kernel
family's backward body is traced. None where the program counts no such
seconds."""
import first_dispatch


def compute(trace, counters, run):
    return first_dispatch.part_s(run, "backward")
