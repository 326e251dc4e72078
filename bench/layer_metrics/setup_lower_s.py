"""jax's lowering seconds before the window opened
(``jit.seconds{phase=lower}``: jaxpr to MLIR module, Mosaic's kernels
among it), under every span of the program but
``telemetry.cost_capture``. One of the five terms of
``first_dispatch``'s partition: None where the program does not count
the step's trace by part."""
import first_dispatch


def compute(trace, counters, run):
    return first_dispatch.lower_s(run)
