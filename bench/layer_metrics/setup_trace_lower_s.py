"""jax's own trace and lowering seconds before the window opened
(``jit.seconds{phase in (trace, lower)}``), under every span of the
program but ``telemetry.cost_capture``, whose share is labelled as its
own wherever it nests. None where the program counts no such
seconds."""
import setup_phases


def compute(trace, counters, run):
    return setup_phases.trace_lower_s(run)
