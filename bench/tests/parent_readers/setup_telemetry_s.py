"""Seconds inside ``telemetry.cost_capture`` spans before the window
opened: what the traced run's set-up pays that the untraced run's does
not (the step's second lowering, the symbol's cost table). None where
the program opens no such span."""
import parent_setup_phases as setup_phases


def compute(trace, counters, run):
    return setup_phases.term(run, "telemetry")
