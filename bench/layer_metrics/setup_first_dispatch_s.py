"""Seconds inside ``train_step.first_dispatch`` spans before the
window opened: the call of each new signature, which traces, lowers and
compiles or loads the fused step. ``telemetry.cost_capture`` opens
after it and is not inside it. None where the program opens no such
span."""
import setup_phases


def compute(trace, counters, run):
    return setup_phases.term(run, "first_dispatch")
