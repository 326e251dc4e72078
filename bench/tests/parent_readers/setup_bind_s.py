"""Seconds inside ``module.bind`` spans before the window opened: shape
and type inference, the executor group and its ``nd.zeros``. None where
the program opens no such span."""
import parent_setup_phases as setup_phases


def compute(trace, counters, run):
    return setup_phases.term(run, "bind")
