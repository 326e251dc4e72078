"""Seconds inside ``module.init_params`` spans before the window
opened: the initializers and the copies into the executor group. None
where the program opens no such span."""
import setup_phases


def compute(trace, counters, run):
    return setup_phases.term(run, "init_params")
