"""Seconds inside ``module.init_optimizer`` spans before the window
opened, with ``module.fused_build``, ``train_step.place_params`` and
``train_step.make_state`` inside them (the kvstore, the fused trainer,
the parameters and the optimizer state placed on the mesh). None where
the program opens no such span."""
import setup_phases


def compute(trace, counters, run):
    return setup_phases.term(run, "init_optimizer")
