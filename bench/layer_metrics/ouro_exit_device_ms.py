"""Busy milliseconds of device 0 per step in what follows the looped
stack's passes, the final norms apart: the head after every pass
(``loop<t>_lm_head``, its float32 cast, ``log_softmax`` and ``pick``),
the exit gate (``loop<t>_exit_gate``), the exit mixing (``exit_gates``,
``exit_nll``, ``exit_mix``, ``exit_loss_mean``, ``exit_mass``) and
``loss``, forward and backward together: four heads over the held
vocabulary where a plain stack has one."""
import ouro_scopes


def compute(trace, counters, run):
    return ouro_scopes.ms(trace, run, "exit")
