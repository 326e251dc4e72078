"""Idle milliseconds of device 0 per step that fall inside the program's
``module.update`` span (staging the batch and enqueueing the fused
step), outside the input spans."""
import reduce_scopes


def compute(trace, counters, run):
    return reduce_scopes.idle_under_ms(trace, run, "dispatch")
