"""Idle milliseconds of device 0 per step that fall inside the program's
``module.update_metric`` span: the blocking fetch of the step's outputs
and the metric's arithmetic."""
import reduce_scopes


def compute(trace, counters, run):
    return reduce_scopes.idle_under_ms(trace, run, "metric")
