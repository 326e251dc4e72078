"""Idle milliseconds of device 0 per step that fall inside the program's
``fit.input`` and ``io.feed_fill`` spans: the device waited while the fit
loop waited for, or staged, a batch."""
import reduce_scopes


def compute(trace, counters, run):
    return reduce_scopes.idle_under_ms(trace, run, "input")
