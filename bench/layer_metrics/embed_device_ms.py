"""Busy milliseconds of device 0 per step in ops whose scope's class is
``embed`` (the Embedding op): the lookup and its backward, which is the
table's gradient (a scatter-add of the cotangent's rows before PR 50, a
sort, a gather and a sorted segment product since)."""
import lm_scopes


def compute(trace, counters, run):
    return lm_scopes.class_ms(trace, run, "embed")
