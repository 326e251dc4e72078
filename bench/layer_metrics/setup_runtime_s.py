"""Seconds from process start (``run["open_t"] - run["setup_s"]``) to
the first statement of ``mxnet_tpu/__init__.py`` (``process.import_t0``,
same ``perf_counter`` clock): the interpreter, ``import jax``, the
device client. None from a program that publishes no stamp."""
import setup_phases


def compute(trace, counters, run):
    return setup_phases.term(run, "runtime")
