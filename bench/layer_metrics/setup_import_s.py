"""Seconds of the package's own import (``process.import_seconds``:
first to last statement of ``mxnet_tpu/__init__.py``). None from a
program that publishes no stamp."""
import setup_phases


def compute(trace, counters, run):
    return setup_phases.term(run, "import")
