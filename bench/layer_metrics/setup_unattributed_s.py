"""``setup_s`` less the five terms that have an entry (runtime, import,
init_params, init_optimizer, first_dispatch) and less the harness's own
interval ``open_t - first_step_t``: symbol building, ``Module(...)``,
``module.bind`` and ``telemetry.cost_capture`` (0.03-0.3 s together:
spans without an entry), the resident batch, ``fit``'s preamble, the
first step's execution and its metric. Judged on the value less those
two spans, which something does cover: fails the run below -0.5 s (an
interval counted twice) and, once jax's own seconds outside every span
are taken out (``jit.seconds{under="-"}``: the harness's batch program),
above the larger of 2 s and 5% of ``setup_s`` (something of size
without a span)."""
import setup_phases


def compute(trace, counters, run):
    return setup_phases.remainder(run)
