"""``setup_s`` less the seven terms the program names (runtime, import,
bind, init_params, init_optimizer, first_dispatch, telemetry) and less
the harness's own interval ``open_t - first_step_t``: symbol building,
``Module(...)``, the resident batch, ``fit``'s preamble, the first
step's execution and its metric. Fails the run below -0.5 s (an
interval counted twice) and, once jax's own seconds outside every span
are taken out (``jit.seconds{under="-"}``: the harness's batch program),
above the larger of 2 s and 5% of ``setup_s`` (something of size
without a span)."""
import parent_setup_phases as setup_phases


def compute(trace, counters, run):
    return setup_phases.remainder(run)
