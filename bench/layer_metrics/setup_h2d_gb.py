"""GB of host memory handed to ``jax.device_put`` under ``module.bind``,
``module.init_params`` and ``module.init_optimizer`` before the window
opened (``device.h2d_bytes``, the numpy arrays' ``nbytes``). None where
the program counts no such bytes."""
import setup_phases


def compute(trace, counters, run):
    return setup_phases.h2d_gb(run)
