"""jax's trace seconds before the window opened
(``jit.seconds{phase=trace}``) less the step's forward, backward and
update parts: the rest of the body (the AMP casts, the gradient pins,
the guard), jax's own work round the body, and the traces under
roots where no step is traced (the initializers' programs, the
optimizer state's). Fails the run where, under the roots that trace a
step, it is below -0.5 s (two parts counted one interval twice) or above
the larger of 1 s and 15% of those roots' trace seconds (a site of size
has no part)."""
import first_dispatch


def compute(trace, counters, run):
    return first_dispatch.remainder(run)
