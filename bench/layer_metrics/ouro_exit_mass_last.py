"""The share of tokens that leave after the LAST pass at the window's
last step (``exit_mass[T-1]``, the mean over the step's tokens of ``p_T =
prod_{j<T} (1 - lambda_j)``, from the model's second output as the kind
``fit_tokens_loop`` fetched it): where training has put the exits. At a
fresh Normal(0.02) gate it is 1/8 at four passes; a model that has learnt
that later exits predict better moves it up. It FAILS THE RUN where the
exits' masses do not sum to 1 within 1e-3."""


def compute(trace, counters, run):
    mass = run.get("exit_mass")
    if not mass:
        return None
    total = sum(mass)
    return 100.0 * mass[-1], abs(total - 1.0) <= 1e-3, \
        "exit_mass %s, sum %.6f" % (["%.4f" % m for m in mass], total)
