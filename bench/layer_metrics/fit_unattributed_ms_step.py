"""Own milliseconds of a ``fit.step`` span: its duration less what the
program's spans inside it cover (input, update, metric, callbacks,
bookkeeping) — the fit loop's time that no span names. Mean over the
``fit.step`` spans that lie inside the slice."""
import reduce_scopes


def compute(trace, counters, run):
    if not trace:
        return None
    red = reduce_scopes.of(run)
    if not red or red["fit_self_s"] is None:
        return None
    return 1e3 * red["fit_self_s"]
