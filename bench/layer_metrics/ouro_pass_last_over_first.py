"""Device time of the last pass's layer nodes (``loop<T>_layer<i>_*``)
over the first pass's (``loop1_layer<i>_*``), forward and backward
together, from the scopes: the four passes run the same layers on the
same shapes, so 1.0 within a few percent unless the compiler treats a
pass differently (keeps one pass's activations and recomputes
another's, fuses one pass's norms and not another's). It FAILS THE RUN
outside [0.8, 1.25], or where the scopes name another number of passes
than ``total_ut_steps``."""
import ouro_scopes

LOW, HIGH = 0.8, 1.25


def compute(trace, counters, run):
    if not trace or not run.get("trace_steps") \
            or not ouro_scopes.ouro_flops(run):
        return None
    red = ouro_scopes.of(run)
    if not red:
        return None
    by_pass = red["pass_s"]
    passes = run["cfg"]["total_ut_steps"]
    first, last = by_pass.get(1), by_pass.get(passes)
    if sorted(by_pass) != list(range(1, passes + 1)) or not first:
        return 0.0, False, "the scopes name passes %s, want 1..%d" % (
            sorted(by_pass), passes)
    ratio = last / first
    steps = run["trace_steps"]
    return ratio, LOW <= ratio <= HIGH, "ms a step by pass %s, last over " \
        "first %.4f, want %s..%s" % (
            [round(1e3 * by_pass[k] / steps, 3) for k in sorted(by_pass)],
            ratio, LOW, HIGH)
