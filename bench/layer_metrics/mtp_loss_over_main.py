"""The prediction module's cross-entropy (the token after next) over the
main head's (the next token) at the window's last step, from the model's
two loss outputs behind ``BlockGrad`` as the kind fetched them. 1.00 at
the first step on uniform random ids, where neither is predictable; on
the cell's one resident sequence the main loss falls first (2.9 after 52
steps, 1.0 after 70) and the module's, which sees the sequence through a
block of its own fed by a stream that is still moving, follows (8.6, 7.9):
3.0 untraced and 8.1 traced on the chip (PR 69). It says how far the
module lags, and moves with the number of steps a run makes."""


def compute(trace, counters, run):
    parts = run.get("loss_parts")
    if not parts or not parts[0]:
        return None
    return parts[1] / parts[0]
