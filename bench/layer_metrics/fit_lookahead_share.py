"""Share of the window's steps whose metric fetch began with a later
step already enqueued (``fit.lookahead_steps``): 100 when ``Module.fit``
keeps one fused step in flight, 0 on a path that fetches each step's
metric before it dispatches the next. A program without the counter
reports nothing."""


def compute(trace, counters, run):
    c = counters["telemetry"].get("fit.lookahead_steps")
    if c is None or not run.get("steps"):
        return None
    return 100.0 * c["value"] / run["steps"]
