"""Share of the window's steps that went through the fused
``ShardedTrainStep`` (``train_step.steps``). A cell whose configuration
says ``fused`` and reads under 100 took the executor path: its numbers
are of another program, so the run is not correct."""


def compute(trace, counters, run):
    c = counters["telemetry"].get("train_step.steps")
    if not run.get("steps"):
        return None
    share = 100.0 * (c["value"] if c else 0.0) / run["steps"]
    if run["cfg"].get("fused"):
        return share, share >= 100.0, "fused steps %s of %d" % (
            c["value"] if c else 0, run["steps"])
    return share
