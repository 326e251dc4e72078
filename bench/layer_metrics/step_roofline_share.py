"""The least time the chip could take for a step — required operations
over the bf16 peak; the step is compute-bound at these shapes — as a
share of the device-busy time the step took."""


def compute(trace, counters, run):
    if (not trace or 0 not in trace["devices"] or not run.get("peak")
            or not run.get("trace_steps")):
        return None
    busy = trace["devices"][0]["busy_s"] / run["trace_steps"]
    least = (run["flops_multiplier"] * run["forward_flops_per_sample"]
             * run["batch"] / run["chips"] / run["peak"]["bf16_flops"])
    return 100.0 * least / busy if busy > 0 else None
