"""Share of device 0's busy time in convolution and dot ops and in the
fusions that hold one (kind kOutput on the TPU; see
``reduce_trace.op_class``)."""


def compute(trace, counters, run):
    if not trace or 0 not in trace["devices"]:
        return None
    d = trace["devices"][0]
    if d["busy_s"] <= 0:
        return None
    return 100.0 * d["by_class_s"].get("convolution", 0.0) / d["busy_s"]
