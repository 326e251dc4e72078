"""Host milliseconds one symbol node costs to trace
(``jit.node_trace_seconds``, sum over count, all op classes): what
separates a deep graph of cheap nodes from a shallow one of dear ones.
None where the program observes no node."""
import first_dispatch


def compute(trace, counters, run):
    return first_dispatch.ms_node(run)
