"""The least time the chip could take for the KDA layers' delta rules of
a step — the larger of their required operations over the bf16 peak and
their required bytes over the HBM peak (the configuration's operations
module: ``kda_core_flops``, the recurrence's own 7 K V operations a
token and head, not a chunk form's, and ``kda_core_bytes``, ``q``,
``k``, ``v``, the decay's K pre-activations and a write strength in and
``o`` out, once), forward and backward (three forwards of each), every
KDA layer (``kda_layers``) — as a share of the ``delta_rule`` scope's
device time. Bound by bytes by the count (0.41 ms a layer forward against
0.15 ms of operations at 64 heads and T 8,192). The same required work
whatever computes the rule. It FAILS THE RUN where the ops named
``kda_fwd_`` / ``kda_bwd_`` hold under half of the scope's time
(``solar2_scopes.lowered_to``: a node in the ``jax.numpy`` chunk form
takes four to five times a kernel's time): the share would then be the
chunk form's, and the cell's `why` untrue. None for a configuration
whose operations module counts no KDA core."""
import gdn_scopes
import kda_scopes
import solar2_scopes


def compute(trace, counters, run):
    flops = kda_scopes.kimi_flops(run)
    if not flops or not run.get("peak"):
        return None
    busy_ms = gdn_scopes.ms(trace, run, "delta_rule")
    if not busy_ms:
        return None
    cfg = run["cfg"]
    return solar2_scopes.roofline_share(
        trace, run, flops.kda_layers(cfg), flops.kda_core_flops(cfg),
        flops.kda_core_bytes(cfg), busy_ms, ("kda_fwd", "kda_bwd"))
