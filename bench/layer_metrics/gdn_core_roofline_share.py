"""The least time the chip could take for the linear-attention layers'
delta rules of a step — the larger of their required operations over the
bf16 peak and their required bytes over the HBM peak
(``flops/olmo_hybrid_symbol``: ``core_flops``, the recurrence's own 7 K V
operations a token and head, not a chunk form's, and ``core_bytes``,
operands in and result out once), forward and backward (three forwards
of each), every linear-attention layer — as a share of the
``delta_rule`` scope's device time. Bound by bytes by the count (0.17 ms
a layer forward against 0.08 ms of operations at T 4,096): the rule as
``jax.numpy`` writes its decay tables, systems and partial results to
HBM many times over, which is what this share is low by."""
import gdn_scopes
import share_scopes


def compute(trace, counters, run):
    flops = share_scopes.flops_of(run)
    busy_ms = gdn_scopes.ms(trace, run, "delta_rule")
    if (not busy_ms or not run.get("peak")
            or not getattr(flops, "core_flops", None)):
        return None
    cfg, peak = run["cfg"], run["peak"]
    per_step = (run["flops_multiplier"] * flops.layers(cfg, flops.LINEAR)
                * run["batch"] / run["chips"])
    least_s = per_step * max(flops.core_flops(cfg) / peak["bf16_flops"],
                             flops.core_bytes(cfg) / peak["hbm_bytes_s"])
    return 100.0 * 1e3 * least_s / busy_ms
