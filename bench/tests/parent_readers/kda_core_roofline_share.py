"""The least time the chip could take for the KDA layers' delta rules of
a step — the larger of their required operations over the bf16 peak and
their required bytes over the HBM peak (``flops/kimi_linear_symbol``:
``kda_core_flops``, the recurrence's own 7 K V operations a token and
head, not a chunk form's, and ``kda_core_bytes``, ``q``, ``k``, ``v``,
the decay's K pre-activations and a write strength in and ``o`` out,
once), forward and backward (three forwards of each), every KDA layer —
as a share of the ``delta_rule`` scope's device time. Bound by bytes by
the count (0.41 ms a layer forward against 0.15 ms of operations at T
8,192). The same work whatever computes the rule: the ``jax.numpy``
chunk form writes its decayed operands, tables, systems and partial
results to HBM many times over, which is what this share is low by, and
a later kernel is read against the same count."""
import gdn_scopes
import kda_scopes


def compute(trace, counters, run):
    flops = kda_scopes.kimi_flops(run)
    if not flops or not run.get("peak"):
        return None
    busy_ms = gdn_scopes.ms(trace, run, "delta_rule")
    if not busy_ms:
        return None
    cfg, peak = run["cfg"], run["peak"]
    per_step = (run["flops_multiplier"] * flops.kda_layers(cfg)
                * run["batch"] / run["chips"])
    least_s = per_step * max(
        flops.kda_core_flops(cfg) / peak["bf16_flops"],
        flops.kda_core_bytes(cfg) / peak["hbm_bytes_s"])
    return 100.0 * 1e3 * least_s / busy_ms
