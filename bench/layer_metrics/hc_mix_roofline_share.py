"""The least time the chip could take for the residual streams' mixing of
a step — the bytes it MUST move, counted from shapes by the
configuration's operations module (``hc_mix_bytes``: every sub-layer's
read and write, forward and backward, each array once in the model's
dtype; the same number whatever implements the mixing), over the chip's
memory bandwidth — as a share of the device time of EVERY pass over the
streams: the scopes ``hc_mix`` and ``hc_coeff`` together (and any op of an
``hc`` node under neither). The coefficients' pass is in the time and
adds no byte to the count because a mixing at its bound would fold it
into the read's pass; and XLA roots fusions that straddle the two scopes
in either (the stream's three cotangents are summed in the fusion that
ends the coefficient products' transpose: ``hc_mix`` alone read 10.9 ms
against 17.6 of bytes on the chip, PR 69). The normalising iterations
touch no stream and are left out (``hc_sinkhorn_device_ms``).
Memory-bound by the count: 4 + 4 multiply-adds a stream element against 2
bytes. A second pass over the stream, a cotangent summed apart or a
padded layout can only lower it."""
import hc_scopes
import share_scopes


def compute(trace, counters, run):
    flops = share_scopes.flops_of(run)
    count = flops and getattr(flops, "hc_mix_bytes", None)
    mix_ms = hc_scopes.ms(trace, run, "hc_mix")
    if not count or not mix_ms or not run.get("peak"):
        return None
    busy_ms = mix_ms + sum(hc_scopes.ms(trace, run, part) or 0.0
                           for part in ("hc_coeff", "hc_other"))
    least_s = (count(run["cfg"]) * run["batch"] / run["chips"]
               / run["peak"]["hbm_bytes_s"])
    return 100.0 * 1e3 * least_s / busy_ms
