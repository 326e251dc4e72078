"""Load imbalance of the expert layers in the last step of the window:
the busiest expert's row count over the mean, the largest over the
layers. The counts are a model output (``TopKMoE``'s second output behind
``BlockGrad``), fetched once after ``fit`` returns. 1.0 is a perfectly
even routing, experts / experts-per-token every token on the same
experts. It describes the traffic more than the code: with seeded
Normal(0.02) weights over 4096 uniform random tokens the causal
attention's running mean is a component shared by all late positions
and as large as a token's own embedding, so the routers of layers past
the first send nearly every token to the same experts before any
training (the float32 reference reads 3.4 / 7.9 / 8.0 at the seeded
weights; PERF.md section 6). A mix whose routing is a trained model's
would read near 1."""


def compute(trace, counters, run):
    counts = run.get("expert_counts")
    if not counts:
        return None
    return max(max(layer) * len(layer) / float(sum(layer))
               for layer in counts)
