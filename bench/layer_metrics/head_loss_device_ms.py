"""Busy milliseconds of device 0 per step in the LM head and its loss
(the nodes named ``lm_head*`` and ``loss``: the head matmul, the float32
logits, their log-softmax, the pick and the mean), forward and backward
together."""
import lm_scopes


def compute(trace, counters, run):
    return lm_scopes.ms(trace, run, lambda red: red["head_loss_s"])
