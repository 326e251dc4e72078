"""Busy milliseconds of device 0 per step under the ``BlockSelect`` nodes
(``attn/<node>/blocks``: ``pool``, the pooled keys' means; ``score``, the
group's queries against them, a softmax a head, the sum over the heads and
a block's largest window; ``choose``, the candidates, the choice of the
best blocks (``topk_mask_*``) and the keep-mask a key), every layer that
chooses. Forward only: the choice has no gradient."""
import linblock_scopes


def compute(trace, counters, run):
    return linblock_scopes.ms(trace, run, "blocks")
