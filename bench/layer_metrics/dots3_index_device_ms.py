"""Busy milliseconds of device 0 per step in the full-attention layers'
indexers (``KeyIndexer``'s scope ``index``: the 64 heads' queries up from
the query latent, the one LayerNormed key a token, the head weights, the
two rotations, the blocked [heads, rows, keys] scores with their ReLU and
weighted sum, and the choice of the keys, ``dots3_index_topk_device_ms``'
part). Forward only: the indexer has no gradient."""
import dots3_scopes


def compute(trace, counters, run):
    return dots3_scopes.ms(trace, run, "index", "index_topk")
