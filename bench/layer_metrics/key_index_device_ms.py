"""Busy milliseconds of device 0 per step in the key indexers
(``KeyIndexer``'s scope ``index``: the heads' queries, the one LayerNormed
key a token, the head weights, the two rotations, the blocked [heads,
rows, keys] scores with their ReLU and weighted sum; and ``index/topk``,
the choice of the keys: the k-th largest score of every row by 32
counting passes over the [T, T] scores' bits, the compare, the count),
every layer, whichever attention reads the mask. Forward only: the
indexer has no gradient."""
import select_scopes


def compute(trace, counters, run):
    return select_scopes.ms(trace, run, ("index", "index_topk"))
