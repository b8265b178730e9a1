"""Device time of the local-update scan's backward pass per block, in ms:
operations under the ``local_update`` scope marked ``transpose(``, less
the recomputed forward, on the device with the most (``scopes.py``)."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.per_block_ms(ctx, lambda s, d: s.buckets[d]["backward"])
