"""Device time of the local-update scan's forward pass per block, in ms:
operations under the ``local_update`` scope marked ``jvp(`` and not
``transpose(``, on the device with the most (``scopes.py``)."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.per_block_ms(ctx, lambda s, d: s.buckets[d]["forward"])
