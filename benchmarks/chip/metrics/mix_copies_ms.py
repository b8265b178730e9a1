"""Device time of the copies around the combination kernel per block, in
ms: operations under the ``combine`` scope and ``flatten`` or
``unflatten`` (the parameter stack laid out as one buffer and back), on
the device with the most (``scopes.py``)."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.per_block_ms(ctx, lambda s, d: s.buckets[d]["mix_copies"])
