"""Device time of the local-update scan's parameter update per block, in
ms: operations under ``local_update/.../apply`` (the gradient transform
and the step), on the device with the most (``scopes.py``)."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.per_block_ms(ctx, lambda s, d: s.buckets[d]["update"])
