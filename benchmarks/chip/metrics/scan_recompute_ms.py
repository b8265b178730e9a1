"""Device time of the forward pass recomputed under ``jax.checkpoint`` in
the local-update scan per block, in ms: operations under the
``local_update`` scope marked ``rematted_computation``, on the device with
the most (``scopes.py``)."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.per_block_ms(ctx, lambda s, d: s.buckets[d]["recompute"])
