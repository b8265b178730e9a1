"""Device time of the attention core per block, in ms: operations under
the ``attention`` scope (scores, softmax and the weighted values; not the
projections) in every pass, so it overlaps the forward, backward and
recompute times, on the device with the most (``scopes.py``)."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.scope_ms(ctx, scopes.ATTENTION)
