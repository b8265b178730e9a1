"""Device time of the Pallas combination kernel per block, in ms: the
summed durations of its operations, found by the kernel's name, on the
device with the most."""
from benchmarks.chip import trace

#: the operation names the combination kernel runs under
PATTERN = r"mix_kernel|diffusion_mix"


def kernel_s_per_block(ctx):
    ns = max(trace.kernel_ns(ctx.events, d, ctx.lo, ctx.hi, PATTERN)
             for d in ctx.devices)
    return ns * 1e-9 / ctx.n_blocks if ns else None


def read(ctx):
    s = kernel_s_per_block(ctx)
    return None if s is None else 1e3 * s
