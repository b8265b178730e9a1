"""Model FLOP of one block over the traced block time, the chips and their
bf16 peak, in percent.  Model FLOP: ``counts.block_model_flops`` (6 per
matmul parameter per token plus causal attention, no recomputation)."""
from benchmarks.chip import counts


def read(ctx):
    flops = counts.block_model_flops(ctx.cfg, ctx.traffic)
    return 100.0 * flops / (ctx.block_s * ctx.chips
                            * ctx.peaks["bf16_flops"])
